"""Seeded random weights for a configuration, made without a dense tree.

Every packed linear is drawn in canonical GQSA form from the
configuration's weight seed: 4-bit codes, a row-balanced group mask that
keeps ``groups_kept_per_row`` groups of each row, and a scale and zero per
group. The program's own packer (``repro.core.bsr.pack_quantized`` and
``stack_bsr``) turns them into the served layout, so a change to that
layout flows through here with no edit. The plain reference
(``bench/references``) draws the same canonical arrays from the same
keys and dequantizes them itself; it never reads the packed tree.

Packing runs on the host, spread over worker processes, and the packed
tree is kept in a cache inside the checkout, keyed by configuration,
weight seed and a digest of the packer's sources, so only the first run
of a checkout packs. Unpacked leaves (embedding, untied head, norms) are
made on the device in one jitted call, in the leaf dtype the
configuration states.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import shutil
import time
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
SRC = os.path.join(CHECKOUT, "src")

# stable fold-in ids: a linear's key is fold_in(fold_in(seed, layer), id)
LINEAR_IDS = {"wq": 0, "wk": 1, "wv": 2, "wo": 3, "wg": 4, "wu": 5, "wd": 6}
EMBED_ID, HEAD_ID = 10_000, 10_001
# std of (q - z) for codes uniform on 0..15: sqrt((16^2 - 1) / 12)
CODE_STD = 4.6098
EMBED_STD = 0.02


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes of a dense GQA decoder, read from a configuration file
    (Hugging Face ``config.json`` key names)."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    tied: bool
    rope_theta: float
    eps: float

    @classmethod
    def from_conf(cls, m: Dict) -> "Model":
        act = m["hidden_act"]
        if act not in ("silu", "gelu_pytorch_tanh"):
            raise ValueError(f"hidden_act {act!r}: the dense transformer "
                             "runs SwiGLU (silu) or tanh-GELU MLPs")
        return cls(layers=m["num_hidden_layers"], d=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"],
                   head_dim=m.get("head_dim") or
                   m["hidden_size"] // m["num_attention_heads"],
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                   gated=act == "silu",
                   tied=bool(m.get("tie_word_embeddings", False)),
                   rope_theta=float(m["rope_theta"]),
                   eps=float(m.get("rms_norm_eps", m.get("norm_epsilon"))))

    def linears(self) -> List[Tuple[str, str, int, int]]:
        """(block, name, n_out, k_in) of every packed linear of a layer."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        out = [("attn", "wq", q, self.d), ("attn", "wk", kv, self.d),
               ("attn", "wv", kv, self.d), ("attn", "wo", self.d, q)]
        if self.gated:
            out.append(("mlp", "wg", self.d_ff, self.d))
        out += [("mlp", "wu", self.d_ff, self.d),
                ("mlp", "wd", self.d, self.d_ff)]
        return out


@dataclasses.dataclass(frozen=True)
class Compression:
    bits: int
    sparsity: float
    group_size: int

    @classmethod
    def from_conf(cls, c: Dict) -> "Compression":
        if c.get("method") != "gqsa" or not c.get("row_balanced", True):
            raise ValueError("only row-balanced GQSA compression is built")
        if c["bits"] != 4:
            raise ValueError("codes are drawn as 4-bit nibbles")
        return cls(bits=4, sparsity=float(c["sparsity"]),
                   group_size=int(c["group_size"]))

    def kept(self, k: int) -> int:
        """Groups kept per row: round(K/G * (1 - sparsity)), at least 1,
        as the program's row-balanced pruning keeps them."""
        return max(1, int(round(k // self.group_size
                                * (1.0 - self.sparsity))))


def linear_key(seed: int, layer: int, name: str):
    base = jax.random.fold_in(jax.random.PRNGKey(seed), layer)
    return jax.random.fold_in(base, LINEAR_IDS[name])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def canonical(key, n: int, k: int, g: int, m: int):
    """One linear in canonical form: codes [N, K] uint8 in 0..15, group
    mask [N, K/G] bool with exactly ``m`` True per row, scale and zero
    [N, K/G] f32. Integer draws and exact float arithmetic only, so the
    CPU (packing) and the chip (reference) draw identical arrays."""
    kc, km, ks, kz = jax.random.split(key, 4)
    c = k // g
    codes = jax.random.bits(kc, (n, k), jnp.uint8) & jnp.uint8(0xF)
    order = jnp.argsort(jax.random.bits(km, (n, c), jnp.uint32), axis=1,
                        stable=True)[:, :m]
    gmask = jnp.zeros((n, c), bool).at[jnp.arange(n)[:, None],
                                       order].set(True)
    # unit-variance outputs for unit-RMS inputs over the m*g kept columns
    s0 = np.float32(1.0 / (CODE_STD * np.sqrt(m * g)))
    scale = s0 * jax.random.uniform(ks, (n, c), jnp.float32, 0.5, 1.5)
    zero = jax.random.uniform(kz, (n, c), jnp.float32, 6.0, 9.0)
    return codes, gmask, scale, zero


def dense_leaves(model: Model, seed: int, dtype):
    """Embedding, untied head and norm weights, made on the default
    device in one jitted call."""

    def make():
        key = jax.random.PRNGKey(seed)
        out = {"embed": (jax.random.normal(
            jax.random.fold_in(key, EMBED_ID), (model.vocab, model.d),
            jnp.float32) * EMBED_STD).astype(dtype),
            "ln1": jnp.ones((model.layers, model.d), dtype),
            "ln2": jnp.ones((model.layers, model.d), dtype),
            "final_norm": jnp.ones((model.d,), dtype)}
        if not model.tied:
            out["lm_head"] = (jax.random.normal(
                jax.random.fold_in(key, HEAD_ID), (model.vocab, model.d),
                jnp.float32) * EMBED_STD).astype(dtype)
        return out
    return jax.jit(make)()


# ---------------------------------------------------------------------------
# packing (host, worker processes)
# ---------------------------------------------------------------------------

def _worker_init(src: str) -> None:
    """Workers pack on the host CPU and never touch the accelerator."""
    import sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    if src not in sys.path:
        sys.path.insert(0, src)
    jax.config.update("jax_platforms", "cpu")


def pack_one(task):
    """Draw and pack one linear: returns its BSR leaves as numpy and the
    static fields, so nothing device-side crosses the process boundary."""
    seed, layer, name, n, k, g, m = task
    from repro.core.bsr import pack_quantized
    arrs = [np.asarray(a) for a in canonical(linear_key(seed, layer, name),
                                             n, k, g, m)]
    bsr = pack_quantized(*arrs, group_size=g)
    leaves, aux = jax.tree_util.tree_flatten(bsr)
    return [np.asarray(l) for l in leaves], aux


def _digest(paths: List[str]) -> str:
    h = hashlib.sha256()
    for root in paths:
        files = [root] if os.path.isfile(root) else sorted(
            os.path.join(dp, f) for dp, _, fs in os.walk(root)
            for f in fs if f.endswith(".py"))
        for f in files:
            h.update(os.path.relpath(f, CHECKOUT).encode())
            with open(f, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()[:16]


def cache_key(conf: Dict) -> str:
    sizes = json.dumps([dataclasses.asdict(Model.from_conf(conf)),
                        conf["compression"]], sort_keys=True)
    return "{}-w{}-{}-{}".format(
        conf["name"], conf["weight_seed"],
        hashlib.sha256(sizes.encode()).hexdigest()[:8],
        _digest([os.path.join(SRC, "repro", "core"),
                 os.path.abspath(__file__)]))


def _memory_available() -> int:
    """Bytes this process may still allocate: the host's available
    memory, or less where a cgroup limits the container (/proc/meminfo
    shows the host's memory there)."""
    avail = 0
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                avail = int(line.split()[1]) * 1024
    for limit, used in (("/sys/fs/cgroup/memory.max",
                         "/sys/fs/cgroup/memory.current"),
                        ("/sys/fs/cgroup/memory/memory.limit_in_bytes",
                         "/sys/fs/cgroup/memory/memory.usage_in_bytes")):
        try:
            with open(limit) as f:
                lim = f.read().strip()
            with open(used) as f:
                cur = int(f.read().strip())
        except (OSError, ValueError):
            continue
        if lim.isdigit():
            avail = min(avail, int(lim) - cur)
    return avail


def pack_workers(model: Model, cap: int) -> int:
    """Worker processes for packing: at most ``cap``, and as many as a
    fifth of the memory left to this process holds at ~22 bytes per
    weight of the largest linear (the measured peak of one ``pack_one``:
    1.6 GB at 14336 x 5120) plus 0.3 GB of runtime each. The rest is
    the parent's, which holds the packed results and the TPU runtime."""
    largest = max(n * k for _, _, n, k in model.linears())
    per = 0.3e9 + 22 * largest
    return max(1, min(cap, int(0.2 * _memory_available() / per)))


def _pack_all(model: Model, comp: Compression, seed: int, out_dir: str,
              workers: int) -> None:
    """Pack every linear of every layer, stack each linear over layers
    with the program's ``stack_bsr`` and save its leaves under
    ``out_dir``."""
    from repro.core.bsr import stack_bsr
    g = comp.group_size
    tasks = [(seed, layer, name, n, k, g, comp.kept(k))
             for _, name, n, k in model.linears()
             for layer in range(model.layers)]
    workers = pack_workers(model, workers)
    if workers > 1:
        pool = ProcessPoolExecutor(max_workers=workers,
                                   mp_context=get_context("spawn"),
                                   initializer=_worker_init,
                                   initargs=(SRC,))
        results = pool.map(pack_one, tasks, chunksize=1)
    else:
        pool, results = None, map(pack_one, tasks)
    try:
        meta = {}
        for block, name, _, _ in model.linears():
            mats = []
            for _ in range(model.layers):
                leaves, aux = next(results)
                mats.append(jax.tree_util.tree_unflatten(aux, leaves))
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                st = stack_bsr(mats, (model.layers,))
            leaves, aux = jax.tree_util.tree_flatten(st)
            for i, leaf in enumerate(leaves):
                np.save(os.path.join(out_dir, f"{name}.{i}.npy"),
                        np.asarray(leaf))
            meta[name] = {"block": block, "n_leaves": len(leaves),
                          "aux": _aux_json(st)}
    finally:
        if pool is not None:
            pool.shutdown(wait=True)
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump(meta, f)


def _aux_json(b) -> Dict:
    return {"shape": list(b.shape), "group_size": b.group_size,
            "bits": b.bits, "m": b.m, "block_n": b.block_n, "lane": b.lane}


def _load_packed(cache_dir: str, device):
    from repro.core.bsr import BSRMatrix
    with open(os.path.join(cache_dir, "meta.json")) as f:
        meta = json.load(f)
    tree: Dict[str, Dict] = {}
    for name, entry in meta.items():
        leaves = [jax.device_put(np.load(os.path.join(
            cache_dir, f"{name}.{i}.npy")), device)
            for i in range(entry["n_leaves"])]
        a = entry["aux"]
        bsr = BSRMatrix(*leaves, shape=tuple(a["shape"]),
                        group_size=a["group_size"], bits=a["bits"], m=a["m"],
                        block_n=a["block_n"], lane=a["lane"])
        tree.setdefault(entry["block"], {})[name] = {"bsr": bsr}
    return tree


def build_params(conf: Dict, cache_root: str, device, workers: int):
    """The served parameter tree on ``device``, and the seconds spent in
    each part of making it (``pack`` is 0 when the cache held it)."""
    model = Model.from_conf(conf)
    comp = Compression.from_conf(conf["compression"])
    parts = {"pack_s": 0.0}
    cache_dir = os.path.join(cache_root, cache_key(conf))
    t = time.perf_counter()
    if not os.path.exists(os.path.join(cache_dir, "meta.json")):
        tmp = cache_dir + ".partial"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        _pack_all(model, comp, int(conf["weight_seed"]), tmp, workers)
        os.replace(tmp, cache_dir)
        parts["pack_s"] = time.perf_counter() - t
    t = time.perf_counter()
    with jax.default_device(device):
        layers = _load_packed(cache_dir, device)
        dense = jax.block_until_ready(dense_leaves(
            model, int(conf["weight_seed"]),
            jnp.dtype(conf["leaf_dtype"])))
    jax.block_until_ready(layers)
    parts["load_s"] = time.perf_counter() - t
    layers["ln1"], layers["ln2"] = dense.pop("ln1"), dense.pop("ln2")
    params = {"embed": dense["embed"], "layers": layers,
              "final_norm": dense["final_norm"]}
    if "lm_head" in dense:
        params["lm_head"] = {"w": dense["lm_head"]}
    return params, parts
