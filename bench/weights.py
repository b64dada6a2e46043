"""Seeded random GQSA linears, the part every model module shares.

A model module (``bench/models/<name>.py``) lists its packed linears as
``(block, name, id, n, k, lead)``: an ``[N, K]`` weight stacked over the
lead shape, such as ``(layers,)`` or ``(layers, experts)``. Each slice is
drawn in canonical GQSA form from the configuration's weight seed: 4-bit
codes, a row-balanced group mask that keeps ``groups_kept_per_row``
groups of each row, and a scale and zero per group. The program's own
packer (``repro.core.bsr.pack_quantized`` and ``stack_bsr``) turns them
into the served layout, so a change to that layout flows through here
with no edit. The plain reference (``bench/references``) draws the same
canonical arrays from the same keys and dequantizes them itself; it
never reads the packed tree.

Packing runs on the host, spread over worker processes, and the packed
tree is kept in a cache inside the checkout, keyed by configuration,
weight seed, the linears' sizes and a digest of the packer's and the
model module's sources, so only the first run of a checkout packs.
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
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
SRC = os.path.join(CHECKOUT, "src")

# std of (q - z) for codes uniform on 0..15: sqrt((16^2 - 1) / 12)
CODE_STD = 4.6098

# (block, name, id, n_out, k_in, lead shape) of one stacked linear
Linear = Tuple[str, str, int, int, int, Tuple[int, ...]]


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


def slice_key(seed: int, index: Sequence, lid: int):
    """Key of slice ``index`` (layer first) of linear ``lid``:
    ``fold_in(fold_in(PRNGKey(seed), layer), lid)``, folded once more
    for each further index (an expert's, say)."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), index[0]), lid)
    for i in index[1:]:
        key = jax.random.fold_in(key, i)
    return key


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
    """Draw and pack one slice of a linear: returns its BSR leaves as
    numpy and the static fields, so nothing device-side crosses the
    process boundary."""
    seed, index, lid, n, k, g, m = task
    from repro.core.bsr import pack_quantized
    arrs = [np.asarray(a) for a in canonical(slice_key(seed, index, lid),
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


def cache_key(conf: Dict, linears: List[Linear], sources: List[str]) -> str:
    """Name of the packed tree's cache directory: ``sources`` are the
    model module's files, digested beside the packer's."""
    sizes = json.dumps([linears, conf["compression"]], sort_keys=True)
    return "{}-w{}-{}-{}".format(
        conf["name"], conf["weight_seed"],
        hashlib.sha256(sizes.encode()).hexdigest()[:8],
        _digest([os.path.join(SRC, "repro", "core"),
                 os.path.abspath(__file__)] + list(sources)))


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


def pack_workers(linears: List[Linear], cap: int) -> int:
    """Worker processes for packing: at most ``cap``, and as many as a
    fifth of the memory left to this process holds at ~22 bytes per
    weight of the largest linear (the measured peak of one ``pack_one``:
    1.6 GB at 14336 x 5120) plus 0.3 GB of runtime each. The rest is
    the parent's, which holds the packed results and the TPU runtime."""
    largest = max(n * k for _, _, _, n, k, _ in linears)
    per = 0.3e9 + 22 * largest
    return max(1, min(cap, int(0.2 * _memory_available() / per)))


def _pack_all(linears: List[Linear], comp: Compression, seed: int,
              out_dir: str, workers: int) -> None:
    """Pack every slice of every linear, stack each linear over its lead
    shape with the program's ``stack_bsr`` and save its leaves under
    ``out_dir``."""
    from repro.core.bsr import stack_bsr
    g = comp.group_size
    tasks = [(seed, index, lid, n, k, g, comp.kept(k))
             for _, _, lid, n, k, lead in linears
             for index in np.ndindex(*lead)]
    workers = pack_workers(linears, workers)
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
        for block, name, _, _, _, lead in linears:
            mats = []
            for _ in range(int(np.prod(lead))):
                leaves, aux = next(results)
                mats.append(jax.tree_util.tree_unflatten(aux, leaves))
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                st = stack_bsr(mats, tuple(lead))
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


def packed_layers(conf: Dict, linears: List[Linear], sources: List[str],
                  cache_root: str, device, workers: int):
    """The packed linears on ``device``, as ``{block: {name: {"bsr":
    BSRMatrix}}}``, packed first where the cache does not hold them; and
    the seconds spent in each part (``pack_s`` is 0 when the cache held
    them)."""
    comp = Compression.from_conf(conf["compression"])
    parts = {"pack_s": 0.0}
    cache_dir = os.path.join(cache_root, cache_key(conf, linears, sources))
    t = time.perf_counter()
    if not os.path.exists(os.path.join(cache_dir, "meta.json")):
        tmp = cache_dir + ".partial"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        _pack_all(linears, comp, int(conf["weight_seed"]), tmp, workers)
        os.replace(tmp, cache_dir)
        parts["pack_s"] = time.perf_counter() - t
    t = time.perf_counter()
    with jax.default_device(device):
        tree = jax.block_until_ready(_load_packed(cache_dir, device))
    parts["load_s"] = time.perf_counter() - t
    return tree, parts
