"""Model module of a dense GQA decoder (see ``bench/harness.py`` for what
a model module provides): its sizes, the program's ``ModelConfig`` for
them, and its served parameter tree drawn from the configuration's
weight seed. It adds no kernel names to the breakdown.

Every linear is stacked over layers and packed by ``bench/weights.py``;
the unpacked leaves (embedding, untied head, norms) are made on the
device in one jitted call, in the leaf dtype the configuration states.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import weights

# stable fold-in ids: a linear's key is fold_in(fold_in(seed, layer), id)
LINEAR_IDS = {"wq": 0, "wk": 1, "wv": 2, "wo": 3, "wg": 4, "wu": 5, "wd": 6}
EMBED_ID, HEAD_ID = 10_000, 10_001
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


def linear_key(seed: int, layer, name: str):
    return weights.slice_key(seed, (layer,), LINEAR_IDS[name])


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


def program_config(conf: Dict):
    """The program's ModelConfig for a configuration file: the registry
    arch, with every size taken from the file."""
    from repro.configs.registry import get_config
    m = Model.from_conf(conf)
    base = get_config(conf["arch"])
    if base.family != "dense":
        raise ValueError(f"arch {conf['arch']!r} is not a dense decoder")
    return dataclasses.replace(
        base, name=conf["name"], n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.kv_heads, head_dim=m.head_dim,
        d_ff=m.d_ff, vocab=m.vocab, rope_theta=m.rope_theta,
        norm_eps=m.eps, tie_embeddings=m.tied,
        mlp_type="swiglu" if m.gated else "gelu",
        dtype=conf["compute_dtype"], qk_norm=False)


def build_params(conf: Dict, cache_root: str, device, workers: int):
    """The served parameter tree on ``device``, and the seconds spent in
    each part of making it (``pack_s`` is 0 when the cache held it)."""
    model = Model.from_conf(conf)
    linears = [(block, name, LINEAR_IDS[name], n, k, (model.layers,))
               for block, name, n, k in model.linears()]
    layers, parts = weights.packed_layers(conf, linears, [__file__],
                                          cache_root, device, workers)
    t = time.perf_counter()
    with jax.default_device(device):
        dense = jax.block_until_ready(dense_leaves(
            model, int(conf["weight_seed"]), jnp.dtype(conf["leaf_dtype"])))
    parts["load_s"] += time.perf_counter() - t
    layers["ln1"], layers["ln2"] = dense.pop("ln1"), dense.pop("ln2")
    params = {"embed": dense["embed"], "layers": layers,
              "final_norm": dense["final_norm"]}
    if "lm_head" in dense:
        params["lm_head"] = {"w": dense["lm_head"]}
    return params, parts
