"""Model module of a DeepSeek-V2 style MLA + MoE decoder (see
``bench/harness.py`` for what a model module provides): its sizes, the
program's ``ModelConfig`` for them, and its served parameter tree drawn
from the configuration's weight seed.

The model is a stack of leading dense layers (SwiGLU of
``intermediate_size``) and a stack of MoE layers (``n_routed_experts``
experts of ``moe_intermediate_size``, top-``num_experts_per_tok``,
``n_shared_experts`` shared experts fused into one SwiGLU), both with
multi-head latent attention whose q is one direct projection. Every
linear of q, the latent KV projection, the output projection, the dense
MLP, the shared experts and the routed experts is packed GQSA by
``bench/weights.py``; the expert linears are stacked over (layers,
experts). The latent up-projections (``kv_b_proj``, as ``w_uk`` and
``w_uv``), the norms, the embedding and the head are bfloat16 leaves and
the router an f32 leaf, made on the device in one jitted call.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import weights

KERNELS = ("paged_latent_attention",)

# stable fold-in ids of the packed linears: slice (l, e, ...) of a stack
# is drawn from fold_in(fold_in(fold_in(seed, l), id), e) ...; l counts
# within its stack (the dense stack's layer 0 is the model's layer 0, the
# MoE stack's layer l the model's layer l + dense layers)
DENSE_IDS = {"wq": 0, "w_kva": 1, "wo": 2, "wg": 3, "wu": 4, "wd": 5}
MOE_IDS = {"wq": 10, "w_kva": 11, "wo": 12, "shared.wg": 13,
           "shared.wu": 14, "shared.wd": 15, "experts.wg": 16,
           "experts.wu": 17, "experts.wd": 18}
# ids of the unpacked per-layer leaves, per stack
LEAF_IDS = {"dense_layers": {"w_uk": 20, "w_uv": 21},
            "layers": {"w_uk": 22, "w_uv": 23, "router": 24}}
EMBED_ID, HEAD_ID = 10_000, 10_001
EMBED_STD = 0.02


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes of a DeepSeek-V2 decoder, read from a configuration file
    (Hugging Face ``config.json`` key names)."""
    layers: int
    dense_layers: int
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    d_expert: int
    experts: int
    top_k: int
    shared: int
    norm_topk: bool
    scaling: float
    vocab: int
    tied: bool
    rope_theta: float
    eps: float
    yarn: Tuple[Tuple[str, float], ...]

    @classmethod
    def from_conf(cls, m: Dict) -> "Model":
        want = {"hidden_act": "silu", "scoring_func": "softmax",
                "topk_method": "greedy", "q_lora_rank": None,
                "moe_layer_freq": 1, "attention_bias": False}
        for key, value in want.items():
            if m.get(key, value) != value:
                raise ValueError(f"{key} {m[key]!r}: the MLA + MoE model "
                                 f"is built with {value!r}")
        rs = m["rope_scaling"]
        if rs.get("type") != "yarn":
            raise ValueError("rope_scaling: only YaRN is built")
        return cls(layers=m["num_hidden_layers"],
                   dense_layers=m["first_k_dense_replace"],
                   d=m["hidden_size"], heads=m["num_attention_heads"],
                   kv_rank=m["kv_lora_rank"], nope=m["qk_nope_head_dim"],
                   rope=m["qk_rope_head_dim"], v_dim=m["v_head_dim"],
                   d_ff=m["intermediate_size"],
                   d_expert=m["moe_intermediate_size"],
                   experts=m["n_routed_experts"],
                   top_k=m["num_experts_per_tok"],
                   shared=m["n_shared_experts"],
                   norm_topk=bool(m["norm_topk_prob"]),
                   scaling=float(m["routed_scaling_factor"]),
                   vocab=m["vocab_size"],
                   tied=bool(m.get("tie_word_embeddings", False)),
                   rope_theta=float(m["rope_theta"]),
                   eps=float(m["rms_norm_eps"]),
                   yarn=(("factor", float(rs["factor"])),
                         ("original_max_position",
                          int(rs["original_max_position_embeddings"])),
                         ("beta_fast", float(rs["beta_fast"])),
                         ("beta_slow", float(rs["beta_slow"])),
                         ("mscale", float(rs["mscale"])),
                         ("mscale_all_dim", float(rs["mscale_all_dim"]))))

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    def attn_linears(self) -> List[Tuple[str, int, int]]:
        """(name, n_out, k_in) of a layer's packed attention linears."""
        q = self.heads * (self.nope + self.rope)
        return [("wq", q, self.d), ("w_kva", self.kv_rank + self.rope,
                                    self.d),
                ("wo", self.d, self.heads * self.v_dim)]

    @staticmethod
    def swiglu(width: int, d: int) -> List[Tuple[str, int, int]]:
        return [("wg", width, d), ("wu", width, d), ("wd", d, width)]

    def linears(self) -> List[weights.Linear]:
        """(block, name, id, n, k, lead) of every packed linear: block is
        the path of its parent in the served tree, "/"-joined."""
        nd, nm = (self.dense_layers,), (self.moe_layers,)
        out = []
        for name, n, k in self.attn_linears():
            out.append(("dense_layers/attn", "dense." + name,
                        DENSE_IDS[name], n, k, nd))
        for name, n, k in self.swiglu(self.d_ff, self.d):
            out.append(("dense_layers/mlp", "dense." + name,
                        DENSE_IDS[name], n, k, nd))
        for name, n, k in self.attn_linears():
            out.append(("layers/attn", name, MOE_IDS[name], n, k, nm))
        for name, n, k in self.swiglu(self.shared * self.d_expert, self.d):
            out.append(("layers/moe/shared", "shared." + name,
                        MOE_IDS["shared." + name], n, k, nm))
        for name, n, k in self.swiglu(self.d_expert, self.d):
            out.append(("layers/moe/experts", "experts." + name,
                        MOE_IDS["experts." + name], n, k,
                        nm + (self.experts,)))
        return out

    def active_kept(self, comp) -> float:
        """Kept weights of the packed linears one decode token runs
        through: every attention linear, the dense MLP, the shared
        experts and top_k routed experts of each MoE layer."""
        def kept(rows):
            return sum(n * comp.kept(k) * comp.group_size
                       for _, n, k in rows)
        return (self.layers * kept(self.attn_linears())
                + self.dense_layers * kept(self.swiglu(self.d_ff, self.d))
                + self.moe_layers * kept(self.swiglu(
                    self.shared * self.d_expert, self.d))
                + self.moe_layers * self.top_k * kept(self.swiglu(
                    self.d_expert, self.d)))


def unpacked_leaves(model: Model, seed: int, dtype):
    """Embedding, head, norms, the latent up-projections (dtype) and the
    routers (f32), made on the default device in one jitted call."""
    def per_layer(stack, name, lead, shape, std, leaf_dtype):
        lid = LEAF_IDS[stack][name]
        return jax.vmap(lambda l: (jax.random.normal(
            weights.slice_key(seed, (l,), lid), shape, jnp.float32)
            * std).astype(leaf_dtype))(jnp.arange(lead))

    def make():
        key = jax.random.PRNGKey(seed)
        out = {"embed": (jax.random.normal(
            jax.random.fold_in(key, EMBED_ID), (model.vocab, model.d),
            jnp.float32) * EMBED_STD).astype(dtype),
            "final_norm": jnp.ones((model.d,), dtype)}
        if not model.tied:
            out["lm_head"] = (jax.random.normal(
                jax.random.fold_in(key, HEAD_ID), (model.vocab, model.d),
                jnp.float32) * EMBED_STD).astype(dtype)
        up = (model.heads, model.nope, model.kv_rank)
        for stack, lead in (("dense_layers", model.dense_layers),
                            ("layers", model.moe_layers)):
            out[stack] = {
                "ln1": jnp.ones((lead, model.d), dtype),
                "ln2": jnp.ones((lead, model.d), dtype),
                "kv_norm": jnp.ones((lead, model.kv_rank), dtype),
                "w_uk": per_layer(stack, "w_uk", lead, up,
                                  model.kv_rank ** -0.5, dtype),
                "w_uv": per_layer(stack, "w_uv", lead,
                                  (model.heads, model.v_dim, model.kv_rank),
                                  model.kv_rank ** -0.5, dtype)}
        out["layers"]["router"] = per_layer(
            "layers", "router", model.moe_layers, (model.experts, model.d),
            model.d ** -0.5, jnp.float32)
        return out
    return jax.jit(make)()


def program_config(conf: Dict):
    """The program's ModelConfig for a configuration file: the registry
    arch, with every size taken from the file. Raises before anything is
    built where the program lacks a field the model needs."""
    from repro.configs import base
    need = {base.ModelConfig: ("n_dense_layers", "dense_d_ff",
                               "rope_scaling"),
            base.MoECfg: ("norm_topk_prob", "routed_scaling_factor")}
    missing = [f"{cls.__name__}.{f}" for cls, fs in need.items()
               for f in fs if f not in {x.name for x in
                                        dataclasses.fields(cls)}]
    if missing or not hasattr(base, "YarnCfg"):
        raise RuntimeError("the program cannot build this MLA + MoE model: "
                           f"it lacks {', '.join(missing) or 'YarnCfg'}")
    from repro.configs.registry import get_config
    m = Model.from_conf(conf)
    cfg = get_config(conf["arch"])
    if cfg.family != "mla_moe":
        raise ValueError(f"arch {conf['arch']!r} is not an MLA + MoE model")
    return dataclasses.replace(
        cfg, name=conf["name"], n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.heads, d_ff=m.d_expert,
        vocab=m.vocab, n_dense_layers=m.dense_layers, dense_d_ff=m.d_ff,
        moe=dataclasses.replace(cfg.moe, n_experts=m.experts,
                                top_k=m.top_k, n_shared=m.shared,
                                d_expert=m.d_expert,
                                norm_topk_prob=m.norm_topk,
                                routed_scaling_factor=m.scaling),
        mla=dataclasses.replace(cfg.mla, kv_lora_rank=m.kv_rank,
                                q_lora_rank=None, qk_nope_dim=m.nope,
                                qk_rope_dim=m.rope, v_dim=m.v_dim),
        rope_theta=m.rope_theta,
        rope_scaling=base.YarnCfg(**dict(m.yarn)), norm_eps=m.eps,
        tie_embeddings=m.tied, dtype=conf["compute_dtype"])


def assemble(packed: Dict, dense: Dict) -> Dict:
    """The served tree from the packed linears (``{block: {name: leaf}}``
    as ``bench/weights.py`` loads them) and the unpacked leaves."""
    params = {"embed": dense["embed"], "final_norm": dense["final_norm"]}
    if "lm_head" in dense:
        params["lm_head"] = {"w": dense["lm_head"]}
    for stack in ("dense_layers", "layers"):
        leaves = dense[stack]
        params[stack] = {"ln1": leaves["ln1"], "ln2": leaves["ln2"],
                         "attn": {"kv_norm": leaves["kv_norm"],
                                  "w_uk": leaves["w_uk"],
                                  "w_uv": leaves["w_uv"]}}
    params["layers"]["moe"] = {"router": {"w": dense["layers"]["router"]}}
    for block, linears in packed.items():
        node = params
        for part in block.split("/"):
            node = node.setdefault(part, {})
        for name, leaf in linears.items():
            node[name.rsplit(".", 1)[-1]] = leaf
    return params


def build_params(conf: Dict, cache_root: str, device, workers: int):
    """The served parameter tree on ``device``, and the seconds spent in
    each part of making it (``pack_s`` is 0 when the cache held it)."""
    program_config(conf)        # refuse a program that cannot serve it
    model = Model.from_conf(conf)
    packed, parts = weights.packed_layers(conf, model.linears(), [__file__],
                                          cache_root, device, workers)
    t = time.perf_counter()
    with jax.default_device(device):
        dense = jax.block_until_ready(unpacked_leaves(
            model, int(conf["weight_seed"]), jnp.dtype(conf["leaf_dtype"])))
    parts["load_s"] += time.perf_counter() - t
    return assemble(packed, dense), parts
