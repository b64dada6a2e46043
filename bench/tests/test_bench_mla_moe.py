"""The MLA + MoE model module, its reference and its readers:

* the module lists every packed linear at the published widths, with the
  routed experts stacked over (layers, experts), and draws each slice
  from its own folded key (pinned digests of a few slices);
* the reference computes, for two tokens, what the published equations
  written out again here in float64 numpy compute;
* each new reader reads a synthetic trace and counters as its formula
  says, and only the kernels it names;
* a program that lacks what the model needs is refused before anything
  is packed.
"""
import dataclasses
import hashlib
import json
import math
import os
import sys
import types

import numpy as np
import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]

from bench import harness, weights  # noqa: E402
from bench.models import mla_moe  # noqa: E402
from bench.trace_reduce import Event, Trace  # noqa: E402

BENCH = os.path.join(CHECKOUT, "bench")
TINY = dict(num_hidden_layers=3, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=64, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=3, vocab_size=512)


def _conf(**kw):
    with open(harness.find(BENCH, "configs", "deepseek-v2-lite.gqsa")) as f:
        conf = json.load(f)
    conf.update(kw)
    return conf


def test_the_module_lists_every_linear_at_published_widths():
    model = mla_moe.Model.from_conf(_conf())
    got = {(block, name): (n, k, lead)
           for block, name, _, n, k, lead in model.linears()}
    assert got == {
        ("dense_layers/attn", "dense.wq"): (3072, 2048, (1,)),
        ("dense_layers/attn", "dense.w_kva"): (576, 2048, (1,)),
        ("dense_layers/attn", "dense.wo"): (2048, 2048, (1,)),
        ("dense_layers/mlp", "dense.wg"): (10944, 2048, (1,)),
        ("dense_layers/mlp", "dense.wu"): (10944, 2048, (1,)),
        ("dense_layers/mlp", "dense.wd"): (2048, 10944, (1,)),
        ("layers/attn", "wq"): (3072, 2048, (13,)),
        ("layers/attn", "w_kva"): (576, 2048, (13,)),
        ("layers/attn", "wo"): (2048, 2048, (13,)),
        ("layers/moe/shared", "shared.wg"): (2816, 2048, (13,)),
        ("layers/moe/shared", "shared.wu"): (2816, 2048, (13,)),
        ("layers/moe/shared", "shared.wd"): (2048, 2816, (13,)),
        ("layers/moe/experts", "experts.wg"): (1408, 2048, (13, 64)),
        ("layers/moe/experts", "experts.wu"): (1408, 2048, (13, 64)),
        ("layers/moe/experts", "experts.wd"): (2048, 1408, (13, 64)),
    }
    ids = [lid for _, _, lid, _, _, _ in model.linears()]
    assert len(set(ids)) == len(ids)


def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.fixture(scope="module")
def tiny_params(tmp_path_factory):
    import jax
    conf = _conf(name="tiny", **TINY)
    params, parts = mla_moe.build_params(
        conf, str(tmp_path_factory.mktemp("w")), jax.devices()[0], 1)
    assert parts["pack_s"] > 0
    return conf, params


@pytest.mark.parametrize("path,index,digest", [
    (("layers", "moe", "experts", "wg"), (1, 5), "0f6808f2c5c1acd9"),
    (("layers", "moe", "experts", "wd"), (0, 2), "b713b6122fff1b8b"),
    (("layers", "moe", "shared", "wu"), (1,), "bb4f3e9e166cd70f"),
    (("dense_layers", "attn", "wq"), (0,), "d0a4d7cf82657271"),
])
def test_slices_are_drawn_from_their_folded_keys(tiny_params, path, index,
                                                 digest):
    """A slice of the served tree is the program's pack of the canonical
    draw from its own key, and its bytes are pinned."""
    import jax
    from repro.core.bsr import pack_quantized
    conf, params = tiny_params
    node = params
    for p in path:
        node = node[p]
    got = [np.asarray(a)[index] for a in
           jax.tree_util.tree_leaves(node["bsr"])]
    stack = "dense_layers" if path[0] == "dense_layers" else "layers"
    key = ("experts." if "experts" in path else "shared."
           if "shared" in path else "") + path[-1]
    lid = (mla_moe.DENSE_IDS if stack == "dense_layers"
           else mla_moe.MOE_IDS)[key]
    n, k = node["bsr"].shape
    comp = weights.Compression.from_conf(conf["compression"])
    want = pack_quantized(*[np.asarray(a) for a in weights.canonical(
        weights.slice_key(int(conf["weight_seed"]), index, lid), n, k,
        comp.group_size, comp.kept(k))], group_size=comp.group_size)
    for g, w in zip(got, jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, np.asarray(w))
    assert _digest(*got) == digest


def _np_weight(conf, stack, name, index, n, k):
    comp = weights.Compression.from_conf(conf["compression"])
    ids = mla_moe.DENSE_IDS if stack == "dense_layers" else mla_moe.MOE_IDS
    codes, gmask, scale, zero = [np.asarray(a, np.float64) for a in
                                 weights.canonical(weights.slice_key(
                                     int(conf["weight_seed"]), index,
                                     ids[name]), n, k, comp.group_size,
                                     comp.kept(k))]
    g = comp.group_size
    c = codes.reshape(n, k // g, g)
    return ((c - zero[..., None]) * scale[..., None]
            * gmask[..., None]).reshape(n, k)


def test_the_reference_computes_the_published_equations_for_two_tokens():
    """Two tokens through the tiny model, written out here from the
    published equations in float64: token 0 attends itself, token 1
    both; YaRN at the published settings; softmax top-k gates not
    renormalised; shared experts always on."""
    import jax.numpy as jnp
    from bench.references import mla_moe as ref
    conf = _conf(name="tiny", leaf_dtype="float32", **TINY)
    m = mla_moe.Model.from_conf(conf)
    toks = np.array([[7, 300]], np.int32)
    hid, _ = ref._final_hidden(conf, toks, "ref")
    leaves = mla_moe.unpacked_leaves(m, int(conf["weight_seed"]),
                                     jnp.float32)
    lv = {s: {k: np.asarray(v, np.float64) for k, v in leaves[s].items()}
          for s in ("dense_layers", "layers")}

    def rms(x):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + m.eps)

    dim = m.rope
    i = np.arange(dim // 2)
    extra = m.rope_theta ** (-2.0 * i / dim)

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) \
            / (2 * math.log(m.rope_theta))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv = extra / 40 * ramp + extra * (1 - ramp)
    mscale = 0.1 * 0.707 * math.log(40) + 1

    def rope(x, pos):                    # x [..., dim], split halves
        a = pos * inv
        x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
        return np.concatenate([x1 * np.cos(a) - x2 * np.sin(a),
                               x2 * np.cos(a) + x1 * np.sin(a)], -1)

    def silu(x):
        return x / (1 + np.exp(-x))

    def swiglu(x, stack, prefix, width, index):
        wg = _np_weight(conf, stack, prefix + "wg", index, width, m.d)
        wu = _np_weight(conf, stack, prefix + "wu", index, width, m.d)
        wd = _np_weight(conf, stack, prefix + "wd", index, m.d, width)
        return (silu(x @ wg.T) * (x @ wu.T)) @ wd.T

    h = np.asarray(leaves["embed"], np.float64)[toks[0]]      # [2, d]
    for stack, n in (("dense_layers", 1), ("layers", 2)):
        for l in range(n):
            L = {k: v[l] for k, v in lv[stack].items()}
            x = rms(h)
            q = (x @ _np_weight(conf, stack, "wq", (l,), m.heads * 32,
                                m.d).T).reshape(2, m.heads, 32)
            kv = x @ _np_weight(conf, stack, "w_kva", (l,), 80, m.d).T
            c = rms(kv[:, :64]) * L["kv_norm"]
            k_rope = [rope(kv[t, 64:], t) for t in range(2)]
            out = np.zeros((2, m.heads, 16))
            for hd in range(m.heads):
                for t in range(2):
                    qn, qr = q[t, hd, :16], rope(q[t, hd, 16:], t)
                    s = np.array([qn @ (L["w_uk"][hd] @ c[j])
                                  + qr @ k_rope[j] for j in range(t + 1)])
                    s = s / math.sqrt(32) * mscale ** 2
                    p = np.exp(s - s.max())
                    p /= p.sum()
                    out[t, hd] = sum(p[j] * (L["w_uv"][hd] @ c[j])
                                     for j in range(t + 1))
            h = h + out.reshape(2, -1) @ _np_weight(
                conf, stack, "wo", (l,), m.d, m.heads * 16).T
            x = rms(h)
            if stack == "dense_layers":
                h = h + swiglu(x, stack, "", m.d_ff, (l,))
                continue
            y = swiglu(x, stack, "shared.", 2 * m.d_expert, (l,))
            logit = x @ L["router"].T
            prob = np.exp(logit - logit.max(-1, keepdims=True))
            prob /= prob.sum(-1, keepdims=True)
            for t in range(2):
                for e in np.argsort(-prob[t])[:m.top_k]:
                    y[t] += prob[t, e] * swiglu(x[t:t + 1], stack,
                                                "experts.", m.d_expert,
                                                (l, int(e)))[0]
            h = h + y
    np.testing.assert_allclose(np.asarray(hid[0]), rms(h), rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# readers, on a synthetic window
# ---------------------------------------------------------------------------

def _ctx(**kw):
    """A window of 10 decode steps of 32 tokens (10 ms each, inside
    ``jit_decode_fn``) holding one expert GEMV, one dense GEMV and one
    latent and one GQA attention op per step, and a prefill op outside
    the decode programs."""
    ops, mods = [], []
    for s in range(10):
        t0 = s * 10e6
        mods.append(Event("jit_decode_fn(7)", t0, t0 + 10e6, ""))
        ops += [Event(f"%gqsa_gemv_ewg.{s} = f32[64,32,1408] custom-call",
                      t0, t0 + 4e6, ""),
                Event(f"%gqsa_gemv_wq.{s} = f32[32,3072] custom-call",
                      t0 + 4e6, t0 + 5e6, ""),
                Event(f"%paged_latent_attention.{s} = f32[32,1,16,576]",
                      t0 + 5e6, t0 + 6e6, ""),
                Event(f"%paged_attention.{s} = f32[32,1,16,128]",
                      t0 + 6e6, t0 + 7e6, "")]
    ops.append(Event("%gqsa_gemv_ewd.99 = f32[64,32,2048] custom-call",
                     101e6, 105e6, ""))
    mods.append(Event("jit_prefill_fn(3)", 100e6, 110e6, ""))
    trace = Trace({"/device:TPU:0": {"modules": mods, "ops": ops}},
                  [Event("bench_window", 0, 120e6, "")])
    base = dict(trace=trace, window=trace.span("bench_window"),
                model=mla_moe.Model.from_conf(_conf()),
                comp=weights.Compression(4, 0.5, 16), peak_flops=197e12,
                peak_bw=819e9, decode_tokens=320,
                decode_context_tokens=320 * 400.0, decode_steps=10,
                counters={"moe.routed_rows.decode": 320 * 6 * 13,
                          "moe.expert_rows.decode": 10 * 64 * 32 * 13,
                          "moe.routed_rows.prefill": 50,
                          "moe.expert_rows.prefill": 5000})
    base.update(kw)
    return harness.Context(**base)


def _read(name, ctx):
    return harness.load_module(harness.find(BENCH, "layer_metrics",
                                            name)).read(ctx)


def test_expert_gemv_roofline_reads_the_expert_kernels_in_decode():
    # per step: 13 layers x 64 experts of paper-BSR bytes, 192 routed
    # rows' activations; the kernels took 4 ms a step
    g, per = 16, 0.0
    for n, k in ((1408, 2048), (1408, 2048), (2048, 1408)):
        m = round(k / g * 0.5)
        per += 64 * (4 * (n + 1) + n * m * 13) + 192 * (n + k) * 2
    want = 100.0 * (13 * per / 819e9) / 4e-3
    got = _read("expert_gemv_roofline.decode", _ctx())
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_latent_attention_roofline_reads_the_latent_kernel_only():
    ctx_tokens, tokens = 320 * 400.0, 320
    nbytes = 14 * (ctx_tokens * 576 * 2 + tokens * 16 * (576 + 512) * 2)
    want = 100.0 * (nbytes / 819e9) / (10 * 1e-3)
    got = _read("paged_latent_attention_roofline.decode", _ctx())
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_expert_row_use_reads_the_decode_counters():
    assert _read("expert_row_use.decode", _ctx()) == pytest.approx(
        100.0 * 6 / 64)
    assert _read("expert_row_use.decode", _ctx(counters={})) is None


def test_step_mfu_counts_the_active_weights_attention_and_head():
    m, comp = mla_moe.Model.from_conf(_conf()), weights.Compression(
        4, 0.5, 16)
    kept = m.active_kept(comp)
    # by hand: attention 14 x (3072x2048 + 576x2048 + 2048x2048), the
    # dense MLP 3 x 10944 x 2048, 13 x shared 3 x 2816 x 2048 and 13 x 6
    # experts of 3 x 1408 x 2048, each at half its weights kept
    dense_equiv = (14 * (3072 + 576 + 2048) * 2048 + 3 * 10944 * 2048
                   + 13 * 3 * 2816 * 2048 + 13 * 6 * 3 * 1408 * 2048)
    assert kept == dense_equiv / 2
    per_token = 2 * (kept + 14 * 16 * 512 * 256 + 13 * 64 * 2048
                     + 102400 * 2048)
    flops = 320 * per_token + 2 * 16 * (576 + 512) * 14 * 320 * 400.0
    got = _read("mla_moe_step_mfu.decode", _ctx())
    assert got == pytest.approx(100.0 * flops / (100e-3 * 197e12))
    assert 0 < got < 100


def test_readers_read_nothing_of_a_model_without_experts():
    from bench.models.dense_transformer import Model as Dense
    with open(harness.find(BENCH, "configs", "starcoder2-3b.gqsa")) as f:
        dense = Dense.from_conf(json.load(f))
    for name in ("expert_gemv_roofline.decode",
                 "paged_latent_attention_roofline.decode",
                 "mla_moe_step_mfu.decode"):
        assert _read(name, _ctx(model=dense)) is None


def test_a_program_without_the_model_fields_is_refused_before_packing(
        tmp_path, monkeypatch):
    """A program whose configs lack the leading dense layers, YaRN and
    the gate options (the parent of this model's support) is refused in
    ``program_config``, which ``build_params`` calls before packing."""
    import repro.configs
    from repro.configs import base

    @dataclasses.dataclass(frozen=True)
    class ModelConfig:
        name: str
        n_layers: int

    @dataclasses.dataclass(frozen=True)
    class MoECfg:
        n_experts: int
    old = types.SimpleNamespace(ModelConfig=ModelConfig, MoECfg=MoECfg,
                                MLACfg=base.MLACfg)
    monkeypatch.setattr(repro.configs, "base", old)
    packed = []
    monkeypatch.setattr(weights, "packed_layers",
                        lambda *a, **k: packed.append(a))
    with pytest.raises(RuntimeError, match="n_dense_layers"):
        mla_moe.build_params(_conf(), str(tmp_path), None, 1)
    assert not packed and not os.listdir(tmp_path)
