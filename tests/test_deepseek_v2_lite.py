"""DeepSeek-V2-Lite (MLA with a direct q projection and YaRN, one leading
dense layer, then MoE layers with un-renormalised top-k gates and shared
experts) against its plain reference, at a tiny size on the CPU.

The served tree is the benchmark's (``bench/models/mla_moe.py``: seeded
canonical GQSA W4 S50 G16 linears, packed by the program's packer) and
the reference is the benchmark's (``bench/references/mla_moe.py``:
float32 at highest precision, the weights drawn again and dequantized,
nothing of the program imported). Both run in float32 here, so the
logits agree to float32 summation order and any change of the
mathematics shows far above that.
"""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT]

from bench.models import mla_moe as bench_model  # noqa: E402
from bench.references import mla_moe as reference  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.models import mla as MLA  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.registry import get_model  # noqa: E402

# the reduced arch's widths under the published configuration's keys
TINY = dict(num_hidden_layers=3, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=64, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=3, vocab_size=512)
B, PROMPT, DECODE, PAGE = 4, 96, 6, 16
# float32 program against float32 reference: the logits (|logit| up to
# ~1.1) differ only by summation order, at most 1.4e-6 (jnp path) and
# 1.8e-6 (kernels) here; 1e-4 leaves that fifty-fold room and sits far
# below what a change of the mathematics moves (renormalised gates 0.19,
# no mscale² 0.75)
LOGIT_ATOL = 1e-4


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    with open(os.path.join(CHECKOUT, "bench", "configs",
                           "deepseek-v2-lite.gqsa.json")) as f:
        conf = json.load(f)
    conf.update(TINY, name="tiny-dsv2", compute_dtype="float32",
                leaf_dtype="float32")
    params, _ = bench_model.build_params(
        conf, str(tmp_path_factory.mktemp("weights")), jax.devices()[0], 1)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (B, PROMPT + DECODE), 0, 512), np.int32)
    hid, head = reference._final_hidden(conf, tokens, "ref")
    ref_logits = np.asarray(jnp.einsum("bld,vd->blv", hid, head,
                                       precision="highest"))
    return conf, params, tokens, ref_logits


def _served_logits(conf, params, tokens, cfg=None, use_pallas=False):
    """Logits of every position fed after the prompt's last: a prefill of
    ragged prompts (4 x 96 rows, the grouped expert path) through the
    paged latent pool, then teacher-forced paged decode steps (4 rows,
    the buffered expert path), the engine's ``prefill`` and
    ``decode_step``."""
    cfg = cfg or bench_model.program_config(conf)
    api = get_model(cfg)
    lengths = np.array([PROMPT, PROMPT - 5, PROMPT - 17, PROMPT - 40],
                       np.int32)
    mp = -(-(PROMPT + DECODE) // PAGE)
    cache = api.init_paged_cache(cfg, B * mp, PAGE)
    bt = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    toks = np.where(np.arange(PROMPT)[None] < lengths[:, None],
                    tokens[:, :PROMPT], 0)
    logits, cache = api.prefill(params, cache, jnp.asarray(toks),
                                jnp.asarray(lengths), bt, cfg,
                                use_pallas=use_pallas)
    out = [(lengths - 1, np.asarray(logits[:, 0]))]
    pos = lengths.copy()
    for _ in range(DECODE):
        feed = tokens[np.arange(B), pos][:, None]
        lg, cache = api.decode_step(params, cache, jnp.asarray(feed),
                                    jnp.asarray(pos), cfg,
                                    use_pallas=use_pallas,
                                    block_tables=bt)
        out.append((pos.copy(), np.asarray(lg[:, 0])))
        pos = pos + 1
    return out


def _worst(served, ref_logits):
    return max(float(np.abs(lg - ref_logits[np.arange(B), p]).max())
               for p, lg in served)


def test_prefill_then_paged_decode_match_the_reference(tiny):
    conf, params, tokens, ref_logits = tiny
    assert _worst(_served_logits(conf, params, tokens),
                  ref_logits) < LOGIT_ATOL


def test_the_kernels_match_the_reference(tiny):
    """The same with the Pallas kernels (interpreted): the fused expert
    GEMV over the stack in decode, the densify pass and the grouped
    matmul in prefill, the latent paged-attention kernel."""
    conf, params, tokens, ref_logits = tiny
    assert _worst(_served_logits(conf, params, tokens, use_pallas=True),
                  ref_logits) < LOGIT_ATOL


@pytest.mark.parametrize("mutation", ["no_mscale", "renormalised_gates"])
def test_a_changed_equation_fails_the_tolerance(tiny, monkeypatch,
                                                mutation):
    """Dropping YaRN's mscale² from the softmax scale, or renormalising
    the top-k gates, moves the logits far past the tolerance."""
    conf, params, tokens, ref_logits = tiny
    cfg = bench_model.program_config(conf)
    if mutation == "no_mscale":
        monkeypatch.setattr(MLA, "softmax_gain", lambda cfg: 1.0)
    else:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, norm_topk_prob=True))
    assert _worst(_served_logits(conf, params, tokens, cfg),
                  ref_logits) > 100 * LOGIT_ATOL


@pytest.mark.parametrize("rows", [32, 512])
def test_the_serving_dispatch_drops_nothing(rows):
    """Every token of a batch routes to the same top-k experts; each gets
    the output it gets alone, with buffers (up to 256 rows) and with the
    grouped dispatch (more) alike. The capacity-bound training block
    drops entries of the same batch."""
    cfg = get_config("deepseek_v2_lite", reduced=True)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    d, k = cfg.d_model, cfg.moe.top_k
    w = np.zeros((cfg.moe.n_experts, d), np.float32)
    w[:k] = np.arange(k, 0, -1)[:, None] * 4.0 / np.sqrt(d)
    p["router"] = {"w": jnp.asarray(w)}
    x = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (1, rows, d))
    _, idx, _ = MOE._route(p["router"], x[0], cfg.moe)
    assert np.all(np.sort(np.asarray(idx), 1) == np.arange(k))
    batch = MOE.moe_serve(p, x, cfg)
    alone = jnp.concatenate([MOE.moe_serve(p, x[:, i:i + 1], cfg)
                             for i in range(0, rows, max(1, rows // 8))],
                            axis=1)
    np.testing.assert_allclose(np.asarray(batch[:, ::max(1, rows // 8)]),
                               np.asarray(alone), rtol=1e-5, atol=1e-5)
    capped, _ = MOE.moe_block(p, x, cfg)
    assert not np.allclose(np.asarray(capped), np.asarray(batch),
                           atol=1e-3)


def test_yarn_frequencies_follow_the_closed_form():
    """DeepSeek-V2's YaRN at its published settings: the ramp between
    original and ÷40 frequencies runs over rope pair indices 10..23 of
    32, cos and sin keep scale 1, and the softmax scale gains
    mscale(40, 0.707)²."""
    cfg = get_config("deepseek_v2_lite")
    y, dim, base = cfg.rope_scaling, cfg.mla.qk_rope_dim, cfg.rope_theta
    i = np.arange(dim // 2, dtype=np.float64)
    extra = base ** (-2 * i / dim)

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) \
            / (2 * math.log(base))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    got = np.asarray(MLA.yarn_inv_freqs(dim, base, y))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], extra[23:] / 40, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert MLA.softmax_gain(cfg) == pytest.approx(mscale ** 2)
    assert mscale ** 2 == pytest.approx(1.590, abs=1e-3)


def _digest(params):
    import hashlib
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    h = hashlib.sha256(str(treedef).encode())
    for path, leaf in sorted(leaves,
                             key=lambda pl: jax.tree_util.keystr(pl[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("arch,digest", [
    ("deepseek_v2_236b",
     "dfcf5642172e3e9142ff409eb946c29cba32b5a9efc17e11db3df58231f47a5f"),
    ("deepseek_moe_16b",
     "726d3186c8e83c9e3e917af6a5fddd8a42163494b1b2b56395c8e9ede25669c0"),
    ("starcoder2_3b",
     "aa3e20f94f8a26939c725cbe9dea454801680ca3bb050576ce619fdc1bfe5690"),
])
def test_configs_without_dense_layers_build_the_tree_they_built(arch,
                                                                 digest):
    """Digests of the reduced configs' init trees, taken before leading
    dense layers existed: a config without them builds the same tree."""
    cfg = get_config(arch, reduced=True)
    assert cfg.n_dense_layers == 0
    assert _digest(get_model(cfg).init_params(jax.random.PRNGKey(0),
                                              cfg)) == digest


def test_the_leading_dense_layer_has_its_own_stack_and_pool_leaf():
    cfg = get_config("deepseek_v2_lite", reduced=True)
    api = get_model(cfg)
    params = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    assert "mlp" in params["dense_layers"] and "moe" in params["layers"]
    assert params["dense_layers"]["mlp"]["wg"]["w"].shape == (1, 96, 64)
    assert params["layers"]["ln1"].shape[0] == cfg.n_layers - 1
    assert "wq" in params["layers"]["attn"]
    cache = jax.eval_shape(lambda: api.init_paged_cache(cfg, 8, 16))
    assert {k: v.shape[0] for k, v in cache.items()} == {
        "lat_pages": 2, "dense_lat_pages": 1}
    full = get_config("deepseek_v2_lite")
    assert full.n_params() == pytest.approx(15.7e9, rel=0.01)
