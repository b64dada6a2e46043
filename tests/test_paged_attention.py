"""Fused paged-attention decode kernel vs the jnp dense-gather oracle
(interpret mode: the CPU's way to run a kernel): T=1 decode and
T=K+1 staircase verify, bf16/f32 and int8+scales pages, ragged lengths,
GQA ratios, OOB-sentinel block tables, and the occupied-page clamp."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # pragma: no cover
    from _hyp import given, settings, st

from _engine_utils import engine_kernels
from repro.kernels import ops, ref as kref
from repro.models.layers import quantize_kv, staircase_mask


def _case(seed, b, t, kh, r, d, ps, mp, num_pages, int8=False,
          dtype=jnp.float32):
    """Random paged-attention instance. Block tables hold each slot's
    occupied prefix of distinct pages followed by OOB sentinels; lengths
    are a ragged per-slot staircase inside the occupied span."""
    assert num_pages >= b * mp
    g = np.random.default_rng(seed)
    q = jnp.asarray(g.normal(size=(b, t, kh * r, d)), dtype)
    kp = jnp.asarray(g.normal(size=(kh, num_pages, ps, d)), dtype)
    vp = jnp.asarray(g.normal(size=(kh, num_pages, ps, d)), dtype)
    pages = g.permutation(num_pages)[:b * mp].reshape(b, mp).astype(np.int32)
    occ = g.integers(1, mp + 1, size=b)                  # ragged occupancy
    bt = np.where(np.arange(mp)[None, :] < occ[:, None], pages,
                  num_pages)                             # sentinel tail
    lengths = np.sort(np.stack(
        [g.integers(1, occ[i] * ps + 1, size=t) for i in range(b)]), axis=1)
    ksc = vsc = None
    if int8:
        kp, ksc = quantize_kv(kp.astype(jnp.float32))
        vp, vsc = quantize_kv(vp.astype(jnp.float32))
        ksc, vsc = ksc[..., None], vsc[..., None]      # [KH, P, ps, 1]
    return (q, kp, vp, jnp.asarray(lengths.astype(np.int32)),
            jnp.asarray(bt), ksc, vsc)


def _run_both(case):
    q, kp, vp, lengths, bt, ksc, vsc = case
    o_ref = kref.paged_attention_ref(q, kp, vp, lengths, bt, ksc, vsc)
    o_ker = ops.paged_decode_attention(q, kp, vp, lengths, bt, ksc, vsc)
    return np.asarray(o_ref), np.asarray(o_ker)


# ---------------------------------------------------------------------------
# parity grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [1, 4])                 # decode / K+1 verify
@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("kh,r", [(2, 4), (1, 8), (4, 1)])   # GQA ratios
def test_paged_kernel_matches_reference(t, int8, kh, r):
    o_ref, o_ker = _run_both(
        _case(7 * t + int8, b=3, t=t, kh=kh, r=r, d=32, ps=8, mp=4,
              num_pages=16, int8=int8))
    np.testing.assert_allclose(o_ker, o_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ps,mp", [(4, 7), (16, 2), (32, 3)])
def test_paged_kernel_page_geometries(ps, mp):
    o_ref, o_ker = _run_both(
        _case(ps + mp, b=2, t=3, kh=2, r=2, d=64, ps=ps, mp=mp,
              num_pages=2 * mp + 3))
    np.testing.assert_allclose(o_ker, o_ref, rtol=1e-4, atol=1e-4)


def test_paged_kernel_bf16_pages():
    o_ref, o_ker = _run_both(
        _case(11, b=2, t=2, kh=2, r=2, d=32, ps=8, mp=4, num_pages=12,
              dtype=jnp.bfloat16))
    np.testing.assert_allclose(o_ker, o_ref, rtol=2e-2, atol=2e-2)


def test_paged_kernel_staircase_is_causal():
    """T > 1 semantics: each query row equals a separate T=1 call at its
    own length — the staircase is exactly per-query causal masking."""
    q, kp, vp, lengths, bt, _, _ = _case(23, b=2, t=3, kh=2, r=2, d=32,
                                         ps=8, mp=4, num_pages=16)
    o = ops.paged_decode_attention(q, kp, vp, lengths, bt)
    for tt in range(3):
        o1 = ops.paged_decode_attention(
            q[:, tt:tt + 1], kp, vp, lengths[:, tt:tt + 1], bt)
        np.testing.assert_allclose(np.asarray(o[:, tt]),
                                   np.asarray(o1[:, 0]),
                                   rtol=1e-5, atol=1e-5)


def test_paged_kernel_all_sentinel_slot_is_finite():
    """A slot whose table is ALL sentinels (inactive slot with a stale
    position) must produce finite output in both implementations (both
    read the same clamped page, masked identically)."""
    q, kp, vp, lengths, bt, _, _ = _case(31, b=2, t=1, kh=2, r=2, d=32,
                                         ps=8, mp=4, num_pages=16)
    bt = bt.at[1].set(kp.shape[1])                     # slot 1: no pages
    o_ref, o_ker = _run_both((q, kp, vp, lengths, bt, None, None))
    assert np.isfinite(o_ker).all() and np.isfinite(o_ref).all()
    np.testing.assert_allclose(o_ker, o_ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# length-invariance: padding pages can NEVER change the output
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 4), st.integers(1, 3),
       st.booleans())
def test_padding_pages_never_change_output(seed, t, mp_extra, int8):
    """Property: widening the block table with sentinel entries and
    rewriting the contents of every page the lengths never reach leaves
    the kernel output BIT-IDENTICAL (dead pages are skipped, not merely
    masked)."""
    g = np.random.default_rng(seed)
    q, kp, vp, lengths, bt, ksc, vsc = _case(seed, b=2, t=t, kh=2, r=2,
                                             d=32, ps=8, mp=3,
                                             num_pages=12, int8=int8)
    base = np.asarray(ops.paged_decode_attention(
        q, kp, vp, lengths, bt, ksc, vsc))

    # 1) widen the table with sentinel columns
    wide = jnp.concatenate(
        [bt, jnp.full((2, mp_extra), kp.shape[1], jnp.int32)], axis=1)
    out_w = np.asarray(ops.paged_decode_attention(
        q, kp, vp, lengths, wide, ksc, vsc))
    np.testing.assert_array_equal(out_w, base)

    # 2) scribble over every (page, offset) no query can see
    b, mp = bt.shape
    n_pages, ps = kp.shape[1], kp.shape[2]
    flat_pos = np.arange(mp * ps)
    lmax = np.asarray(lengths).max(axis=1)
    seen = np.zeros((n_pages,), bool)
    bt_np = np.asarray(bt)
    for i in range(b):
        live = bt_np[i][flat_pos[flat_pos < lmax[i]] // ps]
        seen[live[live < n_pages]] = True
    dead = ~seen
    noise = g.normal(size=kp.shape)
    kp2 = jnp.where(jnp.asarray(dead)[None, :, None, None],
                    jnp.asarray(noise, kp.dtype), kp)
    vp2 = jnp.where(jnp.asarray(dead)[None, :, None, None],
                    jnp.asarray(noise[::-1], vp.dtype), vp)
    out_s = np.asarray(ops.paged_decode_attention(
        q, kp2, vp2, lengths, bt, ksc, vsc))
    np.testing.assert_array_equal(out_s, base)


# ---------------------------------------------------------------------------
# model-level: decode_step kernel path vs jnp fallback, + occupied clamp
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_config
    from repro.models.registry import get_model
    cfg = get_config("llama2_7b", reduced=True)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, api, params


def test_decode_step_pallas_matches_fallback(tiny):
    """Paged decode_step logits: Pallas kernel path == jnp gather path
    (f32 pool: same math, online vs full softmax only)."""
    from repro.models import transformer as T
    cfg, api, params = tiny
    B, PS, MP = 2, 4, 6
    pcache = T.init_paged_cache(cfg, B * MP, PS)
    bt = jnp.asarray(np.arange(B * MP, dtype=np.int32).reshape(B, MP))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, size=(B, 7)).astype(np.int32))
    lens = jnp.asarray([7, 4], jnp.int32)
    _, pcache = T.prefill(params, pcache, toks, lens, bt, cfg)
    nxt = jnp.asarray([[1], [2]], jnp.int32)
    lg_ref, _ = T.decode_step(params, pcache, nxt, lens, cfg,
                              block_tables=bt, use_pallas=False)
    lg_ker, _ = T.decode_step(params, pcache, nxt, lens, cfg,
                              block_tables=bt, use_pallas=True)
    np.testing.assert_allclose(np.asarray(lg_ker), np.asarray(lg_ref),
                               rtol=1e-4, atol=1e-4)
    # multi-token (verify-style) step, and the occupied-page clamp
    nxt4 = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, size=(B, 3)).astype(np.int32))
    outs = []
    for use_pallas in (False, True):
        for mlp in (None, 4):            # full table vs clamped
            lg, _ = T.decode_step(params, pcache, nxt4, lens, cfg,
                                  block_tables=bt, use_pallas=use_pallas,
                                  max_live_pages=mlp)
            outs.append(np.asarray(lg))
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-4)


def test_decode_step_int8_pallas_close_to_fallback(tiny):
    """int8 pool: the kernel dequantizes tiles (f32 contractions) while
    the jnp path re-quantizes q and the softmax weights — logits agree to
    quantization noise (same bar as the contiguous int8 test)."""
    import dataclasses
    from repro.models import transformer as T
    cfg, api, params_fp = tiny
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    params = params_fp
    B, PS, MP = 2, 4, 6
    pcache = T.init_paged_cache(cfg8, B * MP, PS)
    bt = jnp.asarray(np.arange(B * MP, dtype=np.int32).reshape(B, MP))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, size=(B, 6)).astype(np.int32))
    lens = jnp.asarray([6, 3], jnp.int32)
    _, pcache = T.prefill(params, pcache, toks, lens, bt, cfg8)
    nxt = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, size=(B, 3)).astype(np.int32))
    lg_ref, _ = T.decode_step(params, pcache, nxt, lens, cfg8,
                              block_tables=bt, use_pallas=False)
    lg_ker, _ = T.decode_step(params, pcache, nxt, lens, cfg8,
                              block_tables=bt, use_pallas=True)
    np.testing.assert_allclose(np.asarray(lg_ker), np.asarray(lg_ref),
                               atol=0.05)


def test_engine_pallas_matches_reference_outputs(tiny):
    """End-to-end greedy engine generations are identical with the kernel
    path on (FP params: linears are FP either way, attention flips)."""
    from repro.engine import EngineConfig, InferenceEngine, SamplingParams
    cfg, api, params = tiny
    prompts = [np.random.default_rng(s).integers(
        0, cfg.vocab, size=4 + s).astype(np.int32) for s in range(3)]

    def run(use_pallas):
        with engine_kernels(use_pallas):
            eng = InferenceEngine(
                cfg, params, EngineConfig(num_slots=2, max_seq=16,
                                          page_size=4))
        rids = [eng.submit(p, 4) for p in prompts]
        res = eng.run()
        return {r["rid"]: list(r["tokens"]) for r in res["results"]}, rids

    out_ref, rids_ref = run(False)
    out_ker, rids_ker = run(True)
    for r0, r1 in zip(rids_ref, rids_ker):
        assert out_ref[r0] == out_ker[r1]


def test_spec_greedy_lossless_with_kernel_path(tiny):
    """Acceptance pin: greedy spec decode == greedy non-spec, token for
    token, with the Pallas paged-attention path enabled in BOTH."""
    from repro.core.model_compress import compress_draft, draft_layers
    from repro.engine import EngineConfig, InferenceEngine, SamplingParams
    cfg, api, params = tiny
    draft = compress_draft(params, cfg, profile="w4l50")
    prompts = [np.random.default_rng(s).integers(
        0, cfg.vocab, size=4 + s).astype(np.int32) for s in range(3)]

    def run(spec_k):
        with engine_kernels(True):
            eng = InferenceEngine(
                cfg, params,
                EngineConfig(num_slots=2, max_seq=24, page_size=4,
                             spec_k=spec_k,
                             spec_draft_layers=(draft_layers(cfg, "w4l50")
                                                if spec_k else None)),
                SamplingParams(),
                draft_params=draft if spec_k else None)
        rids = [eng.submit(p, 5) for p in prompts]
        res = eng.run()
        return {r["rid"]: list(r["tokens"]) for r in res["results"]}, rids

    out0, rids0 = run(0)
    out1, rids1 = run(3)
    for r0, r1 in zip(rids0, rids1):
        assert out0[r0] == out1[r1]


# ---------------------------------------------------------------------------
# token-tree ancestor masks: kernel vs oracle (DESIGN.md §8)
# ---------------------------------------------------------------------------

def _tree_case(seed, b, fanout, kh, r, d, ps, mp, num_pages, int8=False):
    """Random tree-verify instance: every slot feeds the same BFS tree
    block (window = N+1 tokens) at its own random base position inside
    its occupied pages; block tables end in OOB sentinels."""
    from repro.engine.spec import TreeTemplate
    tpl = TreeTemplate(fanout)
    w = tpl.n_nodes + 1
    g = np.random.default_rng(seed)
    t = w
    q = jnp.asarray(g.normal(size=(b, t, kh * r, d)), jnp.float32)
    kp = jnp.asarray(g.normal(size=(kh, num_pages, ps, d)), jnp.float32)
    vp = jnp.asarray(g.normal(size=(kh, num_pages, ps, d)), jnp.float32)
    pages = g.permutation(num_pages)[:b * mp].reshape(b, mp).astype(np.int32)
    need = -(-w // ps) + 1                       # pages the window spans
    occ = g.integers(need, mp + 1, size=b)
    bt = np.where(np.arange(mp)[None, :] < occ[:, None], pages, num_pages)
    base = np.stack([g.integers(0, occ[i] * ps - w + 1) for i in range(b)])
    lengths = np.broadcast_to((base + w)[:, None], (b, t)).astype(np.int32)
    anc = np.broadcast_to(tpl.anc[None, :], (b, t)).astype(np.int32)
    ksc = vsc = None
    if int8:
        kp, ksc = quantize_kv(kp.astype(jnp.float32))
        vp, vsc = quantize_kv(vp.astype(jnp.float32))
        ksc, vsc = ksc[..., None], vsc[..., None]      # [KH, P, ps, 1]
    return (q, kp, vp, jnp.asarray(lengths), jnp.asarray(bt),
            jnp.asarray(anc), jnp.asarray(base.astype(np.int32)), w,
            ksc, vsc)


@pytest.mark.parametrize("fanout", [(1,), (2,), (2, 2), (4, 2), (1, 3, 2)])
@pytest.mark.parametrize("int8", [False, True])
def test_tree_kernel_matches_tree_oracle(fanout, int8):
    q, kp, vp, lengths, bt, anc, base, w, ksc, vsc = _tree_case(
        sum(fanout) + int8, b=3, fanout=fanout, kh=2, r=2, d=32, ps=4,
        mp=6, num_pages=20, int8=int8)
    o_ref = kref.tree_attention_ref(q, kp, vp, lengths, bt, anc, base, w,
                                    ksc, vsc)
    o_ker = ops.paged_decode_attention(q, kp, vp, lengths, bt, ksc, vsc,
                                       anc=anc, anc_base=base, anc_window=w)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kh,r", [(1, 8), (4, 1)])
def test_tree_kernel_gqa_ratios(kh, r):
    q, kp, vp, lengths, bt, anc, base, w, ksc, vsc = _tree_case(
        13 * kh + r, b=2, fanout=(2, 2), kh=kh, r=r, d=64, ps=8, mp=4,
        num_pages=10)
    o_ref = kref.tree_attention_ref(q, kp, vp, lengths, bt, anc, base, w)
    o_ker = ops.paged_decode_attention(q, kp, vp, lengths, bt,
                                       anc=anc, anc_base=base, anc_window=w)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


def test_tree_kernel_all_sentinel_slot_is_finite():
    q, kp, vp, lengths, bt, anc, base, w, _, _ = _tree_case(
        29, b=2, fanout=(2, 2), kh=2, r=2, d=32, ps=4, mp=6, num_pages=20)
    bt = bt.at[1].set(kp.shape[1])                # slot 1: no pages
    o_ref = kref.tree_attention_ref(q, kp, vp, lengths, bt, anc, base, w)
    o_ker = ops.paged_decode_attention(q, kp, vp, lengths, bt,
                                       anc=anc, anc_base=base, anc_window=w)
    assert np.isfinite(np.asarray(o_ker)).all()
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


def test_tree_kernel_chain_bitmaps_bit_identical_to_staircase():
    """The staircase IS the chain special case: running the kernel with
    prefix-of-ones ancestor bitmaps produces BIT-IDENTICAL output to the
    plain lengths-only kernel (same mask booleans, same float pipeline)."""
    q, kp, vp, lengths, bt, ksc, vsc = _case(41, b=2, t=4, kh=2, r=2,
                                             d=32, ps=8, mp=4, num_pages=16)
    base = jnp.min(lengths, axis=1) - 1           # first fed position
    w = 4
    chain_anc = jnp.broadcast_to(
        jnp.asarray([(1 << (i + 1)) - 1 for i in range(w)],
                    jnp.int32)[None, :], (2, w))
    # staircase lengths equivalent to base + bitmap windowing
    stair = (base[:, None] + 1 + jnp.arange(w)[None, :]).astype(jnp.int32)
    o_plain = ops.paged_decode_attention(q, kp, vp, stair, bt)
    o_tree = ops.paged_decode_attention(
        q, kp, vp, jnp.broadcast_to((base + w)[:, None], (2, w)), bt,
        anc=chain_anc, anc_base=base, anc_window=w)
    np.testing.assert_array_equal(np.asarray(o_tree), np.asarray(o_plain))


def test_spec_tree_greedy_lossless_with_kernel_path(tiny):
    """Acceptance pin: greedy TREE-spec decode == greedy non-spec, token
    for token, with the Pallas paged-attention path enabled in BOTH
    (ancestor-mask kernel on the draft + verify calls)."""
    from repro.core.model_compress import compress_draft, draft_layers
    from repro.engine import EngineConfig, InferenceEngine, SamplingParams
    cfg, api, params = tiny
    draft = compress_draft(params, cfg, profile="w4l50")
    prompts = [np.random.default_rng(s).integers(
        0, cfg.vocab, size=4 + s).astype(np.int32) for s in range(3)]

    def run(fanout):
        with engine_kernels(True):
            eng = InferenceEngine(
                cfg, params,
                EngineConfig(num_slots=2, max_seq=24, page_size=4,
                             spec_fanout=fanout,
                             spec_draft_layers=(draft_layers(cfg, "w4l50")
                                                if fanout else None)),
                SamplingParams(),
                draft_params=draft if fanout else None)
        rids = [eng.submit(p, 5) for p in prompts]
        res = eng.run()
        return {r["rid"]: list(r["tokens"]) for r in res["results"]}, rids

    out0, rids0 = run(None)
    out1, rids1 = run((2, 2))
    for r0, r1 in zip(rids0, rids1):
        assert out0[r0] == out1[r1]


def test_staircase_mask_shared_semantics():
    """The shared helper IS the masking of both jnp attentions: scalar,
    [B] and [B, T] length specs broadcast identically."""
    m_scalar = staircase_mask(jnp.int32(3), 2, 1, 5)
    m_vec = staircase_mask(jnp.asarray([3, 3]), 2, 1, 5)
    np.testing.assert_array_equal(np.asarray(m_scalar), np.asarray(m_vec))
    m_stair = np.asarray(staircase_mask(jnp.asarray([[1, 3]]), 1, 2, 4))
    assert m_stair[0, 0].tolist() == [True, False, False, False]
    assert m_stair[0, 1].tolist() == [True, True, True, False]
