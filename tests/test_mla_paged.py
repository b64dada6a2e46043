"""Paged MLA latent-KV cache (DESIGN.md §9): differential parity of the
paged prefill+decode path against the dense `mla_decode` cache oracle
(dtype x T grid, ragged lengths, sentinel/dead pages), latent-kernel vs
jnp-fallback parity (incl. the lane-tiled D > 128 case and tree ancestor
bitmaps), the registry paged-cache capability flag's error paths, and
the engine pins — latent-page leak-freedom under spec accept/reject
traffic and greedy spec decode (chain AND fanout-1 tree AND a branching
tree) == non-spec, token for token."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # pragma: no cover
    from _hyp import given, settings, st

from repro.configs import get_config
from _engine_utils import engine_kernels
from repro.engine import EngineConfig, InferenceEngine, SamplingParams
from repro.kernels import ops, ref as kref
from repro.models import transformer as T
from repro.models.registry import get_model, paged_families

GREEDY = SamplingParams()


@functools.lru_cache(maxsize=4)
def _tiny(dtype="float32"):
    """Reduced mla_moe cell. Routing drops are disabled (the repo's
    equivalence-check convention, see test_models.py): MoE capacity
    truncation depends on the flattened token count, which differs
    between a T=1 decode step and a T=K+1 verify block — dropless
    routing is what makes the paged-vs-dense and spec-vs-non-spec pins
    exact."""
    cfg = get_config("deepseek_v2_236b", reduced=True)
    cfg = dataclasses.replace(
        cfg, dtype=dtype,
        moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, api, params


@functools.lru_cache(maxsize=4)
def _draft(profile):
    from repro.core.model_compress import compress_draft
    cfg, api, params = _tiny()
    return compress_draft(params, cfg, profile=profile)


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=l).astype(np.int32) for l in lens]


def _paged_setup(cfg, params, lens, ps=4, mp=6, seed=0):
    """Prefill ragged prompts into a fresh latent pool; returns
    (tokens [B, S], lengths, block_tables, filled cache, last logits)."""
    b = len(lens)
    g = np.random.default_rng(seed)
    s = max(lens)
    toks = np.zeros((b, s), np.int32)
    for i, l in enumerate(lens):
        toks[i, :l] = g.integers(0, cfg.vocab, size=l)
    bt = jnp.asarray(np.arange(b * mp, dtype=np.int32).reshape(b, mp))
    pcache = T.init_paged_cache(cfg, b * mp, ps)
    lengths = jnp.asarray(lens, jnp.int32)
    logits, pcache = T.prefill(params, pcache, jnp.asarray(toks), lengths,
                               bt, cfg)
    return toks, lengths, bt, pcache, logits


# ---------------------------------------------------------------------------
# differential parity: paged prefill + decode vs the dense mla_decode path
# ---------------------------------------------------------------------------

def test_mla_paged_prefill_matches_forward():
    """Paged MLA prefill's last-valid-token logits == full-forward logits
    at each row's own (ragged) length."""
    cfg, api, params = _tiny()
    toks, lengths, bt, pcache, logits = _paged_setup(cfg, params, (7, 4))
    logits_fwd, _ = T.forward(params, jnp.asarray(toks), cfg)
    ref = np.stack([np.asarray(logits_fwd)[i, int(lengths[i]) - 1]
                    for i in range(2)])
    np.testing.assert_allclose(np.asarray(logits)[:, 0], ref,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype,rtol", [("float32", 2e-4),
                                        ("bfloat16", 4e-2)])
@pytest.mark.parametrize("t", [1, 3])              # decode / K+1 staircase
def test_mla_paged_decode_matches_dense(dtype, rtol, t):
    """The differential grid: paged decode (T=1 and the T=K+1 verify
    staircase, ragged per-slot positions) against the dense
    `mla_cache_init`/`mla_decode` oracle run token-by-token per slot —
    logits agree and the greedy token choice matches token for token."""
    cfg, api, params = _tiny(dtype)
    lens = (7, 4)
    toks, lengths, bt, pcache, _ = _paged_setup(cfg, params, lens)
    g = np.random.default_rng(1)
    feed = jnp.asarray(g.integers(0, cfg.vocab, size=(2, t)).astype(np.int32))
    lg_p, _ = T.decode_step(params, pcache, feed, lengths, cfg,
                            block_tables=bt)
    for i, l in enumerate(lens):
        cache = T.init_cache(cfg, 1, 24)
        for s in range(l):                 # replay the prompt densely
            _, cache = T.decode_step(params, cache, jnp.asarray(
                toks[i:i + 1, s:s + 1]), jnp.int32(s), cfg)
        for tt in range(t):                # then the fed block, one by one
            lg_d, cache = T.decode_step(params, cache, feed[i:i + 1,
                                                           tt:tt + 1],
                                        jnp.int32(l + tt), cfg)
            np.testing.assert_allclose(np.asarray(lg_p)[i, tt],
                                       np.asarray(lg_d)[0, 0],
                                       rtol=rtol, atol=rtol)
            assert int(np.argmax(np.asarray(lg_p)[i, tt])) == \
                int(np.argmax(np.asarray(lg_d)[0, 0]))


def test_mla_paged_decode_kernel_matches_fallback():
    """decode_step logits: Pallas latent-kernel path == jnp gather path,
    T=1 and multi-token, with and without the occupied-page clamp."""
    cfg, api, params = _tiny()
    toks, lengths, bt, pcache, _ = _paged_setup(cfg, params, (7, 4))
    feed = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab, size=(2, 3)).astype(np.int32))
    outs = []
    for use_pallas in (False, True):
        for mlp in (None, 4):              # full table vs clamped
            lg, _ = T.decode_step(params, pcache, feed, lengths, cfg,
                                  block_tables=bt, use_pallas=use_pallas,
                                  max_live_pages=mlp)
            outs.append(np.asarray(lg))
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# latent kernel vs jnp oracle (ops.paged_latent_attention)
# ---------------------------------------------------------------------------

def _latent_case(seed, b, t, h, dl, v_rank, ps, mp, num_pages,
                 dtype=jnp.float32):
    """Random paged-latent instance: occupied page prefix + sentinel
    tail, ragged staircase lengths inside the occupied span."""
    assert num_pages >= b * mp
    g = np.random.default_rng(seed)
    q = jnp.asarray(g.normal(size=(b, t, h, dl)), dtype)
    lat = jnp.asarray(g.normal(size=(1, num_pages, ps, dl)), dtype)
    pages = g.permutation(num_pages)[:b * mp].reshape(b, mp).astype(np.int32)
    occ = g.integers(1, mp + 1, size=b)
    bt = np.where(np.arange(mp)[None, :] < occ[:, None], pages, num_pages)
    lengths = np.sort(np.stack(
        [g.integers(1, occ[i] * ps + 1, size=t) for i in range(b)]), axis=1)
    return q, lat, jnp.asarray(lengths.astype(np.int32)), jnp.asarray(bt)


@pytest.mark.parametrize("t", [1, 4])              # decode / K+1 verify
@pytest.mark.parametrize("dl,v_rank", [(40, 32), (160, 140), (320, 256)])
def test_latent_kernel_matches_reference(t, dl, v_rank):
    """Latent-kernel parity across the lane-tiling boundary: dl <= 128 is
    the single-dot program, dl > 128 exercises the 128-wide chunked
    score contraction (incl. a ragged tail chunk)."""
    q, lat, lengths, bt = _latent_case(3 * t + dl, b=3, t=t, h=4, dl=dl,
                                       v_rank=v_rank, ps=8, mp=4,
                                       num_pages=16)
    o_ref = kref.paged_latent_attention_ref(q, lat, lengths, bt, v_rank)
    o_ker = ops.paged_latent_attention(q, lat, lengths, bt, v_rank=v_rank)
    assert o_ker.shape == (3, t, 4, v_rank)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


def test_latent_kernel_bf16_pages():
    q, lat, lengths, bt = _latent_case(11, b=2, t=2, h=4, dl=40, v_rank=32,
                                       ps=8, mp=4, num_pages=12,
                                       dtype=jnp.bfloat16)
    o_ref = kref.paged_latent_attention_ref(q, lat, lengths, bt, 32)
    o_ker = ops.paged_latent_attention(q, lat, lengths, bt, v_rank=32)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=2e-2, atol=2e-2)


def test_latent_kernel_all_sentinel_slot_is_finite():
    """A slot whose table is ALL sentinels must stay finite in both
    implementations (same clamped page, masked identically)."""
    q, lat, lengths, bt = _latent_case(17, b=2, t=1, h=4, dl=40, v_rank=32,
                                       ps=8, mp=4, num_pages=16)
    bt = bt.at[1].set(lat.shape[1])
    o_ref = kref.paged_latent_attention_ref(q, lat, lengths, bt, 32)
    o_ker = ops.paged_latent_attention(q, lat, lengths, bt, v_rank=32)
    assert np.isfinite(np.asarray(o_ker)).all()
    assert np.isfinite(np.asarray(o_ref)).all()
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


def test_latent_kernel_tree_ancestor_bitmaps():
    """Token-tree verify on the latent pool: the kernel's ancestor-bitmap
    mask matches the jnp reference (same shared ancestor_mask)."""
    from repro.engine.spec import TreeTemplate
    tpl = TreeTemplate((2, 2))
    w = tpl.n_nodes + 1
    g = np.random.default_rng(23)
    b, h, dl, ps, mp, num_pages = 2, 4, 160, 4, 6, 20
    q = jnp.asarray(g.normal(size=(b, w, h, dl)), jnp.float32)
    lat = jnp.asarray(g.normal(size=(1, num_pages, ps, dl)), jnp.float32)
    pages = g.permutation(num_pages)[:b * mp].reshape(b, mp).astype(np.int32)
    need = -(-w // ps) + 1
    occ = g.integers(need, mp + 1, size=b)
    bt = jnp.asarray(np.where(np.arange(mp)[None, :] < occ[:, None],
                              pages, num_pages))
    base = jnp.asarray(np.stack(
        [g.integers(0, occ[i] * ps - w + 1) for i in range(b)]), jnp.int32)
    lengths = jnp.broadcast_to((base + w)[:, None], (b, w)).astype(jnp.int32)
    anc = jnp.broadcast_to(jnp.asarray(tpl.anc)[None, :], (b, w))
    o_ref = kref.paged_latent_attention_ref(q, lat, lengths, bt, 140,
                                            anc=anc, anc_base=base,
                                            anc_window=w)
    o_ker = ops.paged_latent_attention(q, lat, lengths, bt, v_rank=140,
                                       anc=anc, anc_base=base, anc_window=w)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 3), st.integers(1, 3))
def test_latent_dead_pages_never_change_output(seed, t, mp_extra):
    """Property: widening the block table with sentinel columns and
    scribbling over every page the lengths never reach leaves the latent
    kernel's output BIT-IDENTICAL (dead pages are skipped, not masked)."""
    g = np.random.default_rng(seed)
    q, lat, lengths, bt = _latent_case(seed, b=2, t=t, h=2, dl=40,
                                       v_rank=32, ps=8, mp=3, num_pages=12)
    base = np.asarray(ops.paged_latent_attention(
        q, lat, lengths, bt, v_rank=32))
    wide = jnp.concatenate(
        [bt, jnp.full((2, mp_extra), lat.shape[1], jnp.int32)], axis=1)
    out_w = np.asarray(ops.paged_latent_attention(
        q, lat, lengths, wide, v_rank=32))
    np.testing.assert_array_equal(out_w, base)

    ps = lat.shape[2]
    bt_np = np.asarray(bt)
    lmax = np.asarray(lengths).max(axis=1)
    seen = np.zeros((lat.shape[1],), bool)
    for i in range(2):
        flat = np.arange(bt_np.shape[1] * ps)
        live = bt_np[i][flat[flat < lmax[i]] // ps]
        seen[live[live < lat.shape[1]]] = True
    noise = jnp.asarray(g.normal(size=lat.shape), lat.dtype)
    lat2 = jnp.where(jnp.asarray(~seen)[None, :, None, None], noise, lat)
    out_s = np.asarray(ops.paged_latent_attention(
        q, lat2, lengths, bt, v_rank=32))
    np.testing.assert_array_equal(out_s, base)


# ---------------------------------------------------------------------------
# registry capability flag: early, listed error paths
# ---------------------------------------------------------------------------

def test_supports_paged_cache_flag():
    """mla_moe is now engine-capable; the families without a paged pool
    report so via the capability flag, and the supported list is what
    every error path quotes."""
    assert get_model(get_config("deepseek_v2_236b",
                                reduced=True)).supports_paged_cache
    assert paged_families() == ["dense", "mla_moe", "moe", "vlm"]
    for arch in ("mamba2_130m", "zamba2_7b", "seamless_m4t_large_v2"):
        assert not get_model(get_config(arch, reduced=True)) \
            .supports_paged_cache


def test_unsupported_family_fails_early_with_supported_list():
    """Engine construction on a family without paged-cache support fails
    BEFORE any device allocation, naming the supported families."""
    cfg = get_config("mamba2_130m", reduced=True)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="supported: .*mla_moe"):
        InferenceEngine(cfg, params, EngineConfig(num_slots=1, max_seq=16))
    from repro.engine import PagedKVCache
    with pytest.raises(NotImplementedError, match="supported: .*dense"):
        PagedKVCache(cfg, api, num_slots=1, max_seq=16)


def test_serve_cli_rejects_unsupported_family():
    """serve.py validates the capability flag before building params."""
    from repro.launch import serve
    with pytest.raises(SystemExit):
        serve.main(["--arch", "mamba2_130m", "--reduced",
                    "--compress", "none", "--requests", "1"])


# ---------------------------------------------------------------------------
# engine pins: leak-freedom + greedy spec losslessness on mla_moe
# ---------------------------------------------------------------------------

def _run_engine(seed, max_new, *, spec_k=0, spec_fanout=None, draft=None,
                profile=None, use_pallas=False, num_pages=None,
                prompts_lens=(5, 9, 4)):
    from repro.core.model_compress import draft_layers
    cfg, api, params = _tiny()
    with engine_kernels(use_pallas):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(num_slots=2, max_seq=24, page_size=4,
                         num_pages=num_pages, spec_k=spec_k,
                         spec_fanout=spec_fanout,
                         spec_draft_layers=(draft_layers(cfg, profile)
                                            if profile else None)),
            GREEDY, draft_params=draft)
    prompts = _prompts(cfg.vocab, prompts_lens, seed=seed)
    rids = [eng.submit(p, max_new) for p in prompts]
    res = eng.run()
    out = {r["rid"]: list(r["tokens"]) for r in res["results"]}
    return eng, [out[r] for r in rids], res["metrics"]


def test_mla_engine_full_path_with_eviction():
    """mla_moe runs the full engine path — prefill, paged decode,
    eviction/refill under a pool sized for ~one resident request — and
    matches a naive full-forward greedy loop token for token."""
    cfg, api, params = _tiny()
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=16, page_size=4, num_pages=4))
    prompts = _prompts(cfg.vocab, (5, 6, 7, 5), seed=3)
    rids = [eng.submit(p, 4) for p in prompts]
    res = eng.run()
    assert len(res["results"]) == 4
    assert eng.kv.allocator.num_free == 4

    def ref_generate(prompt):
        toks = list(prompt)
        out = []
        for _ in range(4):
            logits, _ = api.forward(params,
                                    {"tokens": jnp.asarray([toks])}, cfg)
            nxt = int(jnp.argmax(logits[0, -1]))
            out.append(nxt)
            toks.append(nxt)
        return out

    by_rid = {r["rid"]: list(r["tokens"]) for r in res["results"]}
    for rid, p in zip(rids, prompts):
        assert by_rid[rid] == ref_generate(p)


def test_mla_engine_pallas_matches_reference_outputs():
    """Greedy engine generations identical with the latent kernel on."""
    _, toks_ref, _ = _run_engine(7, 5)
    _, toks_ker, _ = _run_engine(7, 5, use_pallas=True)
    assert toks_ref == toks_ker


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 3))
def test_mla_spec_greedy_lossless_chain_and_tree(seed, k):
    """The lossless pin on mla_moe: greedy spec decode — chain K AND the
    degenerate fanout-1 tree — emits exactly the non-spec tokens, and
    chain vs tree leave the latent pool bit-identical (the PR 4
    chain/tree equivalence, now on latent pages)."""
    draft = _draft("w4l50")
    _, base, _ = _run_engine(seed, 6)
    eng_c, chain, m = _run_engine(seed, 6, spec_k=k, draft=draft,
                                  profile="w4l50")
    eng_t, tree, _ = _run_engine(seed, 6, spec_fanout=(1,) * k,
                                 draft=draft, profile="w4l50")
    assert chain == base
    assert tree == base
    assert m["spec_rounds"] > 0
    for lc, lt in zip(jax.tree_util.tree_leaves(eng_c.kv.data),
                      jax.tree_util.tree_leaves(eng_t.kv.data)):
        np.testing.assert_array_equal(np.asarray(lc), np.asarray(lt))


def test_mla_tree_spec_branching_lossless():
    """Greedy losslessness at a real branching fanout on the latent pool
    (tree verify + accepted-path latent compaction)."""
    draft = _draft("w4s75")
    _, base, _ = _run_engine(13, 6)
    _, tree, m = _run_engine(13, 6, spec_fanout=(2, 2), draft=draft,
                             profile="w4s75")
    assert tree == base
    assert m["verify_tokens"] > 0


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.sampled_from([("chain", 3), ("tree", (2,)), ("tree", (2, 2))]))
def test_mla_allocator_leak_free_under_spec_traffic(seed, mode):
    """Latent pages never leak: random admission/eviction interleaved
    with spec accept/reject rounds (chain rollback = positional rewind;
    tree additionally compacts the accepted path) drain the free list
    back to its initial state — the pool only fits ~one resident
    request, so requests stream through slots."""
    from repro.core.model_compress import draft_layers
    from repro.engine.spec import TreeTemplate
    cfg, api, params = _tiny()
    kind, spec = mode
    if kind == "chain":
        lookahead, ecfg = spec, dict(spec_k=spec)
    else:
        lookahead, ecfg = TreeTemplate(spec).n_nodes, dict(spec_fanout=spec)
    pages_per_req = -(-(16 + lookahead) // 4)
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=16, page_size=4,
                     num_pages=pages_per_req + 1,
                     spec_draft_layers=draft_layers(cfg, "w4l50"), **ecfg),
        GREEDY, draft_params=_draft("w4l50"))
    initial_free = eng.kv.allocator.num_free
    lens = np.random.default_rng(seed).integers(3, 8, size=4)
    for p in _prompts(cfg.vocab, tuple(lens), seed=seed):
        eng.submit(p, 4)
    res = eng.run()
    assert len(res["results"]) == 4
    assert all(r["n_generated"] == 4 for r in res["results"])
    assert eng.kv.allocator.num_free == initial_free
