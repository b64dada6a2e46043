"""Token-tree self-speculative decoding (engine/spec/tree.py, DESIGN.md
§8): tree template geometry, sibling-set rejection sampling, the
accepted-path KV compaction, and the two pinned engine properties — a
degenerate (fanout-1) tree is BIT-IDENTICAL to the PR 2 chain spec path,
and random accept/reject tree traffic never leaks a page."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # pragma: no cover
    from _hyp import given, settings, st

from _engine_utils import engine_kernels
from repro.configs import get_config
from repro.core.model_compress import compress_draft, draft_layers
from repro.engine import EngineConfig, InferenceEngine, SamplingParams
from repro.engine.sampling import tree_verify
from repro.engine.spec import TreeTemplate, compact_accepted
from repro.engine.spec.tree import MAX_TREE_TOKENS
from repro.models.registry import get_model

GREEDY = SamplingParams()


@functools.lru_cache(maxsize=2)
def _tiny():
    cfg = get_config("llama2_7b", reduced=True)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, api, params


@functools.lru_cache(maxsize=8)
def _draft(profile):
    cfg, api, params = _tiny()
    return compress_draft(params, cfg, profile=profile)


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=l).astype(np.int32) for l in lens]


# ---------------------------------------------------------------------------
# TreeTemplate geometry
# ---------------------------------------------------------------------------

def test_tree_template_structure():
    tpl = TreeTemplate((2, 2))
    assert tpl.n_nodes == 6 and tpl.depth == 2
    assert tpl.level_starts == (0, 1, 3)
    # BFS: 0=root; 1,2 = level 1; 3,4 children of 1; 5,6 children of 2
    assert list(tpl.parents) == [-1, 0, 0, 1, 1, 2, 2]
    assert list(tpl.depths) == [0, 1, 1, 2, 2, 2, 2]
    assert list(tpl.child_start) == [1, 3, 5, -1, -1, -1, -1]
    # ancestor bitmaps: root path only (node 5 = {0, 2, 5})
    assert tpl.anc[0] == 0b1
    assert tpl.anc[2] == 0b101
    assert tpl.anc[5] == 0b100101
    # chain degenerates to prefix-of-ones bitmaps (the staircase)
    ch = TreeTemplate((1, 1, 1))
    assert ch.n_nodes == 3
    assert [int(a) for a in ch.anc] == [0b1, 0b11, 0b111, 0b1111]


def test_tree_template_rejects_oversized_and_invalid():
    with pytest.raises(ValueError):
        TreeTemplate((8, 4))              # 40 nodes > int32 bitmap lanes
    with pytest.raises(ValueError):
        TreeTemplate(())
    with pytest.raises(ValueError):
        TreeTemplate((2, 0))


# ---------------------------------------------------------------------------
# tree_verify: greedy path == sequential reference walk
# ---------------------------------------------------------------------------

def _ref_tree_walk(logits, feed, fanout, child_start):
    """Reference: walk the tree greedily, one row. Returns (n_acc, the
    n_acc + 1 emitted tokens)."""
    tgt = logits.argmax(-1)
    cur, toks = 0, []
    for f in fanout:
        t = int(tgt[cur])
        toks.append(t)
        nxt = next((child_start[cur] + j for j in range(f)
                    if feed[child_start[cur] + j] == t), None)
        if nxt is None:
            return len(toks) - 1, toks
        cur = nxt
    toks.append(int(tgt[cur]))
    return len(fanout), toks


def _fits_bitmap(fanout):
    """Trees whose fed block fits the int32 ancestor bitmaps; (3, 3, 3)
    needs 40 tokens and TreeTemplate refuses it."""
    nodes = int(sum(np.prod(fanout[:i + 1]) for i in range(len(fanout))))
    return nodes + 1 <= MAX_TREE_TOKENS


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.lists(st.integers(1, 3), min_size=1, max_size=3).filter(
           _fits_bitmap),
       st.integers(4, 17))
def test_tree_verify_greedy_property(seed, fanout, v):
    """For ANY logits/tree, greedy tree_verify emits exactly the
    sequential-greedy walk: longest root-to-leaf path of argmax matches,
    then the argmax correction/bonus — and the path indices are real
    tree slots consistent with the emitted tokens."""
    fanout = tuple(fanout)
    tpl = TreeTemplate(fanout)
    g = np.random.default_rng(seed)
    b = 3
    logits = g.normal(size=(b, tpl.n_nodes + 1, v)).astype(np.float32)
    feed = g.integers(0, v, size=(b, tpl.n_nodes + 1)).astype(np.int32)
    # row 0 adversarial: plant the argmax path so deep walks happen
    tgt0 = logits[0].argmax(-1)
    cur = 0
    for f in fanout:
        cb = tpl.child_start[cur]
        j = g.integers(0, f)
        feed[0, cb + j] = tgt0[cur]
        cur = cb + j
    n_acc, out, path = tree_verify(jnp.asarray(logits), jnp.asarray(feed),
                                   fanout, tpl.child_start,
                                   jax.random.PRNGKey(seed), GREEDY)
    n_acc, out, path = np.asarray(n_acc), np.asarray(out), np.asarray(path)
    for i in range(b):
        n_ref, toks_ref = _ref_tree_walk(logits[i], feed[i], fanout,
                                         tpl.child_start)
        assert n_acc[i] == n_ref
        assert list(out[i, :n_ref + 1]) == toks_ref
        for d in range(n_ref):            # path slots carry the tokens
            assert tpl.depths[path[i, d]] == d + 1
            assert feed[i, path[i, d]] == toks_ref[d]


def test_tree_verify_chain_matches_spec_verify():
    """Fanout-1 tree_verify == chain spec_verify (greedy): same accepted
    length, same emitted tokens, for every accept/reject shape."""
    from repro.engine.sampling import spec_verify
    g = np.random.default_rng(7)
    B, K, V = 4, 3, 16
    tpl = TreeTemplate((1,) * K)
    logits = g.normal(size=(B, K + 1, V)).astype(np.float32)
    tgt = logits.argmax(-1)
    draft = np.stack([
        tgt[0, :K],                               # full accept
        (tgt[1, :K] + 1) % V,                     # reject at 0
        np.concatenate([tgt[2, :1], (tgt[2, 1:K] + 1) % V]),
        g.integers(0, V, size=K),
    ]).astype(np.int32)
    feed = np.concatenate([np.zeros((B, 1), np.int32), draft], axis=1)
    n_c, out_c = spec_verify(jnp.asarray(logits), jnp.asarray(draft),
                             jax.random.PRNGKey(0), GREEDY)
    n_t, out_t, _ = tree_verify(jnp.asarray(logits), jnp.asarray(feed),
                                tpl.fanout, tpl.child_start,
                                jax.random.PRNGKey(0), GREEDY)
    np.testing.assert_array_equal(np.asarray(n_t), np.asarray(n_c))
    for i in range(B):
        n = int(np.asarray(n_c)[i])
        np.testing.assert_array_equal(np.asarray(out_t)[i, :n + 1],
                                      np.asarray(out_c)[i, :n + 1])


# ---------------------------------------------------------------------------
# tree_verify: sibling-set rejection sampling preserves the target
# ---------------------------------------------------------------------------

def test_tree_verify_first_token_distribution_preserved():
    """The first emitted token must be distributed exactly as the target
    p — whatever the sibling candidates propose (the tree analogue of
    the chain distribution-preservation test)."""
    V = 5
    sp = SamplingParams(temperature=1.0)
    tpl = TreeTemplate((2, 2))
    logits0 = np.array([2.0, 1.0, 0.5, 0.0, -1.0], np.float32)
    target = np.exp(logits0) / np.exp(logits0).sum()
    logits = jnp.asarray(np.tile(logits0, (1, tpl.n_nodes + 1, 1)))
    n = 4000
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    walk = jax.vmap(lambda key, fd: tree_verify(
        logits, fd, tpl.fanout, tpl.child_start, key, sp)[1],
        in_axes=(0, None))
    for sibs in ((0, 1), (4, 3)):         # likely and unlikely candidates
        feed = np.zeros((1, tpl.n_nodes + 1), np.int32)
        feed[0, 1], feed[0, 2] = sibs     # root's children
        out = np.asarray(walk(keys, jnp.asarray(feed)))     # [n, 1, D+1]
        freq = np.bincount(out[:, 0, 0], minlength=V) / n
        np.testing.assert_allclose(freq, target, atol=0.05)


def test_tree_verify_rejection_excludes_rejected_siblings():
    """When every sibling has ~zero target mass, the walk stops at depth
    0 and the correction can never be one of the rejected siblings."""
    V = 4
    sp = SamplingParams(temperature=1.0)
    tpl = TreeTemplate((2,))
    logits0 = np.array([10.0, 0.0, -30.0, -30.0], np.float32)
    logits = jnp.asarray(np.tile(logits0, (1, tpl.n_nodes + 1, 1)))
    feed = np.zeros((1, tpl.n_nodes + 1), np.int32)
    feed[0, 1], feed[0, 2] = 2, 3         # both ~impossible
    keys = jax.random.split(jax.random.PRNGKey(1), 400)
    n_acc, out, _ = jax.vmap(lambda k: tree_verify(
        logits, jnp.asarray(feed), tpl.fanout, tpl.child_start, k, sp))(keys)
    assert (np.asarray(n_acc) == 0).all()
    assert not np.isin(np.asarray(out)[:, 0, 0], (2, 3)).any()


# ---------------------------------------------------------------------------
# accepted-path KV compaction
# ---------------------------------------------------------------------------

def test_compact_accepted_moves_path_and_drops_rest():
    """Distinguishable per-position values: the accepted path's slots
    move into the leading positions, other slots' pages and positions
    outside the tree block stay untouched, and invalid rows write
    nothing (sentinel drop)."""
    L, KH, P, ps, D = 2, 1, 6, 4, 2
    pool = jnp.arange(L * KH * P * ps * D, dtype=jnp.float32).reshape(
        L, KH, P, ps, D)
    cache = {"k_pages": pool, "v_pages": pool * 10.0}
    bt = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    positions = jnp.asarray([2, 5], jnp.int32)
    # slot 0 accepted path = tree slots (2, 5); slot 1 produced nothing
    path = jnp.asarray([[2, 5], [1, 3]], jnp.int32)
    n_new = jnp.asarray([3, 0], jnp.int32)
    out = compact_accepted(cache, bt, positions, path, n_new, ps)
    ref = np.asarray(pool).copy()

    def flat(slot, pos):                  # (page, offset) of a position
        return np.asarray(bt)[slot][pos // ps], pos % ps

    for layer in range(L):
        for i, src in enumerate((2, 5)):  # path -> pos+1+i
            sp_, so = flat(0, 2 + src)
            dp, do = flat(0, 2 + 1 + i)
            ref[layer, :, dp, do] = np.asarray(pool)[layer, :, sp_, so]
    np.testing.assert_array_equal(np.asarray(out["k_pages"]), ref)
    np.testing.assert_array_equal(np.asarray(out["v_pages"]), ref * 10.0)


# ---------------------------------------------------------------------------
# engine property 1: degenerate tree == chain, bit for bit
# ---------------------------------------------------------------------------

def _run_spec_engine(seed, max_new, profile, *, spec_k=0, spec_fanout=None,
                     adaptive=False, use_pallas=False):
    cfg, api, params = _tiny()
    with engine_kernels(use_pallas):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(num_slots=2, max_seq=24, page_size=4,
                         spec_k=spec_k, spec_fanout=spec_fanout,
                         spec_adaptive=adaptive,
                         spec_draft_layers=draft_layers(cfg, profile)),
            GREEDY, draft_params=_draft(profile))
    prompts = _prompts(cfg.vocab, (5, 9, 4), seed=seed)
    rids = [eng.submit(p, max_new) for p in prompts]
    res = eng.run()
    out = {r["rid"]: list(r["tokens"]) for r in res["results"]}
    return eng, [out[r] for r in rids], res["metrics"]


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 3),
       st.sampled_from(["w4", "w4s75", "w4l50"]))
def test_degenerate_tree_bit_identical_to_chain(seed, k, profile):
    """A fanout-1 tree IS the chain: generated tokens, the entire paged
    KV pool, and the per-slot position counters end bit-identical to the
    PR 2 chain spec path for any seed/K/draft profile."""
    eng_c, toks_c, _ = _run_spec_engine(seed, 6, profile, spec_k=k)
    eng_t, toks_t, _ = _run_spec_engine(seed, 6, profile,
                                        spec_fanout=(1,) * k)
    assert toks_c == toks_t
    for lc, lt in zip(jax.tree_util.tree_leaves(eng_c.kv.data),
                      jax.tree_util.tree_leaves(eng_t.kv.data)):
        np.testing.assert_array_equal(np.asarray(lc), np.asarray(lt))
    np.testing.assert_array_equal(np.asarray(eng_c._positions),
                                  np.asarray(eng_t._positions))


# ---------------------------------------------------------------------------
# engine property 2: tree accept/reject traffic never leaks a page
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.sampled_from([(2,), (2, 2), (1, 2), (3, 1)]),
       st.sampled_from(["w4s75", "w4l50"]))
def test_tree_allocator_leak_free(seed, fanout, profile):
    """Random accept/reject tree rounds interleaved with slot admission
    and eviction (the pool only fits ~one resident request, so requests
    stream through) drain the free list back to its initial state — tree
    reserve/compact/rewind never touches the allocator mid-request."""
    cfg, api, params = _tiny()
    lookahead = TreeTemplate(fanout).n_nodes
    pages_per_req = -(-(16 + lookahead) // 4)
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=16, page_size=4,
                     num_pages=pages_per_req + 1, spec_fanout=fanout,
                     spec_draft_layers=draft_layers(cfg, profile)),
        GREEDY, draft_params=_draft(profile))
    initial_free = eng.kv.allocator.num_free
    lens = np.random.default_rng(seed).integers(3, 8, size=4)
    for p in _prompts(cfg.vocab, tuple(lens), seed=seed):
        eng.submit(p, 4)
    res = eng.run()
    assert len(res["results"]) == 4
    assert all(r["n_generated"] == 4 for r in res["results"])
    assert eng.kv.allocator.num_free == initial_free


# ---------------------------------------------------------------------------
# greedy losslessness at a real branching fanout + adaptive controller
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fanout", [(2, 2), (3, 2, 1)])
def test_tree_spec_greedy_lossless(fanout):
    """Greedy tree-speculative output is token-for-token identical to
    greedy non-speculative output at branching fanouts (losslessness
    cannot depend on tree shape or draft quality)."""
    cfg, api, params = _tiny()
    prompts = _prompts(cfg.vocab, (5, 9, 4), seed=3)
    eng0 = InferenceEngine(cfg, params,
                           EngineConfig(num_slots=2, max_seq=24,
                                        page_size=4), GREEDY)
    rids0 = [eng0.submit(p, 6) for p in prompts]
    by0 = {r["rid"]: list(r["tokens"]) for r in eng0.run()["results"]}
    eng1, toks1, m = _run_spec_engine(3, 6, "w4s75", spec_fanout=fanout)
    assert [by0[r] for r in rids0] == toks1
    assert m["spec_rounds"] > 0
    assert np.isfinite(m["accepted_len_mean"])
    assert m["verify_tokens"] > 0


def test_tree_spec_temperature_sampling_runs():
    """Sampled path at a branching fanout: budgets exact, tokens valid,
    acceptance accounting sane, pool drained."""
    cfg, api, params = _tiny()
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=24, page_size=4,
                     spec_fanout=(2, 2),
                     spec_draft_layers=draft_layers(cfg, "w4")),
        SamplingParams(temperature=0.8, top_k=16),
        draft_params=_draft("w4"))
    for p in _prompts(cfg.vocab, (4, 6, 5), seed=11):
        eng.submit(p, 5)
    res = eng.run()
    assert len(res["results"]) == 3
    for r in res["results"]:
        assert r["tokens"].shape == (5,)
        assert (r["tokens"] >= 0).all() and (r["tokens"] < cfg.vocab).all()
    m = res["metrics"]
    assert m["draft_accepted"] <= m["draft_proposed"]
    assert eng.kv.allocator.num_free == eng.kv.num_pages


def test_adaptive_ladder_controller():
    """The adaptive controller maps the active-slot EWMA floor onto the
    ladder: thrash -> chain K=1, mid -> depth-equal chain, high -> the
    full tree; and an adaptive run stays lossless."""
    cfg, api, params = _tiny()
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=24, page_size=4,
                     spec_fanout=(2, 2), spec_adaptive=True,
                     spec_draft_layers=draft_layers(cfg, "w4s75")),
        GREEDY, draft_params=_draft("w4s75"))
    assert eng._fanout_ladder == [(1,), (1, 1), (2, 2)]
    from repro.engine.scheduler import DECODE
    eng.submit(np.arange(4, dtype=np.int32), 2)
    for r in eng.scheduler.admit():       # occupy a slot so min() is real
        r.state = DECODE
    eng._accept_ewma[:] = 0.1
    assert eng._segment_fanout() == (1,)
    eng._accept_ewma[:] = 0.5
    assert eng._segment_fanout() == (1, 1)
    eng._accept_ewma[0] = 0.9             # min over ACTIVE slots decides
    eng._accept_ewma[1] = 0.9
    assert eng._segment_fanout() == (2, 2)
    # end-to-end adaptive run == non-spec greedy
    _, toks_a, _ = _run_spec_engine(5, 6, "w4s75", spec_fanout=(2, 2),
                                    adaptive=True)
    eng0 = InferenceEngine(cfg, params,
                           EngineConfig(num_slots=2, max_seq=24,
                                        page_size=4), GREEDY)
    rids0 = [eng0.submit(p, 6) for p in _prompts(cfg.vocab, (5, 9, 4),
                                                 seed=5)]
    by0 = {r["rid"]: list(r["tokens"]) for r in eng0.run()["results"]}
    assert [by0[r] for r in rids0] == toks_a
