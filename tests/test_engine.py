"""Continuous-batching engine: paged-cache equivalence, page reuse,
backpressure, FIFO admission, sampling, metrics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.engine import (EngineConfig, InferenceEngine, PageAllocator,
                          PagedKVCache, SamplingParams, Scheduler, sample)
from repro.models import transformer as T
from repro.models.registry import get_model


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("llama2_7b", reduced=True)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, api, params


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=l).astype(np.int32) for l in lens]


# ---------------------------------------------------------------------------
# paged KV cache vs contiguous cache
# ---------------------------------------------------------------------------

def test_paged_decode_logits_match_contiguous(tiny):
    """Same tokens, same positions: paged view and contiguous cache must
    produce identical decode logits."""
    cfg, api, params = tiny
    B, PS, MAXSEQ = 2, 4, 24
    MP = MAXSEQ // PS
    prompts = _prompts(cfg.vocab, (5, 9))
    S = max(len(p) for p in prompts)
    toks = np.zeros((B, S), np.int32)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p

    # contiguous: feed prompts with per-slot positions
    cache = api.init_cache(cfg, B, MAXSEQ)
    for s in range(S):
        _, cache = T.decode_step(params, cache, jnp.asarray(toks[:, s:s+1]),
                                 jnp.full((B,), s, jnp.int32), cfg)
    # paged: one batched prefill
    pcache = T.init_paged_cache(cfg, B * MP, PS)
    bt = jnp.asarray(np.arange(B * MP, dtype=np.int32).reshape(B, MP))
    logits_pf, pcache = T.prefill(params, pcache, jnp.asarray(toks),
                                  jnp.asarray(lens), bt, cfg)

    # prefill last-token logits == full forward last-token logits
    logits_fwd, _ = T.forward(params, jnp.asarray(toks), cfg)
    ref = np.stack([np.asarray(logits_fwd)[i, lens[i] - 1]
                    for i in range(B)])
    np.testing.assert_allclose(np.asarray(logits_pf)[:, 0], ref,
                               rtol=1e-5, atol=1e-5)

    # one decode step at per-slot positions: paged == contiguous
    nxt = jnp.asarray(np.argmax(ref, -1)[:, None].astype(np.int32))
    lg_c, _ = T.decode_step(params, cache, nxt, jnp.asarray(lens), cfg)
    lg_p, _ = T.decode_step(params, pcache, nxt, jnp.asarray(lens), cfg,
                            block_tables=bt)
    np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_p),
                               rtol=1e-5, atol=1e-5)


def test_engine_matches_naive_greedy_reference(tiny):
    """End-to-end: engine generations (through eviction/refill) equal a
    naive full-forward greedy loop, token for token."""
    cfg, api, params = tiny
    MAX_NEW = 4
    prompts = _prompts(cfg.vocab, (5, 9, 4, 7), seed=3)

    def ref_generate(prompt):
        toks = list(prompt)
        out = []
        for _ in range(MAX_NEW):
            logits, _ = api.forward(params,
                                    {"tokens": jnp.asarray([toks])}, cfg)
            nxt = int(jnp.argmax(logits[0, -1]))
            out.append(nxt)
            toks.append(nxt)
        return out

    eng = InferenceEngine(cfg, params,
                          EngineConfig(num_slots=2, max_seq=16, page_size=4))
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    res = eng.run()
    by_rid = {r["rid"]: list(r["tokens"]) for r in res["results"]}
    for rid, p in zip(rids, prompts):
        assert by_rid[rid] == ref_generate(p)


# ---------------------------------------------------------------------------
# allocator: reuse + backpressure
# ---------------------------------------------------------------------------

def test_page_allocator_reuse():
    a = PageAllocator(4)
    p1 = a.alloc(3)
    assert len(set(p1)) == 3
    assert a.num_free == 1 and not a.can_alloc(2)
    a.free(p1)
    assert a.num_free == 4
    # freed pages come back: a full drain hands out every page exactly once
    p2 = a.alloc(4)
    assert sorted(p2) == [0, 1, 2, 3]
    with pytest.raises(RuntimeError):
        a.alloc(1)


def test_pages_reused_across_requests(tiny):
    """Pool sized for ONE resident request; four requests stream through by
    reusing the freed pages; pool drains back to full."""
    cfg, api, params = tiny
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=16, page_size=4, num_pages=4))
    for p in _prompts(cfg.vocab, (5, 6, 7, 5)):
        eng.submit(p, 4)   # 9-11 tokens -> 3 pages: only one fits at a time
    res = eng.run()
    assert len(res["results"]) == 4
    assert eng.kv.allocator.num_free == 4
    assert all(r["n_generated"] == 4 for r in res["results"])


def test_out_of_pages_backpressure(tiny):
    """Scheduler admits the head only while pages last, and never lets a
    later request bypass a blocked head."""
    cfg, api, params = tiny
    kv = PagedKVCache(cfg, api, num_slots=3, max_seq=16, page_size=4,
                      num_pages=3)
    sch = Scheduler(3, kv, max_seq=16)
    r0 = sch.submit(np.zeros(8, np.int32), 4)    # 12 tokens -> 3 pages
    r1 = sch.submit(np.zeros(4, np.int32), 4)    # 8 tokens  -> 2 pages
    admitted = sch.admit()
    assert [r.rid for r in admitted] == [r0]     # pool exhausted
    assert sch.admit() == []                     # r1 backpressured, queued
    assert sch.waiting[0].rid == r1
    sch.step_decoded()
    sch.finish(sch.slots[admitted[0].slot].request)
    admitted2 = sch.admit()                      # pages freed -> r1 admitted
    assert [r.rid for r in admitted2] == [r1]
    assert kv.allocator.num_free == 1


def test_oversized_request_rejected(tiny):
    cfg, api, params = tiny
    eng = InferenceEngine(cfg, params,
                          EngineConfig(num_slots=1, max_seq=16, page_size=4))
    with pytest.raises(ValueError):
        eng.submit(np.zeros(14, np.int32), 4)    # 18 > max_seq


# ---------------------------------------------------------------------------
# FIFO admission (regression: the seed loop served LIFO via queue.pop())
# ---------------------------------------------------------------------------

def test_fifo_admission_order(tiny):
    cfg, api, params = tiny
    eng = InferenceEngine(cfg, params,
                          EngineConfig(num_slots=1, max_seq=16, page_size=4))
    rids = [eng.submit(p, 2) for p in _prompts(cfg.vocab, (4, 5, 6, 4, 5))]
    eng.run()
    # one slot => service order IS admission order; must equal arrival order
    assert eng.scheduler.admission_order == rids


def test_fifo_under_backpressure(tiny):
    """Even when a later (smaller) request WOULD fit, the blocked head goes
    first once pages free up."""
    cfg, api, params = tiny
    kv = PagedKVCache(cfg, api, num_slots=2, max_seq=16, page_size=4,
                      num_pages=4)
    sch = Scheduler(2, kv, max_seq=16)
    r0 = sch.submit(np.zeros(8, np.int32), 4)    # 3 pages
    r1 = sch.submit(np.zeros(8, np.int32), 4)    # 3 pages (doesn't fit)
    r2 = sch.submit(np.zeros(2, np.int32), 2)    # 1 page (WOULD fit)
    assert [r.rid for r in sch.admit()] == [r0]
    assert sch.admit() == []                     # r2 must NOT bypass r1
    sch.finish(sch.slots[0].request)
    assert [r.rid for r in sch.admit()] == [r1, r2]


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_greedy_and_filters():
    rng = jax.random.PRNGKey(0)
    logits = jnp.asarray([[1.0, 3.0, 2.0, -1.0],
                          [0.0, 0.1, 5.0, 4.9]])
    g = sample(logits, rng, SamplingParams())
    np.testing.assert_array_equal(np.asarray(g), [1, 2])
    # top_k=1 == greedy regardless of temperature
    t1 = sample(logits, rng, SamplingParams(temperature=5.0, top_k=1))
    np.testing.assert_array_equal(np.asarray(t1), [1, 2])
    # tiny top_p keeps only the argmax
    tp = sample(logits, rng, SamplingParams(temperature=1.0, top_p=1e-6))
    np.testing.assert_array_equal(np.asarray(tp), [1, 2])
    # temperature sampling stays inside the top-k support
    draws = [int(sample(logits, jax.random.PRNGKey(i),
                        SamplingParams(temperature=1.0, top_k=2))[0])
             for i in range(20)]
    assert set(draws) <= {1, 2}


def test_engine_temperature_sampling_runs(tiny):
    cfg, api, params = tiny
    eng = InferenceEngine(
        cfg, params, EngineConfig(num_slots=2, max_seq=16, page_size=4),
        SamplingParams(temperature=0.8, top_k=16, top_p=0.95))
    for p in _prompts(cfg.vocab, (4, 6)):
        eng.submit(p, 4)
    res = eng.run()
    assert len(res["results"]) == 2
    for r in res["results"]:
        assert r["tokens"].shape == (4,)
        assert (r["tokens"] >= 0).all() and (r["tokens"] < cfg.vocab).all()


# ---------------------------------------------------------------------------
# speculative decoding (engine/spec/): losslessness + KV rollback hygiene
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def draft_sets(tiny):
    """Draft parameter sets off the same checkpoint: near-target (w4,
    high acceptance), aggressive (w4s75, frequent rejections), and
    depth-pruned (w4l50: half the layers — LayerSkip-style)."""
    from repro.core.model_compress import compress_draft
    cfg, api, params = tiny
    return {p: compress_draft(params, cfg, profile=p)
            for p in ("w4", "w4s75", "w4l50")}


def _run_engine(cfg, params, prompts, max_new, spec_k=0, draft=None,
                sampling=SamplingParams(), **ecfg):
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=24, page_size=4, spec_k=spec_k,
                     **ecfg),
        sampling, draft_params=draft)
    rids = [eng.submit(p, max_new) for p in prompts]
    res = eng.run()
    return eng, rids, res


@pytest.mark.parametrize("profile", ["w4", "w4s75", "w4l50"])
def test_spec_greedy_lossless(tiny, draft_sets, profile):
    """Greedy speculative output is token-for-token identical to greedy
    non-speculative output — for a high-acceptance, a high-rejection and
    a depth-pruned draft (losslessness cannot depend on draft quality)."""
    from repro.core.model_compress import draft_layers
    cfg, api, params = tiny
    prompts = _prompts(cfg.vocab, (5, 9, 4, 7), seed=3)
    _, rids0, res0 = _run_engine(cfg, params, prompts, 6)
    eng, rids1, res1 = _run_engine(
        cfg, params, prompts, 6, spec_k=3, draft=draft_sets[profile],
        spec_draft_layers=draft_layers(cfg, profile))
    by0 = {r["rid"]: list(r["tokens"]) for r in res0["results"]}
    by1 = {r["rid"]: list(r["tokens"]) for r in res1["results"]}
    for r0, r1 in zip(rids0, rids1):
        assert by0[r0] == by1[r1]
    m = res1["metrics"]
    assert m["spec_rounds"] > 0 and m["draft_proposed"] > 0
    assert 0.0 <= m["acceptance_rate"] <= 1.0


def test_spec_kv_rollback_leak_free(tiny, draft_sets):
    """After any mix of accept/reject rounds and request completions
    (pool sized so requests stream through a single resident slot), every
    page returns to the allocator: rollback is positional only — no page
    churn on partial rejection, no leaks at completion."""
    cfg, api, params = tiny
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=16, page_size=4, num_pages=5,
                     spec_k=3),
        SamplingParams(), draft_params=draft_sets["w4s75"])
    initial_free = eng.kv.allocator.num_free
    assert initial_free == 5
    for p in _prompts(cfg.vocab, (5, 6, 7, 5)):
        eng.submit(p, 4)   # 9-11 tokens + lookahead -> 4 pages: one resident
    res = eng.run()
    assert len(res["results"]) == 4
    assert all(r["n_generated"] == 4 for r in res["results"])
    assert eng.kv.allocator.num_free == initial_free


def test_spec_temperature_sampling_runs(tiny, draft_sets):
    """Rejection sampling path (temperature > 0): correct budgets, valid
    tokens, sane acceptance accounting."""
    cfg, api, params = tiny
    prompts = _prompts(cfg.vocab, (4, 6, 5), seed=11)
    eng, _, res = _run_engine(
        cfg, params, prompts, 5, spec_k=4, draft=draft_sets["w4"],
        sampling=SamplingParams(temperature=0.8, top_k=16))
    assert len(res["results"]) == 3
    for r in res["results"]:
        assert r["tokens"].shape == (5,)
        assert (r["tokens"] >= 0).all() and (r["tokens"] < cfg.vocab).all()
    m = res["metrics"]
    assert m["draft_accepted"] <= m["draft_proposed"]
    assert eng.kv.allocator.num_free == eng.kv.num_pages


def test_spec_requires_draft_params(tiny):
    cfg, api, params = tiny
    with pytest.raises(ValueError):
        InferenceEngine(cfg, params, EngineConfig(spec_k=2))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_reported(tiny):
    cfg, api, params = tiny
    eng = InferenceEngine(cfg, params,
                          EngineConfig(num_slots=2, max_seq=16, page_size=4))
    for p in _prompts(cfg.vocab, (4, 6, 5)):
        eng.submit(p, 4)
    m = eng.run()["metrics"]
    assert m["requests"] == 3 and m["tokens"] == 12
    assert m["tok_per_s"] > 0
    for k in ("ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50",
              "latency_ms_p99"):
        assert np.isfinite(m[k]) and m[k] >= 0
    assert m["ttft_ms_p50"] <= m["ttft_ms_p99"] + 1e-9


# ---------------------------------------------------------------------------
# the platform picks the step path; the GQSA kernel serves through it
# ---------------------------------------------------------------------------

def test_engine_path_follows_platform(tiny):
    """No option selects the kernels: on the CPU the engine runs the jnp
    references, and only the test seam turns the kernels on."""
    from _engine_utils import engine_kernels
    cfg, api, params = tiny
    ecfg = EngineConfig(num_slots=1, max_seq=16)
    assert not hasattr(ecfg, "use_pallas")
    assert not InferenceEngine(cfg, params, ecfg)._use_pallas
    with engine_kernels(True):
        assert InferenceEngine(cfg, params, ecfg)._use_pallas


def test_gqsa_engine_kernels_match_reference_greedy():
    """A reduced starcoder2-3b packed as GQSA W4 S50 G16 serves through
    the interpret-mode GQSA GEMV and paged-attention kernels inside the
    engine's layer scan (the work list is a packed leaf, not built while
    tracing), and its greedy tokens equal the reference path's."""
    from _engine_utils import engine_kernels
    from repro.core.gqs_layer import GQSAConfig
    from repro.core.model_compress import compress_params
    cfg = get_config("starcoder2_3b", reduced=True)
    params = compress_params(
        get_model(cfg).init_params(jax.random.PRNGKey(0), cfg), cfg,
        GQSAConfig())
    prompts = _prompts(cfg.vocab, (5, 11, 3, 8))

    def run(kernels):
        with engine_kernels(kernels):
            eng = InferenceEngine(cfg, params, EngineConfig(
                num_slots=2, max_seq=32, page_size=4))
        assert eng._use_pallas == kernels
        rids = [eng.submit(p, 6) for p in prompts]
        out = {r["rid"]: list(r["tokens"]) for r in eng.run()["results"]}
        return [out[r] for r in rids]

    ref = run(False)
    assert run(True) == ref
    assert all(len(t) == 6 for t in ref)
