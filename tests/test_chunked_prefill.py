"""Chunked prefill + two-deep dispatch (DESIGN.md §14): the cross-mode
differential conformance suite.

Chunking splits admitted prompts into token-budget chunks fed between
decode steps — pure scheduling, so greedy outputs must be BIT-IDENTICAL
chunked-on vs chunked-off across every serving mode (plain, chain-spec,
tree-spec, prefix-cache, mla_moe). On top of the digest grid: the chunk
planner's coverage property, leak-free paging under chunked admission,
mid-prefill preemption fold/resume losslessness, the strictly-fewer-
host-syncs pin for the two-deep loop, and the SLO ledger's TPOT-miss
prefill-interference attribution dropping to zero with chunking (the
ROADMAP's stated success metric, as a test).
"""
import dataclasses
import functools
import gc

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # pragma: no cover
    from _hyp import given, settings, st

from repro.configs import get_config
from repro.engine import (EngineConfig, InferenceEngine, SamplingParams,
                          Telemetry, plan_chunks)
from repro.engine.loadgen import SLO, SLOLedger, generate
from repro.engine.loadgen import WorkloadSpec
from repro.models.registry import get_model

from _engine_utils import ScriptedSource, by_rid, make_prompts, \
    shared_prompts

GREEDY = SamplingParams()


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("llama2_7b", reduced=True)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, api, params


@functools.lru_cache(maxsize=2)
def _tiny_mla():
    """Reduced mla_moe cell, dropless routing (the repo's equivalence-
    check convention, test_models.py): capacity truncation depends on
    the flattened token count, which differs between a chunk feed and a
    monolithic prefill — dropless is what makes the pin exact."""
    cfg = get_config("deepseek_v2_236b", reduced=True)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@functools.lru_cache(maxsize=2)
def _draft(arch):
    from repro.core.model_compress import compress_draft
    if arch == "mla":
        cfg, params = _tiny_mla()
    else:
        cfg = get_config("llama2_7b", reduced=True)
        params = get_model(cfg).init_params(jax.random.PRNGKey(0), cfg)
    return compress_draft(params, cfg, profile="w4s75")


# ---------------------------------------------------------------------------
# the differential digest grid
# ---------------------------------------------------------------------------

def _mode_setup(mode, tiny):
    from repro.core.model_compress import draft_layers
    cfg, api, params = tiny
    ekw, dp = {}, None
    prompts = make_prompts(cfg.vocab, (9, 3, 13, 6, 11), seed=21)
    if mode == "mla":
        cfg, params = _tiny_mla()
        prompts = make_prompts(cfg.vocab, (9, 3, 13, 6), seed=21)
    elif mode in ("chain", "tree"):
        dp = _draft("plain")
        ekw["spec_draft_layers"] = draft_layers(cfg, "w4s75")
        if mode == "chain":
            ekw["spec_k"] = 2
        else:
            ekw["spec_fanout"] = (2, 2)
    elif mode == "prefix":
        ekw["prefix_cache"] = True
        # tails longer than the budget so the chunked run chunks TAILS
        # (first chunk starts at the shared boundary, DESIGN.md §14)
        prompts = shared_prompts(cfg.vocab, 8, [7, 0, 11], seed=22) \
            + make_prompts(cfg.vocab, (6,), seed=23)
    return cfg, params, prompts, ekw, dp


def _run_grid(mode, tiny, chunk):
    cfg, params, prompts, ekw, dp = _mode_setup(mode, tiny)
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=32, page_size=4,
                     prefill_chunk_tokens=chunk, **ekw),
        GREEDY, draft_params=dp)
    for p in prompts:
        eng.submit(p.copy(), 6)
    out = eng.run()
    alc = eng.kv.allocator
    assert alc.num_free + alc.num_outstanding == eng.kv.num_pages
    return eng, out


@pytest.mark.parametrize("mode", ["plain", "chain", "tree", "prefix",
                                  "mla"])
def test_chunked_bit_identical(mode, tiny):
    """The tentpole pin: greedy token streams are bit-identical with
    chunked prefill on (budget 5) vs off, in every serving mode."""
    _, off = _run_grid(mode, tiny, 0)
    eng, on = _run_grid(mode, tiny, 5)
    assert by_rid(on) == by_rid(off)
    assert len(on["results"]) == len(off["results"]) >= 4
    # the chunked run must actually have chunked (multi-chunk prompts
    # exist in every mode's prompt set)
    reg = eng.tel.registry
    assert reg.counter("engine.prefill_chunks").value > 0
    assert reg.counter("engine.prefill_chunk_tokens").value > 0


def test_chunk_budget_one_token(tiny):
    """Degenerate budget 1 = one-token-per-boundary prompt feeding —
    the most interleavings possible, still bit-identical."""
    _, off = _run_grid("plain", tiny, 0)
    _, on = _run_grid("plain", tiny, 1)
    assert by_rid(on) == by_rid(off)


# ---------------------------------------------------------------------------
# chunk planner property (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=200, deadline=None)
@given(st.integers(1, 256), st.integers(0, 255), st.integers(-1, 64))
def test_plan_chunks_covers_exactly_once(prompt_len, start, budget):
    """For arbitrary (start, prompt_len, budget): chunks are contiguous,
    cover [start, prompt_len) exactly once, never exceed a positive
    budget, and the final chunk ends exactly at prompt_len."""
    start = start % prompt_len
    chunks = plan_chunks(start, prompt_len, budget)
    p = start
    for cs, cn in chunks:
        assert cs == p
        assert cn >= 1
        if budget > 0:
            assert cn <= budget
        p = cs + cn
    assert p == prompt_len
    if budget > 0:
        assert len(chunks) == -(-(prompt_len - start) // budget)
    else:
        assert len(chunks) == 1


# ---------------------------------------------------------------------------
# leak-free paging under chunked admission (PR 9 storm idiom)
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 6))
def test_chunked_admission_leak_free(tiny, seed, budget):
    """Waves of mixed-length prompts churn through a pool sized for ~2
    resident requests with chunking on: refcount-weighted conservation
    holds at the end, every request drains fully, no page leaks."""
    cfg, api, params = tiny
    rng = np.random.default_rng(seed)
    lens = rng.integers(2, 14, size=8)
    prompts = make_prompts(cfg.vocab, lens, seed=seed % 997)
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=32, page_size=4, num_pages=12,
                     prefill_chunk_tokens=budget),
        GREEDY)
    for p in prompts:
        eng.submit(p, 4)
    out = eng.run()
    assert len(out["results"]) == len(prompts)
    assert all(r["n_generated"] == 4 for r in out["results"])
    alc = eng.kv.allocator
    assert alc.num_free + alc.num_outstanding == eng.kv.num_pages
    assert alc.num_outstanding == 0


# ---------------------------------------------------------------------------
# mid-prefill preemption folds and resumes bit-identically
# ---------------------------------------------------------------------------

def test_midprefill_preemption_lossless(tiny):
    """A high-priority arrival lands while a low-priority prompt is
    mid-chunk: the PREFILLING victim (full remaining budget) is
    preempted first, its empty fold re-queues the original prompt, and
    the re-admission replays the chunk ladder — outputs bit-identical
    to an ample-pool run that never preempts."""
    cfg, api, params = tiny
    low_long = make_prompts(cfg.vocab, (12,), seed=31)[0]
    low_short = make_prompts(cfg.vocab, (4,), seed=32)[0]
    big = make_prompts(cfg.vocab, (10,), seed=33)[0]
    # short gets a SMALLER budget so the PREFILLING long prompt (full
    # remaining) is the strict choose_victims front-runner at poll 2
    sched = [(1, low_long, 6, 0), (1, low_short, 3, 0), (2, big, 16, 1)]

    def run(num_pages):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(num_slots=2, max_seq=32, page_size=4,
                         num_pages=num_pages, prefill_chunk_tokens=4),
            GREEDY)
        out = eng.run(source=ScriptedSource(sched))
        alc = eng.kv.allocator
        assert alc.num_free + alc.num_outstanding == eng.kv.num_pages
        return eng, out

    eng_amp, ample = run(16)             # everything fits, no pressure
    eng_prs, pressured = run(9)          # big can only fit by eviction
    assert eng_amp.metrics.summary()["preemptions"] == 0
    assert eng_prs.metrics.summary()["preemptions"] > 0
    # the victim was taken MID-CHUNK (the point of this test): the
    # 12-token prompt at budget 4 is still PREFILLING at poll 2
    reg = eng_prs.tel.registry
    assert reg.counter("resil.midprefill_preemptions").value > 0
    assert by_rid(pressured) == by_rid(ample)
    assert len(pressured["results"]) == 3


# ---------------------------------------------------------------------------
# two-deep dispatch: strictly fewer host syncs than segments
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [0, 4])
def test_two_deep_strictly_fewer_syncs(tiny, monkeypatch, chunk):
    """The old loop blocked once per decode segment plus once per
    prefill dispatch. The two-deep loop retires the trailing segment
    only, so its ``jax.block_until_ready`` count must be STRICTLY
    below that old-loop floor (counted from the tracer's spans)."""
    cfg, api, params = tiny
    counts = [0]
    real = jax.block_until_ready

    def counted(x):
        counts[0] += 1
        return real(x)

    tel = Telemetry(trace=True)
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=32, page_size=4,
                     prefill_chunk_tokens=chunk),
        GREEDY, telemetry=tel)
    # two admission waves -> at least two decode segments
    for p in make_prompts(cfg.vocab, (9, 5, 11, 7), seed=41):
        eng.submit(p, 6)
    monkeypatch.setattr(jax, "block_until_ready", counted)
    eng.run()
    monkeypatch.setattr(jax, "block_until_ready", real)
    totals = tel.tracer.phase_totals()
    segments = totals.get("decode_segment", {}).get("count", 0)
    prefills = sum(totals.get(n, {}).get("count", 0)
                   for n in ("prefill", "prefill_tail", "prefill_chunk"))
    assert segments >= 2
    old_loop_floor = segments + prefills
    assert 0 < counts[0] < old_loop_floor


# ---------------------------------------------------------------------------
# SLO interference regression: the ROADMAP metric as a test
# ---------------------------------------------------------------------------

def _traced_workload_run(tiny, chunk):
    cfg, api, params = tiny
    # mixed prompt lengths, staggered decode budgets: slots free one at
    # a time, so each admission prefill lands inside a live co-resident
    # decode window (the interference being measured). The seeded
    # requests all arrive at the first scheduling boundary: wall-clock
    # arrivals would make which prefills overlap a decode depend on host
    # speed.
    spec = WorkloadSpec(process="poisson", rate=100.0, requests=8,
                        prompt_min=24, prompt_max=64, max_new_min=4,
                        max_new_max=16, seed=13)
    source = ScriptedSource([(1, r.prompt, r.max_new, 0)
                             for r in generate(spec, cfg.vocab).requests])
    tel = Telemetry(trace=True)
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=128,
                     prefill_chunk_tokens=chunk),
        GREEDY, telemetry=tel)
    out = eng.run(source=source)
    return eng, tel, out


def test_chunking_zeroes_prefill_interference_attribution(tiny):
    """Seeded mixed-prompt-length workload: a monolithic
    admission prefill stalls co-resident decodes for its full duration
    in ONE inter-token gap; chunking at budget 8 bounds the longest
    stall to one chunk. Judged at a stall limit every chunked request
    meets (chunked max stall x1.5), the chunked run has ZERO prefill-
    attributed misses and the monolithic run at least one (DESIGN.md
    §11's interference attribution, driven to zero — the ROADMAP's
    stated success metric as a test). Outputs stay bit-identical
    between the two runs under load."""
    # warm the jit caches so compile time doesn't land inside spans
    _traced_workload_run(tiny, 8)
    _traced_workload_run(tiny, 0)
    # a garbage-collector pause (5-10 ms) inside one chunk span would
    # read as a chunk-sized stall as long as a monolithic prefill
    gc.collect()
    gc.disable()
    try:
        eng_c, tel_c, out_c = _traced_workload_run(tiny, 8)
        eng_m, tel_m, out_m = _traced_workload_run(tiny, 0)
    finally:
        gc.enable()
    assert by_rid(out_c) == by_rid(out_m)
    assert any(e.get("name") == "prefill_chunk"
               for e in tel_c.tracer.events)
    # derive the limit from the chunked run itself: every chunked
    # request meets it by construction, so its prefill-attributed miss
    # count is 0 by measure — the regression bites iff monolithic
    # serving stalls some decode past that bound (a 24..64-token
    # monolithic prefill span vs an 8-token chunk span leaves x1.5
    # plenty of separation)
    stalls = [v.stall_ms for v in SLOLedger(SLO(stall_ms=1e9)).judge(
        eng_c.metrics, tel_c.tracer) if v.stall_ms == v.stall_ms]
    lim = max(max(stalls) * 1.5, 0.05)
    led_c = SLOLedger(SLO(stall_ms=lim))
    led_c.judge(eng_c.metrics, tel_c.tracer)
    led_m = SLOLedger(SLO(stall_ms=lim))
    led_m.judge(eng_m.metrics, tel_m.tracer)
    assert led_c.summary().get("miss_phase_prefill", 0) == 0
    assert led_m.summary()["missed_stall"] > 0
    assert led_m.summary()["miss_phase_prefill"] > 0


# ---------------------------------------------------------------------------
# chunk spans + flow events land in the trace
# ---------------------------------------------------------------------------

def test_chunk_spans_in_trace(tiny):
    cfg, api, params = tiny
    tel = Telemetry(trace=True)
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=2, max_seq=32, page_size=4,
                     prefill_chunk_tokens=3),
        GREEDY, telemetry=tel)
    for p in make_prompts(cfg.vocab, (11, 8), seed=51):
        eng.submit(p, 4)
    eng.run()
    spans = [e for e in tel.tracer.events
             if e.get("ph") == "X" and e.get("name") == "prefill_chunk"]
    # 11 tokens at budget 3 -> 4 chunks; 8 -> 3 chunks; slots co-feed
    assert len(spans) >= 4
    done = sum(e["args"].get("completed", 0) for e in spans)
    assert done == 2                      # each prompt completes once
