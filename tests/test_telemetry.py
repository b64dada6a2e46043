"""Engine observability layer (DESIGN.md §10): span tracer + Chrome
trace export, per-request flow events, streaming-histogram quantile
bounds, registry wiring, zero-overhead-when-off (no extra device syncs).
"""
import json

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # pragma: no cover
    from _hyp import given, settings, st

from repro.configs import get_config
from repro.engine import (EngineConfig, InferenceEngine, MetricsRegistry,
                          SpanTracer, StreamingHistogram, Telemetry)
from repro.engine.telemetry import NULL_SPAN, TID_ENGINE
from repro.models.registry import get_model

S = settings(max_examples=30, deadline=None)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("llama2_7b", reduced=True)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, api, params


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=l).astype(np.int32) for l in lens]


def _run(cfg, params, tel, *, n_req=4, max_new=6, slots=2, max_seq=32,
         spec_k=0, draft=None, dlayers=None):
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=slots, max_seq=max_seq, spec_k=spec_k,
                     spec_draft_layers=dlayers),
        draft_params=draft, telemetry=tel)
    for p in _prompts(cfg.vocab, tuple(4 + i % 3 for i in range(n_req))):
        eng.submit(p, max_new)
    return eng, eng.run()


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

def test_registry_get_or_create():
    reg = MetricsRegistry()
    c = reg.counter("a.b")
    c.inc()
    c.inc(3)
    assert reg.counter("a.b") is c and c.value == 4
    g = reg.gauge("g")
    g.set(2)
    assert reg.gauge("g") is g and g.value == 2.0
    h = reg.histogram("h")
    h.record(5.0)
    assert reg.histogram("h") is h and h.count == 1
    snap = reg.snapshot()
    assert snap["a.b"] == 4 and snap["g"] == 2.0
    assert snap["h.count"] == 1 and snap["h.p50"] == 5.0


def test_histogram_empty_and_single():
    h = StreamingHistogram()
    assert np.isnan(h.quantile(50)) and np.isnan(h.mean)
    h.record(7.25)
    # single sample: every quantile is that sample, exactly (clamped to
    # [min, max])
    for q in (0, 50, 99, 100):
        assert h.quantile(q) == 7.25
    assert h.mean == 7.25


def test_histogram_zero_bucket_exact():
    h = StreamingHistogram()
    for _ in range(10):
        h.record(0.0)
    h.record(100.0)
    assert h.quantile(50) == 0.0
    assert h.quantile(100) == 100.0


def test_histogram_monotone_in_q():
    h = StreamingHistogram()
    xs = np.random.default_rng(1).uniform(0.01, 1e4, 300)
    for v in xs:
        h.record(v)
    qs = [h.quantile(q) for q in range(0, 101, 5)]
    assert all(a <= b + 1e-12 for a, b in zip(qs, qs[1:]))


def _check_quantile_bound(xs, qs):
    h = StreamingHistogram()
    for v in xs:
        h.record(v)
    for q in qs:
        exact = float(np.percentile(xs, q, method="lower"))
        got = h.quantile(q)
        if exact == 0.0:
            assert got == 0.0
        else:
            assert abs(got - exact) / exact <= h.rel_error_bound, (
                f"q={q}: {got} vs exact {exact} "
                f"(bound {h.rel_error_bound})")


def test_histogram_quantile_bound_grid():
    """Deterministic version of the property test (runs even without
    hypothesis): quantiles stay within rel_error_bound of the exact
    order statistic across distributions spanning decades."""
    rng = np.random.default_rng(0)
    cases = [
        rng.lognormal(2, 1.5, 1000),
        rng.uniform(1e-3, 1e3, 500),
        np.full(100, 42.0),
        rng.exponential(250.0, 733),
        np.concatenate([np.zeros(50), rng.uniform(1, 100, 50)]),
    ]
    for xs in cases:
        _check_quantile_bound(xs, qs=(0, 10, 25, 50, 75, 90, 99, 100))


@S
@given(st.lists(st.floats(min_value=0.0, max_value=1e9,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=400),
       st.integers(min_value=0, max_value=100))
def test_histogram_quantile_bound_property(xs, q):
    _check_quantile_bound(np.asarray(xs, np.float64), qs=(q,))


# ---------------------------------------------------------------------------
# tracer mechanics
# ---------------------------------------------------------------------------

def test_disabled_tracer_is_null():
    tr = SpanTracer(enabled=False)
    assert tr.span("x") is NULL_SPAN
    # a span with args (which would become the profiler annotation's
    # stats) is the same shared no-op
    assert tr.span("x", cat="sync", bucket=4) is NULL_SPAN
    with tr.span("x") as sp:
        sp.set(tokens=3)
    tr.instant("i")
    tr.flow_point(0, "enqueue")
    tr.async_begin("w", 0)
    tr.async_end("w", 0)
    assert tr.events == []


def test_tracer_records_spans_and_args():
    tr = SpanTracer(enabled=True)
    with tr.span("outer") as sp:
        sp.set(tokens=5)
        with tr.span("inner", cat="dispatch"):
            pass
    assert [e["name"] for e in tr.events] == ["inner", "outer"]
    outer = tr.events[1]
    assert outer["ph"] == "X" and outer["args"]["tokens"] == 5
    assert tr.events[0]["cat"] == "dispatch"
    totals = tr.phase_totals()
    assert totals["outer"]["count"] == 1 and totals["outer"]["ms"] >= 0


# ---------------------------------------------------------------------------
# end-to-end: traced engine runs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_plain(tiny):
    cfg, api, params = tiny
    tel = Telemetry(trace=True)
    eng, out = _run(cfg, params, tel)
    return tel, eng, out


def _export(tel, tmp_path):
    path = tel.tracer.export(tmp_path / "trace.json")
    return json.loads(path.read_text())


def test_trace_chrome_format(traced_plain, tmp_path):
    tel, eng, out = traced_plain
    doc = _export(tel, tmp_path)
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert "ph" in ev and "pid" in ev and "name" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0 and ev["ts"] >= 0.0
    # thread metadata present (Perfetto track names)
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in doc["traceEvents"])
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"admit", "prefill", "decode_segment", "sync",
            "evict"} <= names


def test_trace_spans_monotonic_and_nested(traced_plain, tmp_path):
    """Complete events on one tid must form a proper nesting (a stack):
    sorted by start, each span ends before every enclosing one."""
    tel, eng, out = traced_plain
    doc = _export(tel, tmp_path)
    by_tid = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] == "X":
            by_tid.setdefault(ev["tid"], []).append(ev)
    assert by_tid, "no complete events"
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for ev in evs:
            t0, t1 = ev["ts"], ev["ts"] + ev["dur"]
            while stack and t0 >= stack[-1] - 1e-6:
                stack.pop()
            for end in stack:
                assert t1 <= end + 1e-6, (
                    f"span {ev['name']} [{t0},{t1}] crosses an "
                    f"enclosing span ending at {end}")
            stack.append(t1)


def test_trace_flow_covers_lifecycle(traced_plain, tmp_path):
    """Every request's flow arrow runs s -> t... -> f, and every
    submitted rid has one."""
    tel, eng, out = traced_plain
    doc = _export(tel, tmp_path)
    flows = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] in ("s", "t", "f"):
            flows.setdefault(ev["id"], []).append(ev)
    assert set(flows) == {r["rid"] for r in out["results"]}
    for rid, evs in flows.items():
        phs = [e["ph"] for e in evs]
        assert phs[0] == "s" and phs[-1] == "f"
        assert all(p == "t" for p in phs[1:-1])
        phases = [e["args"]["phase"] for e in evs]
        assert phases[0] == "enqueue" and phases[-1] == "finish"
        assert "prefill" in phases and "decode_segment" in phases
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)


def test_trace_tokens_reconcile_with_metrics(traced_plain):
    """Span-attached token counts must sum to the metrics totals: the
    trace and the summary are two views of the same run."""
    tel, eng, out = traced_plain
    span_tokens = sum(e["args"].get("tokens", 0)
                      for e in tel.tracer.events if e["ph"] == "X"
                      and e["name"] in ("prefill", "decode_segment"))
    assert span_tokens == out["metrics"]["tokens"]


def test_trace_tokens_reconcile_spec(tiny):
    from repro.core.model_compress import compress_draft, draft_layers
    cfg, api, params = tiny
    draft = compress_draft(params, cfg, profile="w4l50")
    dl = draft_layers(cfg, "w4l50")
    tel = Telemetry(trace=True)
    eng, out = _run(cfg, params, tel, spec_k=3, draft=draft, dlayers=dl)
    span_tokens = sum(e["args"].get("tokens", 0)
                      for e in tel.tracer.events if e["ph"] == "X"
                      and e["name"] in ("prefill", "spec_segment"))
    assert span_tokens == out["metrics"]["tokens"]
    names = {e["name"] for e in tel.tracer.events if e["ph"] == "X"}
    assert {"draft", "verify", "spec_segment"} <= names
    # per-round draft/verify spans are dispatch-only by contract
    assert all(e["cat"] == "dispatch" for e in tel.tracer.events
               if e["ph"] == "X" and e["name"] in ("draft", "verify"))


# ---------------------------------------------------------------------------
# overhead-when-off: tracing must not change the sync structure
# ---------------------------------------------------------------------------

def test_tracing_adds_no_device_syncs(tiny, monkeypatch):
    """Pin the zero-extra-syncs guarantee: the engine calls
    ``jax.block_until_ready`` the same number of times with tracing on
    and off (the tracer only reads the host clock at existing sync
    points)."""
    cfg, api, params = tiny
    counts = {}
    real = jax.block_until_ready

    def counted(label):
        def wrapper(x):
            counts[label] += 1
            return real(x)
        return wrapper

    for label, trace in (("off", False), ("on", True)):
        counts[label] = 0
        monkeypatch.setattr(jax, "block_until_ready", counted(label))
        _run(cfg, params, Telemetry(trace=trace))
    assert counts["on"] == counts["off"] > 0


def test_disabled_telemetry_records_no_events(tiny):
    cfg, api, params = tiny
    tel = Telemetry()                      # defaults: everything off
    eng, out = _run(cfg, params, tel)
    assert tel.tracer.events == []
    # the registry still accumulates (counters/gauges are always cheap)
    snap = tel.registry.snapshot()
    assert snap["engine.requests_finished"] == out["metrics"]["requests"]
    assert snap["sched.queue_depth"] == 0
    assert snap["kv.pages_free"] == snap["kv.num_pages"]


# ---------------------------------------------------------------------------
# metrics summary stability + registry wiring through the engine
# ---------------------------------------------------------------------------

def test_summary_keys_and_queue_wait(tiny):
    cfg, api, params = tiny
    eng, out = _run(cfg, params, Telemetry())
    m = out["metrics"]
    for k in ("requests", "tokens", "seconds", "tok_per_s",
              "decode_steps", "ttft_ms_p50", "ttft_ms_p99",
              "tpot_ms_p50", "tpot_ms_p99", "latency_ms_p50",
              "latency_ms_p99", "itl_ms_mean", "spec_rounds",
              "draft_proposed", "draft_accepted", "acceptance_rate",
              "accepted_len_mean", "verify_tokens",
              "queue_wait_ms_p50", "queue_wait_ms_p99"):
        assert k in m, k
    assert np.isfinite(m["queue_wait_ms_p50"])
    assert m["queue_wait_ms_p50"] <= m["queue_wait_ms_p99"] + 1e-9
    assert "queue p50" in eng.metrics.format_summary()


def test_engine_registry_gauges_and_counters(tiny):
    cfg, api, params = tiny
    tel = Telemetry()
    eng, out = _run(cfg, params, tel, n_req=5)
    snap = tel.registry.snapshot()
    assert snap["sched.submitted"] == 5
    assert snap["sched.admissions"] == 5 == snap["sched.evictions"]
    assert snap["kv.page_allocs"] == snap["kv.page_frees"] > 0
    assert snap["kv.occupancy"] == 0.0
    assert snap["engine.queue_wait_ms.count"] == 5
    assert snap["jit.decode_retraces"] >= 0


def test_stats_interval_emits_line(tiny, capsys):
    cfg, api, params = tiny
    _run(cfg, params, Telemetry(stats_interval_s=1e-9))
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[stats] ")]
    assert lines and "pages_free" in lines[0] and "queue" in lines[0]


# ---------------------------------------------------------------------------
# counters of the work as dispatched: padded prefill rows, spanned pages
# ---------------------------------------------------------------------------

def _counters(eng, *names):
    return tuple(eng.tel.registry.counter(n).value for n in names)


PREFILL = ("engine.prefill_rows", "engine.prefill_tokens")
PAGES = ("engine.decode_pages_live", "engine.decode_pages_spanned")


def test_prefill_rows_and_pages_full_path(tiny):
    """Prompts of 5 and 9 tokens share one prefill at bucket 16 on 2
    slots: 32 rows for 14 real tokens. Budgets 4 and 6 (page 4, no
    lookahead) reserve 3 and 4 pages, so max_live is 4; the first
    segment runs 3 steps at write positions 5..7 and 9..11 (pages
    2+2+2 and 3+3+3), the second 2 steps of the 9-token request at 12
    and 13 (4+4)."""
    cfg, api, params = tiny
    eng = InferenceEngine(cfg, params, EngineConfig(num_slots=2,
                                                    max_seq=32,
                                                    page_size=4))
    for p, n in zip(_prompts(cfg.vocab, (5, 9), seed=3), (4, 6)):
        eng.submit(p, n)
    eng.run()
    assert _counters(eng, *PREFILL) == (2 * 16, 5 + 9)
    assert eng.metrics.decode_steps == 3 + 2
    assert _counters(eng, *PAGES) == (6 + 9 + 8, (3 + 2) * 2 * 4)


def test_prefill_rows_prefix_tail_path(tiny):
    """A 19-token prompt whose first 16 tokens (four full pages) are
    cached feeds a 3-token tail, padded to 8 rows on each of 2 slots."""
    cfg, api, params = tiny
    eng = InferenceEngine(cfg, params, EngineConfig(
        num_slots=2, max_seq=32, page_size=4, prefix_cache=True))
    head, = _prompts(cfg.vocab, (16,), seed=5)
    eng.submit(head, 2)
    eng.run()
    assert _counters(eng, *PREFILL) == (2 * 16, 16)
    tail, = _prompts(cfg.vocab, (3,), seed=6)
    eng.submit(np.concatenate([head, tail]), 2)
    eng.run()
    assert _counters(eng, *PREFILL) == (2 * 16 + 2 * 8, 16 + 3)
    assert eng.tel.tracer.events == []       # counters need no tracing


def test_prefill_rows_chunked_path(tiny):
    """A 10-token prompt at a chunk budget of 4 feeds chunks of 4, 4
    and 2 tokens, each padded to the bucket floor of 8 rows on 2 slots;
    its one decode step writes position 10 (3 pages of 4) with 12 tokens
    reserved (3 pages: max_live 4)."""
    cfg, api, params = tiny
    eng = InferenceEngine(cfg, params, EngineConfig(
        num_slots=2, max_seq=32, page_size=4, prefill_chunk_tokens=4))
    p, = _prompts(cfg.vocab, (10,), seed=7)
    eng.submit(p, 2)
    eng.run()
    assert _counters(eng, *PREFILL) == (3 * 2 * 8, 10)
    assert eng.tel.registry.counter("engine.prefill_chunk_tokens") \
        .value == 10
    assert _counters(eng, *PAGES) == (3, 1 * 2 * 4)


# ---------------------------------------------------------------------------
# finish stamps at completion
# ---------------------------------------------------------------------------

def test_finish_is_stamped_when_its_last_tokens_are_ready(tiny,
                                                          monkeypatch):
    """Every request's ``finish_t`` is no earlier than the return of the
    call that retired its last token array (a boundary
    ``block_until_ready``, or the drain's host read when ``run`` ends):
    under two-deep dispatch a request's last segment is still in flight
    at the boundary that evicts it."""
    import time
    cfg, api, params = tiny
    ready = {}                       # id(array) -> (return time, how)

    def noting(real, how):
        def wrapper(x):
            out = real(x)
            t = time.perf_counter()
            for leaf in jax.tree_util.tree_leaves(x):
                ready.setdefault(id(leaf), (t, how))
            return out
        return wrapper
    monkeypatch.setattr(jax, "block_until_ready",
                        noting(jax.block_until_ready, "sync"))
    monkeypatch.setattr(jax, "device_get", noting(jax.device_get, "drain"))
    eng = InferenceEngine(cfg, params, EngineConfig(num_slots=2,
                                                    max_seq=32))
    for p, n in zip(_prompts(cfg.vocab, (4, 6, 5, 7, 4), seed=9),
                    (3, 7, 5, 4, 6)):
        eng.submit(p, n)
    eng.run()
    how = []
    for r in eng.scheduler.finished:
        t_ready, by = ready[id(eng._token_log[r.log_entries[-1]])]
        assert eng.metrics.requests[r.rid].finish_t >= t_ready, r.rid
        how.append(by)
    assert len(how) == 5
    assert "sync" in how and "drain" in how


# ---------------------------------------------------------------------------
# one span API: Chrome JSON and the profiler's host plane
# ---------------------------------------------------------------------------

def test_tracing_off_constructs_no_trace_annotation(tiny, monkeypatch):
    from repro.engine.telemetry import tracer as tracer_mod
    cfg, api, params = tiny
    made = []
    real = tracer_mod.TraceAnnotation

    class Counted(real):
        def __init__(self, *a, **k):
            made.append(a[0])
            super().__init__(*a, **k)
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Counted)
    _run(cfg, params, Telemetry())
    assert made == []
    _run(cfg, params, Telemetry(trace=True))
    assert {"admit", "prefill", "decode_segment", "evict",
            "slot_sync", "sync"} <= set(made)


HOST_SPANS = ("admit", "evict", "slot_sync", "sync", "prefill",
              "prefill_tail", "prefill_chunk", "decode_segment",
              "spec_segment", "draft", "verify")


def _profiled_run(tmp_path, fn):
    """Run ``fn`` under jax.profiler; return the host plane's engine
    spans as {name: [(start_ns, end_ns, stats)]} sorted by start."""
    import glob
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = {}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return {k: sorted(v, key=lambda s: s[0]) for k, v in spans.items()}, out


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _engine_case(tiny, case, tel):
    cfg, api, params = tiny
    if case == "spec":
        from repro.core.model_compress import compress_draft, draft_layers
        return _run(cfg, params, tel, spec_k=2,
                    draft=compress_draft(params, cfg, profile="w4l50"),
                    dlayers=draft_layers(cfg, "w4l50"))[0]
    ecfg = EngineConfig(num_slots=2, max_seq=32, page_size=4,
                        prefix_cache=case == "prefix",
                        prefill_chunk_tokens=4 if case == "chunked" else 0)
    eng = InferenceEngine(cfg, params, ecfg, telemetry=tel)
    if case == "prefix":
        head, tail = _prompts(cfg.vocab, (16, 3), seed=5)
        eng.submit(head, 3)
        eng.run()
        prompts = [np.concatenate([head, tail]), head[:9]]
    else:
        prompts = _prompts(cfg.vocab, (3, 10, 6), seed=11)
    for p in prompts:
        eng.submit(p, 4)
    eng.run()
    return eng


@pytest.mark.parametrize("case", ["chunked", "prefix", "spec"])
def test_spans_land_on_the_profiler_host_plane(tiny, tmp_path, case):
    """Under jax.profiler (CPU), every engine span is also an annotation
    of the same name on the /host:CPU plane, one per JSON span, covering
    at least the JSON span's duration; ``sync`` sits inside each
    prefill that waits and never inside ``decode_segment`` (which
    brackets only the enqueue), ``slot_sync`` inside an ``evict``, and
    ``draft``/``verify`` inside ``spec_segment``."""
    tel = Telemetry(trace=True)
    spans, _ = _profiled_run(tmp_path, lambda: _engine_case(tiny, case,
                                                            tel))
    json_spans = {}
    for ev in tel.tracer.events:
        if ev["ph"] == "X":
            json_spans.setdefault(ev["name"], []).append(ev)
    assert set(spans) == set(json_spans)
    for name, evs in json_spans.items():
        evs.sort(key=lambda e: e["ts"])
        assert len(spans[name]) == len(evs), name
        for (s, e, _), j in zip(spans[name], evs):
            assert (e - s) * 1e-3 >= j["dur"] - 1.0, name
    expect = {"admit", "evict", "slot_sync", "sync", "prefill"}
    expect |= {"chunked": {"prefill_chunk", "decode_segment"},
               "prefix": {"prefill_tail", "decode_segment"},
               "spec": {"spec_segment", "draft", "verify"}}[case]
    assert expect <= set(spans)
    syncs = spans["sync"]
    for name in ("prefill", "prefill_tail", "prefill_chunk"):
        for p in spans.get(name, []):
            waits = p[2].get("completed", 1) > 0
            assert sum(_inside(s, p) for s in syncs) == int(waits), name
    for d in spans.get("decode_segment", []):
        assert not any(_inside(s, d) for s in syncs)
    assert any(_inside(s, e) for s in spans["slot_sync"]
               for e in spans["evict"])
    for name in ("draft", "verify"):
        for d in spans.get(name, []):
            assert any(_inside(d, s) for s in spans["spec_segment"])
    # a span's creation args become the annotation's stats
    assert all(p[2]["bucket"] > 0 for p in spans["prefill"])
