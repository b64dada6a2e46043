"""What the program counts and names reaches what the benchmark reads:

* the program's registry counters, which the harness hands readers over
  a traced window, count exactly the refills that
  ``prefill_ms_per_ktok`` reads;
* a GQSA GEMV kernel named by its linear (``gqsa_gemv_wq``) reads under
  ``gqsa_gemv`` in the trace reduction.
"""
import os
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.dirname(os.path.abspath(__file__)), CHECKOUT,
                os.path.join(CHECKOUT, "src")]

from bench import harness  # noqa: E402
from bench.trace_reduce import Event, Trace  # noqa: E402
from test_bench_control import SEED, tiny  # noqa: E402,F401


def test_window_counters_count_the_refills_prefill_ms_per_ktok_reads(
        tiny, monkeypatch):   # noqa: F811
    """In a traced run, the registry counters that the harness hands
    readers as ``ctx.counters`` hold the real prompt tokens of exactly
    the refills that ``prefill_ms_per_ktok`` divides by, more rows than
    tokens, and no more live KV pages than the decode steps spanned; the
    readers of the two shares report them."""
    cell, root = tiny
    kept = {}
    real_measure, real_read = harness.measure, cell.readers["decode_occupancy"]

    def measure(srv, *a, **k):
        kept["win"] = real_measure(srv, *a, **k)
        return kept["win"]

    def read(ctx):
        kept["ctx"] = ctx
        return real_read(ctx)
    monkeypatch.setattr(harness, "measure", measure)
    monkeypatch.setitem(cell.readers, "decode_occupancy", read)
    res = harness.run(cell, SEED + 2, 1.5, True, require_chip=False,
                      cache_root=os.path.join(root, ".cache"), workers=1,
                      root=root)
    assert res["correct"], res["check"]
    refills = sum(s.prompt_len for s in kept["win"].served
                  if s.admitted and not s.first_fill)
    c = kept["ctx"].counters
    assert refills > 0
    assert c["engine.prefill_tokens"] == refills
    assert c["engine.prefill_rows"] > refills
    assert 0 < c["engine.decode_pages_live"] <= \
        c["engine.decode_pages_spanned"]
    assert c["decode_steps"] == kept["ctx"].decode_steps > 0
    m = res["metrics"]
    assert m["prefill_row_use"]["value"] == pytest.approx(
        100.0 * refills / c["engine.prefill_rows"])
    assert 0 < m["kv_page_use.decode"]["value"] <= 100.0


def test_kernels_named_by_their_linear_read_under_the_kernel():
    """A GEMV named by its linear (``gqsa_gemv_wq``, as the HLO
    instruction ``%gqsa_gemv_wq.3``) counts as ``gqsa_gemv``: kernel time
    sums over the linears and the breakdown keeps one key."""
    ops = [Event("%gqsa_gemv_wq.3 = f32[8,3072] custom-call(...)", 10, 14,
                 ""),
           Event("%gqsa_gemv_wd.7 = f32[8,3072] custom-call(...)", 15, 25,
                 ""),
           Event("%fusion.1 = bf16[8] fusion(...)", 25, 27, "")]
    mods = [Event("jit_decode_fn(3)", 9, 30, "")]
    t = Trace({"/device:TPU:0": {"modules": mods, "ops": ops}},
              [Event("bench_window", 0, 40, "")])
    win = t.span("bench_window")
    assert t.kernel_ns("gqsa_gemv", win) == 4 + 10
    assert t.kernel_ns("gqsa_gemv", win, within="decode_fn") == 4 + 10
    top = dict(t.top_ops(win, ["gqsa_gemv", "paged_attention"]))
    assert top == pytest.approx({"gqsa_gemv": 14e-9, "fusion": 2e-9})
