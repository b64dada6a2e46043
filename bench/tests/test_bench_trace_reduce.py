"""trace_reduce against hand counts: on events laid out by hand, and on a
small trace recorded on a TPU v5e (bench/testdata)."""
import os
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]

from bench.trace_reduce import Event, Trace, union  # noqa: E402

TESTDATA = os.path.join(CHECKOUT, "bench", "testdata")


def _hand_trace():
    """One chip, window [0, 100]: a decode program over [10, 40] running
    a while op over [11, 31] around a kernel over [12, 20] and a fusion
    over [20, 30]; a prefill program
    over [50, 90] running the kernel over [55, 85] and an op at [95, 120]
    past the window. Host: a prefill annotation over [45, 92] and a
    decode_segment over [0, 44]."""
    ops = [Event("%while.2 = (s32[]) while(...)", 11, 31, ""),
           Event("gqsa_gemv", 12, 20, ""),
           Event("%fusion.3 = bf16[8] fusion(...)", 20, 30, ""),
           Event("custom-call.7", 55, 85, "kernel gqsa_gemv"),
           Event("copy.1", 95, 120, "")]
    mods = [Event("jit_decode_fn(7)", 10, 40, ""),
            Event("jit_prefill_fn(8)", 50, 90, "")]
    host = [Event("bench_window", 0, 100, ""),
            Event("decode_segment", 0, 44, ""), Event("prefill", 45, 92, "")]
    return Trace({"/device:TPU:0": {"modules": mods, "ops": ops}}, host)


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_hand_counts():
    t = _hand_trace()
    win = t.span("bench_window")
    assert win == (0, 100)
    # busy: [11, 31] + [55, 85] + [95, 100] = 20 + 30 + 5
    assert t.busy_ns(win) == 55
    assert t.module_ns("decode_fn", win) == 30
    assert t.module_ns("prefill_fn", win) == 40
    assert t.module_ns("fn", win) == 0          # whole names only
    assert t.kernel_ns("gqsa_gemv", win) == 8 + 30
    assert t.kernel_ns("gqsa_gemv", win, within="decode_fn") == 8
    assert t.kernel_ns("gqsa_gemv", win, within="prefill_fn") == 30
    top = dict(t.top_ops(win, ["gqsa_gemv"]))
    assert top["gqsa_gemv"] == pytest.approx(38e-9)
    assert top["fusion"] == pytest.approx(10e-9)
    assert "while" not in top                   # it encloses the others
    # idle: [0, 11] lies under decode_segment; [31, 55] overlaps
    # decode_segment by 13 and prefill by 10, so goes to decode_segment
    # whole; [85, 95] overlaps only prefill
    gaps = dict(t.idle_gaps(win, ["decode_segment", "prefill"]))
    assert gaps["host:decode_segment"] == pytest.approx(11e-9 + 24e-9)
    assert gaps["host:prefill"] == pytest.approx(10e-9)


RECORDED = os.path.join(TESTDATA, "tiny_decode.xplane.pb.gz")
# counted from the same file straight from ProfileData, without this
# module: a one-layer model at d 512 serving four requests on a TPU v5e
# (one prefill program, six decode programs)
HAND = {"window_ns": 127831939.0, "busy_ns": 666777.0,
        "decode_programs": 6, "decode_ns": 565030.0,
        "prefill_programs": 1, "prefill_ns": 102586.0,
        "gqsa_gemv_decode_ns": 383740.0, "gqsa_gemv_prefill_ns": 65613.0,
        "paged_attention_decode_ns": 32068.0}


def test_recorded_chip_trace():
    t = Trace.load(RECORDED)
    win = t.span("bench_window")
    assert win[1] - win[0] == HAND["window_ns"]
    assert t.busy_ns(win) == pytest.approx(HAND["busy_ns"])
    assert len(t.modules("decode_fn", win)) == HAND["decode_programs"]
    assert len(t.modules("prefill_fn", win)) == HAND["prefill_programs"]
    assert t.module_ns("decode_fn", win) == pytest.approx(HAND["decode_ns"])
    assert t.module_ns("prefill_fn", win) == pytest.approx(
        HAND["prefill_ns"])
    assert t.kernel_ns("gqsa_gemv", win, within="decode_fn") == \
        pytest.approx(HAND["gqsa_gemv_decode_ns"])
    assert t.kernel_ns("gqsa_gemv", win, within="prefill_fn") == \
        pytest.approx(HAND["gqsa_gemv_prefill_ns"])
    assert t.kernel_ns("paged_attention", win, within="decode_fn") == \
        pytest.approx(HAND["paged_attention_decode_ns"])
    top = dict(t.top_ops(win, ["gqsa_gemv", "paged_attention"]))
    assert top["gqsa_gemv"] == pytest.approx(
        (HAND["gqsa_gemv_decode_ns"] + HAND["gqsa_gemv_prefill_ns"]) * 1e-9)
    idle = sum(v for _, v in t.idle_gaps(win, ["prefill", "decode_segment"],
                                        n=100))
    assert idle == pytest.approx((HAND["window_ns"] - HAND["busy_ns"])
                                 * 1e-9)
