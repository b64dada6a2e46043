"""The FLOP and byte counts behind each roofline and step share, against
values worked by hand at one small shape, and the readers' arithmetic on
a stub trace."""
import os
import sys

import numpy as np
import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]

from bench import harness  # noqa: E402
from bench.models.dense_transformer import Model  # noqa: E402
from bench.weights import Compression  # noqa: E402

METRICS = os.path.join(CHECKOUT, "bench", "layer_metrics")

# 2 layers, d 256, 4 heads of 64, 2 KV heads, GELU d_ff 512, vocab 1000
MODEL = Model(layers=2, d=256, heads=4, kv_heads=2, head_dim=64, d_ff=512,
              vocab=1000, gated=False, tied=True, rope_theta=1e4, eps=1e-5)
COMP = Compression(bits=4, sparsity=0.5, group_size=16)


def _reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"))


def test_gqsa_gemv_work_by_hand():
    # kept groups per row: K=256 -> 8, K=512 -> 16. Paper BSR bytes per
    # linear 4 (N+1) + 13 N M: wq 27652, wk 13828, wv 13828, wo 27652,
    # wu 55300, wd 54276 = 192536 per layer; activations for 4 tokens,
    # 2 bytes each way: 4 * 2 * (512 + 384 + 384 + 512 + 768 + 768) = 26624
    flops, nbytes = _reader("gqsa_gemv_roofline.decode").work(MODEL, COMP, 4)
    assert nbytes == 2 * (192536 + 26624)
    # 2 * N * M * G * tokens: 262144 + 131072 * 2 + 262144 + 524288 * 2
    assert flops == 2 * 1835008


def test_paged_attention_work_by_hand():
    # K and V of 1000 context tokens: 1000 * 2 * 2 heads * 64 * 2 bytes;
    # q in and out for 4 tokens: 4 * 2 * 4 heads * 64 * 2 bytes
    flops, nbytes = _reader("paged_attention_roofline.decode").work(
        MODEL, 1000, 4)
    assert nbytes == 2 * (512000 + 4096)
    assert flops == 2 * 4 * 4 * 64 * 1000


def test_decode_context_counts_real_keys():
    s = harness.Served(rid=0, prompt=np.zeros(10, np.int32), max_new=8,
                       admitted=True, first_fill=False,
                       tokens=np.zeros(4, np.int32))
    # decode steps 1..3 attend to 11, 12 and 13 keys
    assert harness._decode_context([s]) == 36


class _StubTrace:
    def __init__(self, steps, module_ns, kernel_ns, busy_ns):
        self.steps, self.mod, self.ker, self.busy = (steps, module_ns,
                                                    kernel_ns, busy_ns)

    def modules(self, fn, window):
        ev = harness.Context(start=0.0, end=self.mod / self.steps)
        return [ev] * self.steps if fn == "decode_fn" else []

    def module_ns(self, fn, window):
        return {"decode_fn": self.mod, "prefill_fn": 2e8}.get(fn, 0.0)

    def kernel_ns(self, kernel, window, within=None):
        return self.ker

    def busy_ns(self, window):
        return self.busy


def _ctx(**kw):
    base = dict(trace=_StubTrace(10, 1e9, 5e8, 9e8), window=(0.0, 1e9),
                window_ns=1e9, model=MODEL, comp=COMP, slots=4,
                peak_flops=1e12, peak_bw=1e9, decode_steps=10,
                decode_tokens=40, decode_context_tokens=1000.0,
                requests=[])
    base.update(kw)
    return harness.Context(**base)


def test_readers_on_a_stub_trace():
    ctx = _ctx()
    # 40 tokens over 10 steps of 4 slots
    assert _reader("decode_occupancy").read(ctx) == 100.0
    assert _reader("decode_step_ms").read(ctx) == pytest.approx(100.0)
    assert _reader("idle_share.decode").read(ctx) == pytest.approx(10.0)
    # bytes-bound: 438320 B per step at 1 GB/s, 10 steps, in 0.5 s
    assert _reader("gqsa_gemv_roofline.decode").read(ctx) == \
        pytest.approx(100.0 * 10 * 438320 / 1e9 / 0.5)
    assert "bound by bytes" in ctx.notes[0]
    # FLOPs: 2 * kept * tokens + attention + head, over 1 s at 1 TFLOP/s
    kept = 2 * (256 * 8 + 128 * 8 * 2 + 256 * 8 + 512 * 8 + 256 * 16) * 16
    flops = 2 * kept * 40 + 4 * 4 * 64 * 2 * 1000 + 2 * 1000 * 256 * 40
    assert _reader("step_mfu.decode").read(ctx) == \
        pytest.approx(100.0 * flops / 1e12)


def test_prefill_readers_count_only_the_refills():
    def served(plen, first_fill):
        return harness.Served(rid=0, prompt=np.zeros(plen, np.int32),
                              max_new=8, admitted=True,
                              first_fill=first_fill,
                              tokens=np.zeros(2, np.int32))
    # the first fill was prefilled before the window opened
    ctx = _ctx(requests=[served(500, True), served(100, False),
                         served(300, False)])
    # 0.2 s of prefill programs for 400 real prompt tokens
    assert _reader("prefill_ms_per_ktok").read(ctx) == pytest.approx(500.0)
    kept = 2 * (256 * 8 + 128 * 8 * 2 + 256 * 8 + 512 * 8 + 256 * 16) * 16
    flops = sum(2 * kept * p + 4 * 4 * 64 * 2 * p * (p + 1) / 2
                + 2 * 1000 * 256 for p in (100, 300))
    assert _reader("step_mfu.prefill").read(ctx) == \
        pytest.approx(100.0 * flops / (0.2 * 1e12))


def test_counter_readers_by_hand():
    ctx = _ctx(counters={"engine.prefill_rows": 400,
                         "engine.prefill_tokens": 100,
                         "engine.decode_pages_spanned": 80,
                         "engine.decode_pages_live": 30})
    assert _reader("prefill_row_use").read(ctx) == 25.0
    assert _reader("kv_page_use.decode").read(ctx) == 37.5


def test_readers_find_nothing_without_a_trace():
    ctx = _ctx(trace=None, decode_steps=0, counters={})
    for name in ("decode_occupancy", "decode_step_ms", "prefill_ms_per_ktok",
                 "idle_share.decode", "gqsa_gemv_roofline.decode",
                 "paged_attention_roofline.decode", "step_mfu.decode",
                 "step_mfu.prefill", "prefill_row_use", "kv_page_use.decode"):
        assert _reader(name).read(ctx) is None
