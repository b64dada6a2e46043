"""Paged decode attention: dense per-step page gather vs the fused kernel.

Sweeps context length x page size x T (decode / speculative verify) on a
continuous-batching-shaped workload: a ragged batch where one slot sits at
the sweep's context length and the rest are 8x shorter, with block tables
sized for a 2x larger max_seq (the engine's worst-case reservation) — the
regime where the dense gather pays O(B * max_pages) per layer per step.

Three series per point, emitted into BENCH_serve.json via ``common.emit``:

* ``ref_dense``   — the pre-PR hot path: gather ALL table entries
  (sentinels included) into a dense [B, MP*ps, KH, D] copy, then attend.
* ``ref_clamped`` — the jnp fallback after the occupied-page clamp
  (``decode_step(max_live_pages=...)``): gather only allocated pages.
  This is a *measured* wall-clock speedup on any backend.
* ``kernel``      — the Pallas kernel's HBM traffic model (it streams
  only live pages; O(live tokens)), as a dense/kernel byte ratio. The
  kernel itself is parity-checked here at a small shape — wall-clock is
  only meaningful on a real TPU (interpret mode is a Python emulator).

    PYTHONPATH=src python benchmarks/paged_attn.py [--quick]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref as kref
from repro.launch.compile_cache import enable_compile_cache

try:
    from benchmarks.common import emit, time_call, write_bench_json
except ImportError:      # direct `python benchmarks/paged_attn.py` run
    from common import emit, time_call, write_bench_json

B, KH, R, D = 4, 2, 4, 64          # decode-shaped GQA attention


def kv_bytes_per_token(kh: int, d: int) -> int:
    """K + V bytes per cached token at deployed bf16 width."""
    return 2 * kh * d * 2


def make_case(ctx: int, page_size: int, t: int, seed: int = 0,
              b: int = B, kh: int = KH, r: int = R, d: int = D):
    """Ragged batch: slot 0 at ``ctx`` tokens, the rest at ctx/8; tables
    sized for 2*ctx (reservation) so MP = 2 * ctx/ps table entries.
    Shared with ``benchmarks/serve_engine.decode_attention_series`` so
    the table/sentinel convention lives in one place."""
    g = np.random.default_rng(seed)
    mp = 2 * ctx // page_size                       # table width (max_seq)
    lens = np.asarray([ctx] + [max(ctx // 8, t)] * (b - 1), np.int64)
    occ = -(-lens // page_size)                     # occupied pages
    num_pages = int(occ.sum()) + 1
    # distinct pages per slot, occupied prefix + sentinel tail
    ids = np.split(g.permutation(num_pages - 1).astype(np.int32),
                   np.cumsum(occ)[:-1])
    bt = np.full((b, mp), num_pages, np.int32)
    for i, pg in enumerate(ids):
        bt[i, :len(pg)] = pg
    lengths = (lens[:, None] - (t - 1) + np.arange(t)[None, :]).clip(1)
    q = jnp.asarray(g.normal(size=(b, t, kh * r, d)), jnp.float32)
    kp = jnp.asarray(g.normal(size=(kh, num_pages, page_size, d)) * 0.1,
                     jnp.float32)
    vp = jnp.asarray(g.normal(size=(kh, num_pages, page_size, d)) * 0.1,
                     jnp.float32)
    return (q, kp, vp, jnp.asarray(lengths.astype(np.int32)),
            jnp.asarray(bt), int(occ.max()), lens)


def time_dense_vs_clamped(case):
    """Wall-clock the jnp reference over a ``make_case`` workload: full
    table (dense gather) vs occupied-page clamp. Shared with
    ``serve_engine.decode_attention_series``."""
    q, kp, vp, lengths, bt, occ, _ = case
    ref = jax.jit(lambda *a: kref.paged_attention_ref(*a))
    us_dense = time_call(ref, q, kp, vp, lengths, bt)
    us_clamp = time_call(ref, q, kp, vp, lengths, bt[:, :occ])
    return us_dense, us_clamp


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smallest sweep point only (CI smoke)")
    args, _ = ap.parse_known_args(argv)
    enable_compile_cache()

    # parity gate first: the kernel must match the oracle before any
    # traffic claim is emitted (interpret mode, small shape)
    q, kp, vp, lengths, bt, occ, _ = make_case(64, 16, 3, seed=7)
    o_ref = kref.paged_attention_ref(q, kp, vp, lengths, bt)
    o_ker = ops.paged_decode_attention(q, kp, vp, lengths, bt)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)
    print("# kernel parity vs dense-gather reference: OK (max|err| "
          f"{float(jnp.max(jnp.abs(o_ker - o_ref))):.2e})")

    sweep = [(1024, 16, 1)] if args.quick else [
        (1024, 16, 1), (1024, 16, 4), (1024, 128, 1),
        (8192, 16, 1), (8192, 16, 4), (8192, 128, 1),
    ]
    speedup_8k = []
    for ctx, ps, t in sweep:
        case = make_case(ctx, ps, t)
        q, kp, vp, lengths, bt, occ, lens = case
        mp = bt.shape[1]
        us_dense, us_clamp = time_dense_vs_clamped(case)
        wall = us_dense / max(us_clamp, 1e-9)
        # HBM byte model: dense gather touches every table entry; the
        # kernel streams each slot's live pages only
        item = kv_bytes_per_token(KH, D)
        dense_bytes = B * mp * ps * item
        live_bytes = int((-(-lens // ps) * ps).sum()) * item
        traffic = dense_bytes / max(live_bytes, 1)
        tag = f"c{ctx}_ps{ps}_t{t}"
        emit(f"paged_attn_ref_dense_{tag}", us_dense,
             f"dense gather [B,{mp}*{ps}] ({dense_bytes/2**20:.1f} MiB KV "
             f"read/layer/step)", kv_bytes=dense_bytes)
        emit(f"paged_attn_ref_clamped_{tag}", us_clamp,
             f"occupied-page clamp: {wall:.2f}x vs dense",
             kv_bytes=occ * B * ps * item, speedup_vs_dense=wall)
        emit(f"paged_attn_kernel_{tag}", 0.0,
             f"live-page stream: {traffic:.2f}x less KV traffic than "
             f"dense ({live_bytes/2**20:.2f} MiB)", timed=False,
             kv_bytes=live_bytes, traffic_ratio_vs_dense=traffic)
        if ctx >= 8192:
            speedup_8k.append(wall)
        print(f"#   ctx={ctx} ps={ps} T={t}: dense {us_dense:.0f}us, "
              f"clamped {us_clamp:.0f}us ({wall:.2f}x), kernel traffic "
              f"{traffic:.2f}x less")
    if speedup_8k:
        emit("paged_attn_speedup_8k", 0.0,
             f"min measured clamped-vs-dense speedup at 8k ctx: "
             f"{min(speedup_8k):.2f}x", timed=False,
             speedup=round(min(speedup_8k), 2))
    write_bench_json()


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
