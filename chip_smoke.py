#!/usr/bin/env python3
"""Smoke run of the GQSA serving path on one TPU chip.

    python3 chip_smoke.py

Runs from the root of a checkout with no environment variables. It builds
starcoder2-3b at its published widths and full depth (30 layers, d_model
3072, 24 heads, 2 KV heads, d_ff 12288, vocab 49152) with random weights
from a seed, packs every linear as GQSA W4 S50 G16 on the host, moves the
packed tree to the chip, and then:

1. compares the logits of one prefill and a few decode steps through the
   Pallas kernels with the plain-jnp reference path (``use_pallas=False``)
   on the same packed weights, and checks that faults planted in the
   kernel path's inputs read above the tolerance;
2. serves 8 requests (prompts of 64-512 tokens, 32 new tokens each)
   through ``InferenceEngine`` on the paged KV pool, once to compile and
   once timed.

Any failure exits non-zero. The last line of standard output is one JSON
object naming the device; it is printed only when every phase passed.
The timings printed before it are a smoke run, not a benchmark.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "starcoder2_3b"
SEED = 0
GROUP, SPARSITY = 16, 0.5          # the paper's W4 S50 G16
SLOTS, REQUESTS, MAX_NEW = 8, 8, 32
PROMPT_MIN, PROMPT_MAX = 64, 512
PAGE = 16
CHECK_BATCH, CHECK_PROMPT, CHECK_STEPS = 2, 128, 4
# Logit tolerance, relative to the largest reference logit. Both paths
# carry bf16 activations between layers; they differ where the kernels
# round: the GEMV feeds the MXU bf16 dequantized weights (the reference
# multiplies f32 weights), and the paged kernel accumulates attention in
# f32 where the reference uses a bf16 value product. Each is a relative
# error of a few bf16 ulps (2^-8 = 3.9e-3) per layer, compounding over
# 30 residual layers. A wrong kernel must read above the tolerance:
# check_logits plants three faults (a page of another slot, a length one
# short, the KV heads swapped) and the run fails if any of them reads
# within it.
# On a TPU v5e at this seed the worst honest reading is 1.307e-2 and the
# subtlest fault (length one short) 3.846e-2; the tolerance is their
# geometric mean, about 1.7x from each.
LOGIT_RTOL = 2.2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> int:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    return 1


def build_params(cfg):
    """Random f32 weights built and packed on the host by the serving
    CLI's own helper; only the packed tree is moved to the chip."""
    import argparse
    import jax
    from repro.launch.serve import compressed_params
    args = argparse.Namespace(compress="gqsa", sparsity=SPARSITY,
                              group_size=GROUP)
    return jax.block_until_ready(
        compressed_params(cfg, args, jax.random.PRNGKey(SEED)))


def rel_err(what, ker, ref) -> float:
    """max|ker - ref| / max|ref| over a batch of logits, both finite."""
    import numpy as np
    ker = np.asarray(ker, np.float32)
    ref = np.asarray(ref, np.float32)
    if ker.shape != ref.shape or not np.isfinite(ker).all():
        raise AssertionError(f"{what}: kernel logits {ker.shape} "
                             f"not finite / not {ref.shape}")
    return float(np.abs(ker - ref).max() / np.abs(ref).max())


def check_logits(cfg, params):
    """Prefill + CHECK_STEPS decode steps through the kernels and through
    the reference, same packed weights and same fed tokens. At the first
    decode step it also plants faults in the kernel path's inputs (a page
    of another slot, a length one short, the KV heads swapped) and reads
    them the same way. Returns (worst honest reading, least fault
    reading)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.registry import get_model
    api = get_model(cfg)
    b, s = CHECK_BATCH, CHECK_PROMPT
    mp = -(-(s + CHECK_STEPS) // PAGE)
    rng = np.random.default_rng(SEED)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    lengths = jnp.asarray([s, s - 37], jnp.int32)
    bt = jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp)

    def steps(use_pallas):
        prefill = jax.jit(lambda p, c, t, l, blk: api.prefill(
            p, c, t, l, blk, cfg, None, use_pallas))
        decode = jax.jit(lambda p, c, t, pos, blk: api.decode_step(
            p, c, t, pos, cfg, None, use_pallas, block_tables=blk))
        return prefill, decode

    runs = {flag: steps(flag) for flag in (True, False)}
    caches = {flag: api.init_paged_cache(cfg, b * mp, PAGE)
              for flag in runs}
    worst, least_fault = 0.0, float("inf")

    def compare(what, ker, ref):
        nonlocal worst
        err = rel_err(what, ker, ref)
        log(f"  {what}: max|logit diff| / max|logit| = {err:.3e}")
        worst = max(worst, err)

    def plant_faults(cache, feed, pos, ref):
        nonlocal least_fault
        _, decode = runs[True]
        flipped = {k: jnp.flip(v, axis=1) for k, v in cache.items()}
        faults = {"page of another slot": (cache, pos,
                                           bt.at[0, 3].set(bt[1, 3])),
                  "length one short": (cache, pos - 1, bt),
                  "KV heads swapped": (flipped, pos, bt)}
        for name, (c, p, blk) in faults.items():
            out, _ = decode(params, c, feed[:, None], p, blk)
            err = rel_err(name, out, ref)
            log(f"  planted fault, {name}: max|logit diff| / max|logit| "
                f"= {err:.3e}")
            least_fault = min(least_fault, err)

    out = {}
    for flag, (prefill, _) in runs.items():
        out[flag], caches[flag] = prefill(params, caches[flag], tokens,
                                          lengths, bt)
    compare("prefill", out[True], out[False])
    pos = lengths
    for i in range(CHECK_STEPS):
        feed = jnp.argmax(out[False][:, -1], axis=-1).astype(jnp.int32)
        before = caches[True]
        for flag, (_, decode) in runs.items():
            out[flag], caches[flag] = decode(params, caches[flag],
                                             feed[:, None], pos, bt)
        compare(f"decode step {i}", out[True], out[False])
        if i == 0:
            plant_faults(before, feed, pos, out[False])
        pos = pos + 1
    return worst, least_fault


def serve(cfg, params):
    """Serve REQUESTS requests through the engine; returns (engine,
    results, seconds). The engine picks the kernels from the platform."""
    import numpy as np
    from repro.engine import EngineConfig, InferenceEngine, SamplingParams
    engine = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=SLOTS, max_seq=PROMPT_MAX + MAX_NEW,
                     page_size=PAGE, seed=SEED),
        SamplingParams())
    if not engine._use_pallas:
        raise AssertionError("the engine did not select the kernels")
    rng = np.random.default_rng(SEED)
    for n in rng.integers(PROMPT_MIN, PROMPT_MAX + 1, REQUESTS):
        engine.submit(rng.integers(0, cfg.vocab, n).astype(np.int32),
                      MAX_NEW)
    t0 = time.perf_counter()
    out = engine.run()
    secs = time.perf_counter() - t0
    res = out["results"]
    if len(res) != REQUESTS:
        raise AssertionError(f"served {len(res)} of {REQUESTS} requests")
    for r in res:
        toks = np.asarray(r["tokens"])
        if toks.shape != (MAX_NEW,) or toks.min() < 0 \
                or toks.max() >= cfg.vocab:
            raise AssertionError(f"request {r['rid']}: bad tokens "
                                 f"{toks.shape} in [{toks.min()}, "
                                 f"{toks.max()}]")
    return engine, res, secs


def main() -> int:
    try:
        import jax
        from repro.configs.registry import get_config
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return fail(f"cannot import the program ({e}); run from the root "
                    "of a checkout")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return fail(f"no TPU found: JAX reports platform {dev.platform!r}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = build_params(cfg)
    nbytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(params))
    log(f"setup: {cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} "
        f"packed GQSA W4 S{int(SPARSITY * 100)} G{GROUP} on the host, "
        f"{nbytes / 2**30:.2f} GiB on the chip, "
        f"{time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    worst, least_fault = check_logits(cfg, params)
    log(f"logit check (kernels vs reference, compile included): worst "
        f"{worst:.3e} <= {LOGIT_RTOL:.1e} < least planted fault "
        f"{least_fault:.3e} in {time.perf_counter() - t0:.1f}s")
    if not worst <= LOGIT_RTOL:
        return fail(f"kernel logits off by {worst:.3e} of the logit range "
                    f"(tolerance {LOGIT_RTOL:.1e})")
    if not least_fault > LOGIT_RTOL:
        return fail(f"a planted fault reads {least_fault:.3e}, within the "
                    f"tolerance {LOGIT_RTOL:.1e}: the check cannot see it")

    _, _, compile_s = serve(cfg, params)
    log(f"compile: first engine run (compiles prefill + decode) "
        f"{compile_s:.1f}s")
    engine, _, serve_s = serve(cfg, params)
    # host clock to results on the host (run() materializes every token,
    # so the device is done). The engine's own TPOT timestamps decode
    # tokens at dispatch under two-deep dispatch, so it is not used here;
    # prefill blocks on its first token, so TTFT is a completed time.
    ttft_s = engine.metrics.summary()["ttft_ms_p99"] / 1e3
    tpot_s = (serve_s - ttft_s) / (MAX_NEW - 1)
    log(f"serve: {REQUESTS} requests x {MAX_NEW} new tokens, prompts "
        f"{PROMPT_MIN}-{PROMPT_MAX}, {SLOTS} slots in {serve_s:.3f}s")
    log(f"[smoke run, not a benchmark] {REQUESTS * MAX_NEW / serve_s:.1f} "
        f"tok/s | TTFT p50 "
        f"{engine.metrics.summary()['ttft_ms_p50']:.1f}ms p99 "
        f"{ttft_s * 1e3:.1f}ms | TPOT (decode wall / step, all slots "
        f"together) {tpot_s * 1e3:.2f}ms")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak device memory {stats['peak_bytes_in_use'] / 2**30:.2f} "
            "GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
