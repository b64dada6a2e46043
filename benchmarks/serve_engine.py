"""Engine throughput: continuous-batching engine vs the seed decode loop.

The seed loop (pre-engine ``launch/serve.py``) fed prompts one token per
step through a shared position counter, popped the request queue LIFO and
round-tripped every token through ``int()`` on the host. The engine
prefills whole prompts in one batched call, tracks per-slot positions over
a paged KV cache and feeds sampled tokens back on device. Equal
slots/requests/budgets on the reduced config; the acceptance bar is
>= 2x engine tokens/s over the seed loop.

    PYTHONPATH=src python benchmarks/serve_engine.py [--compress gqsa,none]
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.engine import (EngineConfig, InferenceEngine, ResilienceConfig,
                          SamplingParams, Telemetry)
from repro.engine.loadgen import (SLO, SLOLedger, WorkloadSpec, generate,
                                  make_source)
from repro.launch.serve import compressed_params, make_requests
from repro.models.registry import get_model
from repro.launch.compile_cache import enable_compile_cache

try:
    from benchmarks.common import emit, write_bench_json
except ImportError:      # direct `python benchmarks/serve_engine.py` run
    from common import emit, write_bench_json


def decode_attention_series(cfg, ctx: int = 1024, page_size: int = 16):
    """Per-step decode-attention time at the serve model's head geometry
    (one layer, ragged [B=4] batch at ``ctx``): dense full-table gather
    (pre-PR hot path) vs the occupied-page-clamped reference the engine
    now runs off-TPU. Tracks the decode-attention share of the serve
    trajectory across PRs (the fused kernel's own win is O(live tokens)
    HBM traffic — see benchmarks/paged_attn.py, whose ``make_case``
    supplies the workload so the table/sentinel convention has one
    definition)."""
    try:
        from benchmarks.paged_attn import make_case, time_dense_vs_clamped
    except ImportError:
        from paged_attn import make_case, time_dense_vs_clamped
    case = make_case(ctx, page_size, 1, b=4, kh=cfg.n_kv_heads,
                     r=cfg.n_heads // cfg.n_kv_heads, d=cfg.hd)
    us_dense, us_clamp = time_dense_vs_clamped(case)
    emit("serve_decode_attn_dense", us_dense,
         f"per-layer decode attention, dense [B,{case[4].shape[1]}]-page "
         f"gather @ ctx {ctx}")
    emit("serve_decode_attn_clamped", us_clamp,
         f"occupied-page clamp: {us_dense / max(us_clamp, 1e-9):.2f}x "
         f"vs dense @ ctx {ctx}",
         speedup_vs_dense=us_dense / max(us_clamp, 1e-9))


def mla_series(slots: int = 2, requests: int = 6, max_new: int = 8,
               max_seq: int = 64, seed: int = 0):
    """mla_moe serve series (DESIGN.md §9): the paged LATENT cache vs a
    hypothetical dense-KV MLA cache.

    Bytes/token are static math at FULL deepseek-v2-236b geometry (the
    memory claim the latent layout exists for: kv_lora_rank + qk_rope_dim
    floats per token per layer, vs K = H * (nope + rope) plus
    V = H * v_dim for an engine that up-projected at write time); the
    decode tok/s is measured on the reduced config through the full
    engine path (prefill -> paged latent decode -> eviction)."""
    full = get_config("deepseek_v2_236b")
    m = full.mla
    el = jnp.dtype(full.dtype).itemsize
    latent_bt = (m.kv_lora_rank + m.qk_rope_dim) * el * full.n_layers
    dense_bt = full.n_heads * (m.qk_nope_dim + m.qk_rope_dim
                               + m.v_dim) * el * full.n_layers
    # not a timing (timed=False): the payload rides in the
    # machine-readable extras
    emit("serve_mla_latent_bytes_per_token", 0.0,
         f"{latent_bt / 1024:.1f} KiB/token paged latent row, "
         f"deepseek-v2-236b geometry "
         f"({dense_bt / latent_bt:.1f}x below dense KV)", timed=False,
         latent_bytes_per_token=float(latent_bt),
         dense_bytes_per_token=float(dense_bt),
         compression_vs_dense=dense_bt / latent_bt)

    cfg = get_config("deepseek_v2_236b", reduced=True)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(seed), cfg)
    prompts = make_requests(requests, cfg.vocab,
                            np.random.default_rng(seed))
    eng = engine_run(cfg, params, prompts, slots, max_new, max_seq)
    emit("serve_engine_mla_moe",
         eng["seconds"] * 1e6 / max(eng["tokens"], 1),
         f"{eng['tok_per_s']:.1f} tok/s on the paged latent cache "
         f"(reduced cell, TTFT p50 {eng['ttft_ms_p50']:.0f}ms)",
         tok_per_s=eng["tok_per_s"], ttft_ms_p50=eng["ttft_ms_p50"],
         tpot_ms_p50=eng["tpot_ms_p50"])


def seed_loop(cfg, params, prompts: List[np.ndarray], slots: int,
              max_new: int, max_seq: int) -> dict:
    """The seed repo's serving loop, verbatim semantics: shared position
    counter, one-token-per-step prompt feeding, LIFO queue, per-token
    host syncs."""
    api = get_model(cfg)
    queue = list(prompts)
    cache = api.init_cache(cfg, slots, max_seq)

    @jax.jit
    def decode(params, cache, tokens, pos):
        logits, cache = api.decode_step(params, cache, tokens, pos, cfg)
        return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), cache

    active = [None] * slots
    produced = [0] * slots
    outputs = []
    tokens = jnp.zeros((slots, 1), jnp.int32)
    n_tokens = 0
    pos = 0

    def refill(slot):
        nonlocal tokens
        if queue:
            req = queue.pop()            # the seed's LIFO bug, kept as-is
            active[slot] = req
            produced[slot] = 0
            tokens = tokens.at[slot, 0].set(int(req[0]))

    for s in range(slots):
        refill(s)
    # warmup compile outside the timed region (same courtesy the engine
    # gets via its own warmup below)
    jax.block_until_ready(decode(params, cache, tokens, jnp.int32(0))[0])

    t_start = time.perf_counter()
    while any(a is not None for a in active) and pos < max_seq - 1:
        next_tok, cache = decode(params, cache, tokens, jnp.int32(pos))
        pos += 1
        for s in range(slots):
            if active[s] is None:
                continue
            req = active[s]
            if pos < len(req):
                tokens = tokens.at[s, 0].set(int(req[pos]))
            else:
                tokens = tokens.at[s, 0].set(int(next_tok[s]))
                produced[s] += 1
                n_tokens += 1
                if produced[s] >= max_new:
                    outputs.append((len(req), produced[s]))
                    active[s] = None
                    refill(s)
    dt = time.perf_counter() - t_start
    return {"requests": len(outputs), "tokens": n_tokens, "seconds": dt,
            "tok_per_s": n_tokens / max(dt, 1e-9)}


def engine_run(cfg, params, prompts, slots, max_new, max_seq,
               warmup: bool = True, telemetry=None) -> dict:
    def once(tel=None):
        eng = InferenceEngine(
            cfg, params, EngineConfig(num_slots=slots, max_seq=max_seq),
            SamplingParams(), telemetry=tel)
        for p in prompts:
            eng.submit(p, max_new)
        return eng.run()["metrics"]
    if warmup:
        once()                           # compile prefill/decode once
    return once(telemetry)


def phase_breakdown_series(cfg, params, prompts, slots, max_new, max_seq):
    """Where a post-warmup serve run spends its wall clock, by engine
    phase span (telemetry tracer, DESIGN.md §10) — the Table-6-style
    stage decomposition of the serve trajectory. Not a per-call timing
    (timed=False): the payload is the per-phase totals."""
    tel = Telemetry(trace=True)
    m = engine_run(cfg, params, prompts, slots, max_new, max_seq,
                   telemetry=tel)
    totals = tel.tracer.phase_totals()
    top = sorted(totals.items(), key=lambda kv: -kv[1]["ms"])[:3]
    emit("serve_engine_phase_breakdown", 0.0,
         "phase ms of a traced serve run: "
         + ", ".join(f"{k} {v['ms']:.0f}ms" for k, v in top),
         timed=False, tok_per_s=m["tok_per_s"],
         **{f"{k}_ms": v["ms"] for k, v in totals.items()})


def load_sweep_series(cfg, params, slots, max_seq, seed=0):
    """Load-conditioned serve trajectory (DESIGN.md §11): the same
    seeded workload replayed open-loop at increasing offered rates
    through the engine's timed-admission path, each run judged against
    one fixed SLO. A batch-everything-at-t0 run measures capacity; this
    sweep measures what load does to it — tok/s, TTFT p99, SLO
    attainment and goodput vs offered req/s, plus one bursty point
    (gamma arrivals, same mean rate) for the clumped-arrival tail."""
    slo = SLO.parse("ttft=2000,tpot=500")
    # compile the engine path for THESE params and the sweep's exact
    # prompt shapes outside the recorded runs: replay the workload once
    # at a fast rate (arrival draws consume the same rng budget at any
    # rate, so the prompt draws — and hence the padded prefill shapes —
    # match every swept run of the same seed)
    warm = generate(WorkloadSpec(process="poisson", rate=64.0, requests=8,
                                 prompt_min=4, prompt_max=10,
                                 max_new_min=6, max_new_max=6, seed=seed),
                    cfg.vocab)
    InferenceEngine(cfg, params,
                    EngineConfig(num_slots=slots, max_seq=max_seq),
                    SamplingParams()).run(source=make_source(warm))
    sweeps = [("poisson", r, 1.0) for r in (2.0, 8.0, 32.0)]
    sweeps.append(("bursty", 8.0, 0.25))
    for process, rate, burstiness in sweeps:
        spec = WorkloadSpec(process=process, rate=rate,
                            burstiness=burstiness, requests=8,
                            prompt_min=4, prompt_max=10,
                            max_new_min=6, max_new_max=6, seed=seed)
        wl = generate(spec, cfg.vocab)
        eng = InferenceEngine(
            cfg, params, EngineConfig(num_slots=slots, max_seq=max_seq),
            SamplingParams())
        m = eng.run(source=make_source(wl))["metrics"]
        ledger = SLOLedger(slo)
        ledger.judge(eng.metrics)
        s = ledger.summary()
        emit(f"serve_load_{process}_r{rate:g}",
             m["seconds"] * 1e6 / max(m["tokens"], 1),
             f"offered {wl.offered_rate:.1f} req/s -> "
             f"{m['tok_per_s']:.1f} tok/s, TTFT p99 "
             f"{m['ttft_ms_p99']:.0f}ms, attainment {s['attainment']:.0%}, "
             f"goodput {s['goodput_tok_per_s']:.1f} tok/s",
             offered_req_per_s=wl.offered_rate, tok_per_s=m["tok_per_s"],
             ttft_ms_p99=m["ttft_ms_p99"], attainment=s["attainment"],
             goodput_tok_per_s=s["goodput_tok_per_s"])


def overload_sweep_series(cfg, params, slots, max_seq, seed=0):
    """Overload cliff (DESIGN.md §12): the same seeded workload offered
    at rates up to far beyond sustainable, against a per-request TTFT
    deadline and a KV pool sized for ~two resident requests. The ladder
    (shed -> degrade -> preempt) turns saturation into bounded verdicts
    instead of unbounded queue wait: goodput holds as offered load
    climbs, the excess lands in ``sheds``. Sheds/preemptions ride as
    machine-readable extras (unclassified by the regression gate —
    counts, not timings); goodput/attainment stay timing-class."""
    slo = SLO.parse("ttft=50")
    rcfg = ResilienceConfig(deadline_ttft_ms=50.0)
    # two priority bands: the high band preempts the low one under pool
    # pressure, so the sweep exercises the whole ladder, not just sheds
    wargs = dict(requests=64, prompt_min=4, prompt_max=10,
                 max_new_min=6, max_new_max=12, priority_levels=2,
                 seed=seed)
    ecfg = EngineConfig(num_slots=slots, max_seq=max_seq, num_pages=3,
                        resilience=rcfg)
    # compile outside the recorded runs (same prompt-shape argument as
    # the load sweep's warmup), without the deadline so every shape the
    # swept runs can hit is actually reached
    warm = generate(WorkloadSpec(process="poisson", rate=64.0, **wargs),
                    cfg.vocab)
    InferenceEngine(cfg, params,
                    dataclasses.replace(ecfg, resilience=None),
                    SamplingParams()).run(source=make_source(warm))
    for rate in (8.0, 64.0, 2000.0):
        wl = generate(WorkloadSpec(process="poisson", rate=rate, **wargs),
                      cfg.vocab)
        eng = InferenceEngine(cfg, params, ecfg, SamplingParams())
        m = eng.run(source=make_source(wl))["metrics"]
        ledger = SLOLedger(slo)
        ledger.judge(eng.metrics)
        s = ledger.summary()
        emit(f"serve_overload_r{rate:g}",
             m["seconds"] * 1e6 / max(m["tokens"], 1),
             f"offered {wl.offered_rate:.0f} req/s -> goodput "
             f"{s['goodput_tok_per_s']:.1f} tok/s, attainment "
             f"{s['attainment']:.0%}, {s['shed']} shed, "
             f"{int(m['preemptions'])} preempted",
             offered_req_per_s=wl.offered_rate, tok_per_s=m["tok_per_s"],
             goodput_tok_per_s=s["goodput_tok_per_s"],
             attainment=s["attainment"], sheds=float(s["shed"]),
             preemptions=float(m["preemptions"]))


def prefix_sweep_series(cfg, params, slots, max_seq, seed=0,
                        prompt_len=120, prefix_len=116):
    """Shared-prefix KV reuse (DESIGN.md §13): the same seeded prompt
    mix replayed offline (submit-everything — deterministic, so page
    traffic is quality-class) through the prefix-cached engine at
    increasing prefix-share ratios. TTFT falls with share (admission
    prefills only the unshared tail) and so does page traffic per
    request (shared blocks are mapped, not re-allocated); both ride the
    records the regression gate guards. Each recorded run gets its own
    same-share warmup engine so compilation of the tail-prefill buckets
    never lands in the timed region."""
    page_size = 4                        # full blocks inside the prefix
    # one slot, not args.slots: an admission wave's requests all stamp
    # their first token when the whole wave's prefill completes, so a
    # wave mixing cold and warm requests charges every member BOTH
    # group dispatches — single-request waves keep each TTFT the cost
    # of that request's own prefill. Long prefix, short tail: a warm
    # admission runs the 4-token tail staircase instead of the padded
    # full-prompt prefill, and the prompt is long enough (its own
    # max_seq, not the serve default) that the compute gap clears
    # per-dispatch host overhead on CPU runners
    slots = 1
    seq = prompt_len + 8
    # fixed seed offset: a representative template draw (the half-share
    # point actually lands 8-of-16 shared, so the sweep measures the
    # share ratio, not one seed's binomial luck)
    wargs = dict(process="poisson", rate=64.0, requests=16,
                 prompt_min=prompt_len, prompt_max=prompt_len,
                 max_new_min=8, max_new_max=8, seed=seed + 6)
    for share in (0.0, 0.5, 1.0):
        pargs = dict(wargs)
        if share > 0:
            pargs.update(prefix_share=share, prefix_pool=2,
                         prefix_len=prefix_len)
        wl = generate(WorkloadSpec(**pargs), cfg.vocab)

        def once():
            eng = InferenceEngine(
                cfg, params,
                EngineConfig(num_slots=slots, max_seq=seq,
                             page_size=page_size, prefix_cache=True,
                             # every request's prefix stays cached (the
                             # unique ones insert too): the sweep
                             # measures reuse, not LRU eviction — the
                             # eviction path has its own tests
                             num_pages=(len(wl.requests) + 2)
                             * (seq // page_size)),
                SamplingParams())
            for r in wl.requests:
                eng.submit(r.prompt, r.max_new, priority=r.priority)
            m = eng.run()["metrics"]
            return m, eng
        once()                           # compile this share's buckets
        m, eng = once()
        reg = eng.tel.registry
        n = len(wl.requests)
        pages = reg.counter("kv.page_allocs").value / n
        hits = reg.counter("prefix.hits").value
        # admission-to-first-token, mean: queue wait at this tiny scale
        # is host-noise-dominated and would bury the prefill savings;
        # the mean (not a p50) interpolates with the warm fraction
        # instead of sitting on the cold side of the mixture
        ttft = 1e3 * sum(rt.first_token_t - rt.admit_t for rt in
                         eng.metrics.requests.values()) / n
        emit(f"serve_prefix_share{int(share * 100)}",
             m["seconds"] * 1e6 / max(m["tokens"], 1),
             f"prefix share {share:.0%}: {pages:.1f} pages/request, "
             f"{int(hits)} prefix hits, TTFT mean "
             f"{ttft:.1f}ms, {m['tok_per_s']:.1f} tok/s",
             pages_per_request=pages, prefix_hits=float(hits),
             prefix_hit_tokens=float(
                 reg.counter("prefix.hit_tokens").value),
             ttft_ms_mean=ttft, tok_per_s=m["tok_per_s"])


def chunked_sweep_series(cfg, params, max_seq, seed=0, budget=8):
    """Chunked prefill vs monolithic admission under load (DESIGN.md
    §14): the same seeded Poisson workload served both ways at rates
    8 and 32 req/s. Two slots and staggered decode budgets keep decode
    occupancy high, so every admission prefill lands inside a live
    decode window — monolithic admission stalls the co-resident's next
    token for the whole prompt's prefill in ONE gap, chunking bounds
    that gap to one budget's worth. The worst such gap rides as
    ``stall_ms_max`` (the ledger's longest-single-prefill-span-overlap
    statistic). Caveats at CPU bench scale: spans are sync-inclusive
    (a final chunk's first-token block drains the two-deep pipeline
    into its span), and reduced-model prefills are so cheap that the
    stall separation only shows under sustained co-residency — the
    conformance test (tests/test_chunked_prefill.py) pins it there;
    these records track the trajectory. TPOT p99 rides as ``tpot_p99``
    (its own check_bench rule) next to TTFT p99 and tok/s, and sits
    slightly HIGHER chunked-on at this scale (per-chunk dispatch
    overhead) — the trade the stall bound buys."""
    slots = 2
    seq = 128
    wargs = dict(process="poisson", requests=16, prompt_min=24,
                 prompt_max=64, max_new_min=4, max_new_max=16, seed=seed)
    for chunk in (0, budget):
        mode = "on" if chunk else "off"
        ecfg = EngineConfig(num_slots=slots, max_seq=seq,
                            prefill_chunk_tokens=chunk)
        # compile this mode's buckets outside the recorded runs (draws
        # consume the same rng budget at any rate, so prompt shapes —
        # and hence the jit buckets — match every swept rate)
        warm = generate(WorkloadSpec(rate=64.0, **wargs), cfg.vocab)
        InferenceEngine(cfg, params, ecfg,
                        SamplingParams()).run(source=make_source(warm))
        for rate in (8.0, 32.0):
            wl = generate(WorkloadSpec(rate=rate, **wargs), cfg.vocab)
            # traced both modes alike (host-append only): the stall
            # statistic is measured from prefill-span overlaps
            tel = Telemetry(trace=True)
            eng = InferenceEngine(cfg, params, ecfg, SamplingParams(),
                                  telemetry=tel)
            m = eng.run(source=make_source(wl))["metrics"]
            stalls = [v.stall_ms for v in
                      SLOLedger(SLO(stall_ms=1e9)).judge(eng.metrics,
                                                         tel.tracer)
                      if v.stall_ms == v.stall_ms]
            stall = max(stalls) if stalls else 0.0
            emit(f"serve_chunked_{mode}_r{rate:g}",
                 m["seconds"] * 1e6 / max(m["tokens"], 1),
                 f"chunked={mode} @ {wl.offered_rate:.1f} req/s: "
                 f"stall max {stall:.1f}ms, TPOT p99 "
                 f"{m['tpot_ms_p99']:.1f}ms, TTFT p99 "
                 f"{m['ttft_ms_p99']:.0f}ms, {m['tok_per_s']:.1f} tok/s",
                 stall_ms_max=stall, tpot_p99=m["tpot_ms_p99"],
                 ttft_ms_p99=m["ttft_ms_p99"], tok_per_s=m["tok_per_s"],
                 offered_req_per_s=wl.offered_rate)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--compress", default="gqsa,w4,none")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args, _ = ap.parse_known_args(argv)
    enable_compile_cache()

    cfg = get_config("llama2_7b", reduced=True)
    speedups = []
    for comp in args.compress.split(","):
        cargs = argparse.Namespace(compress=comp, sparsity=0.5,
                                   group_size=16)
        params = compressed_params(cfg, cargs, jax.random.PRNGKey(0))
        prompts = make_requests(args.requests, cfg.vocab,
                                np.random.default_rng(args.seed))
        seed = seed_loop(cfg, params, prompts, args.slots, args.max_new,
                         args.max_seq)
        eng = engine_run(cfg, params, prompts, args.slots, args.max_new,
                         args.max_seq)
        speedup = eng["tok_per_s"] / max(seed["tok_per_s"], 1e-9)
        speedups.append(speedup)
        emit(f"serve_seed_loop_{comp}",
             seed["seconds"] * 1e6 / max(seed["tokens"], 1),
             f"{seed['tok_per_s']:.1f} tok/s",
             tok_per_s=seed["tok_per_s"])
        emit(f"serve_engine_{comp}",
             eng["seconds"] * 1e6 / max(eng["tokens"], 1),
             f"{eng['tok_per_s']:.1f} tok/s ({speedup:.1f}x seed, "
             f"TTFT p50 {eng['ttft_ms_p50']:.0f}ms, "
             f"TPOT p50 {eng['tpot_ms_p50']:.1f}ms)",
             tok_per_s=eng["tok_per_s"], speedup_vs_seed=speedup,
             ttft_ms_p50=eng["ttft_ms_p50"],
             tpot_ms_p50=eng["tpot_ms_p50"])
    # phase breakdown of the last compress config's serve run
    phase_breakdown_series(cfg, params, prompts, args.slots,
                           args.max_new, args.max_seq)
    decode_attention_series(cfg)
    # load sweep on the paper configuration (GQSA-compressed serve)
    gq = argparse.Namespace(compress="gqsa", sparsity=0.5, group_size=16)
    gq_params = compressed_params(cfg, gq, jax.random.PRNGKey(0))
    load_sweep_series(cfg, gq_params, args.slots, args.max_seq,
                      seed=args.seed)
    overload_sweep_series(cfg, gq_params, args.slots, args.max_seq,
                          seed=args.seed)
    chunked_sweep_series(cfg, gq_params, args.max_seq, seed=args.seed)
    prefix_sweep_series(cfg, gq_params, args.slots, args.max_seq,
                        seed=args.seed)
    mla_series(slots=args.slots, requests=args.requests,
               max_new=args.max_new, max_seq=args.max_seq, seed=args.seed)
    print(f"# engine vs seed-loop speedups: "
          f"{', '.join(f'{s:.1f}x' for s in speedups)}")
    write_bench_json()
    return speedups


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
