"""Serving CLI: a thin wrapper over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch llama2_7b --reduced \
        --compress gqsa --requests 16 --max-new 32

Requests are admitted in FIFO arrival order into a fixed pool of batch
slots backed by a paged KV cache; prompts are prefilled in one batched
flash-attention call (no one-token-per-step prompt feeding) and decode
runs one fused per-slot-position step with device-side token feedback.
Reports tokens/s, TTFT, TPOT and p50/p99 latency (repro.engine).

Load-conditioned serving (DESIGN.md §11): ``--workload`` replaces the
submit-everything-up-front default with a seeded traffic spec (open-loop
Poisson / bursty / closed-loop arrival processes, prompt and budget
distributions, shared-prefix pools) whose requests arrive MID-RUN
through the engine's timed-admission loop, and ``--slo`` judges every
request against TTFT/TPOT/e2e deadlines — printing attainment, goodput
(tokens delivered within deadline) and per-miss phase attribution:

    PYTHONPATH=src python -m repro.launch.serve --arch llama2_7b \
        --workload 'process=poisson,rate=20,requests=16' \
        --slo ttft=500,tpot=50 --slo-json /tmp/slo.json
"""
from __future__ import annotations

import argparse
import hashlib
import json
import time
from typing import List

import jax
import numpy as np

from repro.configs.registry import get_config, list_draft_profiles
from repro.core.gqs_layer import GQSAConfig
from repro.core.model_compress import (compress_draft, compress_params,
                                       compress_params_w4, draft_layers)
from repro.core.pruning import PruneConfig
from repro.core.quant import QuantConfig
from repro.engine import (ChaosConfig, EngineConfig, InferenceEngine,
                          ResilienceConfig, SamplingParams, Telemetry)
from repro.engine.loadgen import SLO, SLOLedger, generate, make_source
from repro.engine.loadgen import WorkloadSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_model


def make_requests(n, vocab, rng, lo=4, hi=16):
    lens = rng.integers(lo, hi, size=n)
    return [rng.integers(0, vocab, size=l).astype(np.int32) for l in lens]


def host_device():
    """The host CPU device: random weights are built and packed there, so
    the accelerator only ever holds the packed tree."""
    return jax.local_devices(backend="cpu")[0]


def compressed_params(cfg, args, rng, fp_params=None):
    """Random FP weights from ``rng`` -> the ``--compress`` serving tree,
    built on the host; only the result moves to the default device."""
    api = get_model(cfg)
    with jax.default_device(host_device()):
        if fp_params is None:
            fp_params = api.init_params(rng, cfg)
        params = _compress(cfg, args, fp_params)
    return jax.device_put(params, jax.devices()[0])


def _compress(cfg, args, params):
    t0 = time.time()
    if args.compress == "gqsa":
        gqsa = GQSAConfig(
            quant=QuantConfig(bits=4, group_size=args.group_size),
            prune=PruneConfig(sparsity=args.sparsity,
                              group_size=args.group_size))
        params = compress_params(params, cfg, gqsa)
        print(f"packed GQSA W4 S{int(args.sparsity*100)}% "
              f"G{args.group_size} in {time.time()-t0:.1f}s")
    elif args.compress == "w4":
        params = compress_params_w4(params, cfg, QuantConfig(
            bits=4, group_size=args.group_size))
        print(f"packed W4 in {time.time()-t0:.1f}s")
    return params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2_7b")
    # reduced is the default: this CLI's job is exercising the serving
    # stack, which the reduced configs do at a fraction of the cost
    # (--full restores full-scale params for real measurements)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="full-scale params (default: reduced config)")
    ap.add_argument("--compress", default="gqsa",
                    choices=["none", "w4", "gqsa"])
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--group-size", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV page pool size (default: slots*max_seq worth)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-prefix KV reuse (DESIGN.md §13): radix "
                         "cache of full-page prompt blocks; admissions "
                         "map cached prefixes to existing pages "
                         "(refcounted, copy-on-write) and prefill only "
                         "the unshared tail — lossless for greedy")
    ap.add_argument("--chunked-prefill", type=int, default=0, metavar="N",
                    help="Sarathi-style chunked prefill (DESIGN.md §14): "
                         "split admitted prompts into N-token chunks fed "
                         "between decode steps instead of one monolithic "
                         "prefill — bounds decode-latency interference; "
                         "greedy output is bit-identical (0 = off)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per round "
                         "(0 = off); lossless — output matches non-spec")
    ap.add_argument("--spec-tree", default=None, metavar="F1,F2,..",
                    help="token-TREE drafting: top-k fanout per draft "
                         "depth (e.g. 4,2,2 = 28 nodes / depth 3); one "
                         "tree-attention verify call per round; implies "
                         "--spec; lossless like the chain")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="retune the tree online from the observed "
                         "acceptance rate (per-slot EWMA: thrash shrinks "
                         "to a chain K=1, sustained acceptance widens "
                         "back to the full --spec-tree profile)")
    ap.add_argument("--draft-profile", default="w4s75",
                    choices=list_draft_profiles(),
                    help="draft compression of the same checkpoint")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record phase spans + per-request flow events "
                         "and export Chrome trace-event JSON (load at "
                         "ui.perfetto.dev); also prints the phase "
                         "breakdown after the run")
    ap.add_argument("--stats-interval", type=float, default=0.0,
                    metavar="SEC",
                    help="print a one-line engine stats snapshot every "
                         "SEC seconds of serving (0 = off)")
    ap.add_argument("--workload", default=None, metavar="SPEC",
                    help="load-conditioned serving: a workload-spec JSON "
                         "file or inline k=v list ('process=poisson,"
                         "rate=20,requests=16,prompt=4:12,max_new=8'); "
                         "requests arrive mid-run through timed "
                         "admission instead of all up front (overrides "
                         "--requests/--max-new)")
    ap.add_argument("--slo", default=None, metavar="DEADLINES",
                    help="judge every request against deadlines (ms): "
                         "'ttft=500,tpot=50,e2e=2000' (any subset; also "
                         "'stall=50' — worst single prefill stall in the "
                         "decode window, needs --trace); prints "
                         "attainment + goodput + per-miss phase "
                         "attribution after the run")
    ap.add_argument("--slo-json", default=None, metavar="OUT.json",
                    help="also write the SLO ledger (summary + "
                         "per-request verdicts) as JSON")
    ap.add_argument("--deadline", type=float, default=None, metavar="MS",
                    help="per-request TTFT deadline (ms from arrival): "
                         "queued requests past it are SHED before "
                         "prefill instead of served late (first-class "
                         "SLO verdicts, DESIGN.md §12)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection: k=v rates per "
                         "injection-point visit ('alloc_fail=0.05,"
                         "latency=0.02,device_err=0.01,nan_logits=0.01' "
                         "— any subset, plus latency_spike_ms/retries/"
                         "backoff_ms/quarantine knobs), seeded by "
                         "--seed; same seed + same spec replays "
                         "bit-identically (offline mode)")
    args = ap.parse_args(argv)

    workload_spec = None
    if args.workload is not None:
        try:
            workload_spec = WorkloadSpec.parse(args.workload)
        except (ValueError, OSError) as e:
            ap.error(f"--workload: {e}")
    slo = None
    if args.slo is not None:
        try:
            slo = SLO.parse(args.slo)
        except ValueError as e:
            ap.error(f"--slo: {e}")
    if args.slo_json and slo is None:
        ap.error("--slo-json requires --slo")
    chaos = None
    if args.chaos is not None:
        try:
            chaos = ChaosConfig.parse(args.chaos, seed=args.seed)
        except ValueError as e:
            ap.error(f"--chaos: {e}")
    resilience = ResilienceConfig(deadline_ttft_ms=args.deadline,
                                  chaos=chaos)

    spec_fanout = None
    if args.spec_tree:
        try:
            spec_fanout = tuple(int(f) for f in args.spec_tree.split(","))
        except ValueError:
            ap.error(f"--spec-tree wants a comma list of fanouts, "
                     f"got {args.spec_tree!r}")
    spec_on = args.spec > 0 or spec_fanout is not None
    if args.spec_adaptive and spec_fanout is None:
        ap.error("--spec-adaptive requires --spec-tree")

    cfg = get_config(args.arch, reduced=args.reduced)
    # fail early, before any params are built: the engine needs the
    # family's paged cache + batched prefill (registry capability flag)
    if not get_model(cfg).supports_paged_cache:
        from repro.models.registry import paged_families
        ap.error(f"--arch {args.arch}: family {cfg.family!r} has no "
                 f"paged-cache support "
                 f"(supported: {', '.join(paged_families())})")
    enable_compile_cache()
    with jax.default_device(host_device()):
        rng = jax.random.PRNGKey(args.seed)
        # the FP tree is only needed as the shared source of target + draft
        # compression; don't keep a full-scale checkpoint alive otherwise
        fp_params = get_model(cfg).init_params(rng, cfg) if spec_on \
            else None
    params = compressed_params(cfg, args, rng, fp_params=fp_params)
    draft_params = None
    dlayers = None
    if spec_on:
        t0 = time.time()
        with jax.default_device(host_device()):
            draft_params = compress_draft(fp_params, cfg,
                                          profile=args.draft_profile,
                                          group_size=args.group_size)
        draft_params = jax.device_put(draft_params, jax.devices()[0])
        dlayers = draft_layers(cfg, args.draft_profile)
        print(f"packed draft profile {args.draft_profile} "
              f"({dlayers}/{cfg.n_layers} layers) in {time.time()-t0:.1f}s")
        if spec_fanout is not None:
            print(f"token-tree drafting: fanout {spec_fanout}"
                  + (" (adaptive)" if args.spec_adaptive else ""))
        fp_params = None                 # free the FP tree before serving

    telemetry = Telemetry(trace=args.trace is not None,
                          stats_interval_s=args.stats_interval)
    engine = InferenceEngine(
        cfg, params,
        EngineConfig(num_slots=args.slots, max_seq=args.max_seq,
                     page_size=args.page_size, num_pages=args.num_pages,
                     prefix_cache=args.prefix_cache,
                     prefill_chunk_tokens=args.chunked_prefill,
                     seed=args.seed,
                     spec_k=args.spec, spec_draft_layers=dlayers,
                     spec_fanout=spec_fanout,
                     spec_adaptive=args.spec_adaptive,
                     resilience=resilience),
        SamplingParams(temperature=args.temperature, top_k=args.top_k,
                       top_p=args.top_p),
        draft_params=draft_params, telemetry=telemetry)

    if workload_spec is not None:
        # every stream request must fit: worst-case prompt + budget
        worst = workload_spec.prompt_max + workload_spec.max_new_max
        if worst > args.max_seq:
            ap.error(f"--workload: prompt_max + max_new_max = {worst} "
                     f"exceeds --max-seq {args.max_seq}")
        workload = generate(workload_spec, cfg.vocab)
        rate = workload.offered_rate
        print(f"workload: {workload_spec.process}, "
              f"{workload_spec.requests} requests"
              + (f", offered {rate:.1f} req/s" if rate is not None
                 else f", {workload_spec.concurrency} users closed-loop"))
        out = engine.run(source=make_source(workload))
    else:
        nprng = np.random.default_rng(args.seed)
        # prompts must leave room for the generation budget within max_seq
        maxlen = args.max_seq - args.max_new
        if maxlen < 1:
            ap.error(f"--max-new {args.max_new} leaves no prompt room "
                     f"within --max-seq {args.max_seq}")
        lo = min(4, maxlen)
        hi = max(lo + 1, min(16, maxlen + 1))
        prompts: List[np.ndarray] = make_requests(args.requests, cfg.vocab,
                                                  nprng, lo=lo, hi=hi)
        for p in prompts:
            engine.submit(p, args.max_new)
        out = engine.run()

    m = out["metrics"]
    print(engine.metrics.format_summary()
          + f" ({args.slots} slots, {m['decode_steps']} decode steps)")
    if out.get("interrupted"):
        print("[interrupted] graceful drain: queue shed, in-flight "
              "requests accounted, all pages freed")
    # results digest: sha256 over (rid, tokens) in rid order — the replay
    # pin the CI chaos smoke compares across two identically-seeded runs
    h = hashlib.sha256()
    for r in sorted(out["results"], key=lambda d: d["rid"]):
        h.update(np.int64(r["rid"]).tobytes())
        h.update(np.asarray(r["tokens"], np.int32).tobytes())
    print(f"[digest] {h.hexdigest()}")
    if args.prefix_cache:
        reg = telemetry.registry
        print("[prefix] hits="
              f"{int(reg.counter('prefix.hits').value)} "
              f"misses={int(reg.counter('prefix.misses').value)} "
              f"hit_tokens={int(reg.counter('prefix.hit_tokens').value)} "
              f"cow={int(reg.counter('prefix.cow_copies').value)} "
              f"evicted={int(reg.counter('prefix.evicted_pages').value)} "
              f"cached={int(reg.gauge('prefix.cached_pages').value)}")
    if engine.chaos is not None:
        snap = engine.chaos.snapshot()
        retries = int(telemetry.registry.counter(
            "chaos.device_retries").value)
        print("[chaos] injected "
              + " ".join(f"{k}={v}" for k, v in sorted(snap.items()))
              + f" device_retries={retries} | recovered: "
              f"{int(m['preemptions'])} preemptions, "
              f"{int(m['shed'])} shed")
    slo_summary = None
    if slo is not None:
        ledger = SLOLedger(slo, registry=telemetry.registry)
        verdicts = ledger.judge(engine.metrics, telemetry.tracer)
        slo_summary = ledger.summary()
        print(ledger.format_summary())
        if args.slo_json:
            doc = {"slo": {d: slo.limit(d) for d in ("ttft", "tpot", "e2e")
                           if slo.limit(d) is not None},
                   "summary": slo_summary,
                   "requests": [{"rid": v.rid, "met": v.met,
                                 "verdict": v.verdict,
                                 "n_tokens": v.n_tokens,
                                 "ttft_ms": round(v.ttft_ms, 3),
                                 # single-token requests have no TPOT
                                 "tpot_ms": (None if v.tpot_ms != v.tpot_ms
                                             else round(v.tpot_ms, 3)),
                                 "e2e_ms": round(v.e2e_ms, 3),
                                 "queue_wait_ms": round(v.queue_wait_ms, 3),
                                 "misses": v.misses} for v in verdicts]}
            with open(args.slo_json, "w") as f:
                json.dump(doc, f, indent=2)
            print(f"wrote SLO ledger -> {args.slo_json}")
    if args.trace is not None:
        path = telemetry.tracer.export(args.trace)
        totals = telemetry.tracer.phase_totals()
        print(f"wrote trace ({len(telemetry.tracer.events)} events) -> "
              f"{path} (load at ui.perfetto.dev)")
        for name, d in sorted(totals.items(), key=lambda kv: -kv[1]["ms"]):
            print(f"  {name:16s} {d['ms']:9.2f}ms  x{d['count']}")
    # legacy result keys (kept stable for tests + examples)
    res = dict(m, requests=int(m["requests"]), tokens=int(m["tokens"]),
               results=out["results"])
    if slo_summary is not None:
        res["slo"] = slo_summary
    return res


if __name__ == "__main__":
    main()
