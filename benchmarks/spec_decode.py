"""Speculative decoding speedup vs the PR-1 non-speculative engine.

One trained checkpoint (the cached benchmark LM), two parameter sets: the
deployed GQSA-W4S50 target and an aggressively compressed draft profile.
The engine drafts K greedy tokens per round in one fused call and
verifies them in one multi-token target call, so a round costs ~2
dispatches for 1..K+1 tokens — the speedup is governed by the acceptance
rate, which we sweep across draft profiles (the paper's
quality/compression trade becomes a pure throughput knob: the verify
step pins output quality to the target regardless of draft quality).

Decode throughput (decode-phase wall time / decoded tokens) is compared
at equal slots/requests/budgets; the acceptance bar is >= 1.5x at the
default draft profile AND greedy spec output identical to non-spec.

    PYTHONPATH=src python benchmarks/spec_decode.py [--spec-k 4]
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core.bqpo import BQPOConfig
from repro.core.e2e_oqp import E2EConfig
from repro.core.gqs_layer import GQSAConfig
from repro.core.model_compress import compress_draft, draft_layers
from repro.core.pipeline import gqsa_compress
from repro.core.pruning import PruneConfig
from repro.core.quant import QuantConfig
from repro.engine import (EngineConfig, InferenceEngine, SamplingParams,
                          Telemetry)
from repro.launch.compile_cache import enable_compile_cache

try:
    from benchmarks.common import (calib_batches, emit, held_out_batches,
                                   trained_spec_model, write_bench_json)
except ImportError:      # direct `python benchmarks/spec_decode.py` run
    from common import (calib_batches, emit, held_out_batches,
                        trained_spec_model, write_bench_json)

# the regime the headline speedup + acceptance bar are measured at:
# depth-pruned drafter (first layer of the dual-exit checkpoint) — the
# only profile whose draft STEP is structurally cheaper than a target
# step in every cost regime (ops, FLOPs and bytes), which is what
# converts acceptance into wall-clock speedup
DEFAULT_DRAFT_PROFILE = "w4l12"
# acceptance regimes: draft == target (ceiling), full-depth quant-only
# (high acceptance, expensive drafts), aggressive width sparsity, and
# the depth-pruned default
PROFILES = ("self", "w4", "w4s75", "w4l12")
# token-tree sweep (DESIGN.md §8): trees buy accepted length exactly
# where chains collapse — a drafter whose top-1 acceptance is poor but
# whose top-k sibling set covers the target. w2s75 is that drafter here
# (chain top-1 acceptance ~0.3; top-2-per-depth coverage ~0.7), and the
# comparison currency is mean ACCEPTED DRAFTS PER VERIFY DISPATCH at
# EQUAL verify token budget: tree fanout F (N nodes, T = N+1) vs chain
# K = N (same T). Greedy, so accepted lengths are deterministic.
TREE_DRAFT_PROFILE = "w2s75"
TREE_FANOUTS = ((2, 2), (4, 2), (2, 2, 2))
HEADLINE_FANOUT = (2, 2, 2)


def bench_prompts(cfg, n, lens=(12, 20, 8, 16)):
    """In-distribution prompts sliced from a held-out synthetic batch."""
    toks = np.asarray(held_out_batches(cfg, n=1)[0]["tokens"])
    return [toks[i % toks.shape[0], :lens[i % len(lens)]].astype(np.int32)
            for i in range(n)]


def make_runner(cfg, params, prompts, *, slots, max_new, max_seq, spec_k=0,
                spec_fanout=None, draft=None, draft_layers=None):
    def once(telemetry=None):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(num_slots=slots, max_seq=max_seq, spec_k=spec_k,
                         spec_fanout=spec_fanout,
                         spec_draft_layers=draft_layers),
            SamplingParams(), draft_params=draft, telemetry=telemetry)
        for p in prompts:
            eng.submit(p, max_new)
        out = eng.run()
        return out["metrics"], out["results"]
    return once


def best_of(runs):
    """Keep the fastest decode phase (the host is noisy; acceptance and
    outputs are deterministic across repeats, only wall-clock varies)."""
    return min(runs, key=lambda mr: mr[0]["itl_ms_mean"])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=3)
    args, _ = ap.parse_known_args(argv)
    enable_compile_cache()

    # serving-regime checkpoint: decode steps are dispatch/op-bound (like
    # DMA-bound real-size decode, the K+1-token verify costs about one
    # step) and the dual-exit training makes the depth-pruned drafter of
    # the SAME checkpoint accurate -> honest acceptance
    cfg, fp_params = trained_spec_model()
    # deployed W4S50 target through the FULL pipeline (calibrate -> BQPO
    # -> E2E-OQP -> pack): the paper's deployment, and what makes verify
    # against the target meaningful (one-shot W4S50 wrecks a tiny model)
    target, _ = gqsa_compress(
        fp_params, calib_batches(cfg, n=4), cfg,
        GQSAConfig(quant=QuantConfig(bits=4, group_size=16),
                   prune=PruneConfig(sparsity=0.5, group_size=16)),
        bqpo_cfg=BQPOConfig(steps=40, lr=5e-4),
        e2e_cfg=E2EConfig(steps=60, lr=5e-4))
    prompts = bench_prompts(cfg, args.requests)
    kw = dict(slots=args.slots, max_new=args.max_new, max_seq=args.max_seq)

    runners = {"baseline": make_runner(cfg, target, prompts, **kw)}
    for profile in PROFILES:
        if profile == "self":
            draft, dl = target, None
        else:
            draft = compress_draft(fp_params, cfg, profile=profile)
            dl = draft_layers(cfg, profile)
        runners[profile] = make_runner(cfg, target, prompts,
                                       spec_k=args.spec_k, draft=draft,
                                       draft_layers=dl, **kw)
    for once in runners.values():
        once()                           # compile everything up front
    # interleaved passes: host load drift hits every config equally
    runs = {name: [] for name in runners}
    for _ in range(args.repeats):
        for name, once in runners.items():
            runs[name].append(once())

    base_m, base_r = best_of(runs["baseline"])
    base_itl = base_m["itl_ms_mean"]
    base_out = {r["rid"]: list(r["tokens"]) for r in base_r}
    emit("spec_decode_baseline", base_itl * 1e3,
         f"{1e3 / base_itl:.1f} decode tok/s (non-speculative engine)",
         decode_tok_per_s=1e3 / base_itl, tok_per_s=base_m["tok_per_s"],
         ttft_ms_p50=base_m["ttft_ms_p50"],
         tpot_ms_p50=base_m["tpot_ms_p50"])

    speedups = {}
    for profile in PROFILES:
        m, r = best_of(runs[profile])
        itl = m["itl_ms_mean"]
        speedup = base_itl / itl
        speedups[profile] = speedup
        lossless = ({q["rid"]: list(q["tokens"]) for q in r} == base_out)
        assert lossless, f"spec output diverged from target ({profile})"
        emit(f"spec_decode_{profile}", itl * 1e3,
             f"{1e3 / itl:.1f} decode tok/s ({speedup:.2f}x, "
             f"acceptance {m['acceptance_rate']:.0%}, "
             f"TTFT p50 {m['ttft_ms_p50']:.0f}ms)",
             decode_tok_per_s=1e3 / itl, speedup_vs_nonspec=speedup,
             acceptance_rate=m["acceptance_rate"],
             tok_per_s=m["tok_per_s"], ttft_ms_p50=m["ttft_ms_p50"],
             spec_k=args.spec_k, lossless=lossless)

    default = speedups[DEFAULT_DRAFT_PROFILE]
    print(f"# speculative decode speedups vs PR-1 engine (K={args.spec_k}): "
          + ", ".join(f"{p}={s:.2f}x" for p, s in speedups.items()))
    print(f"# default profile {DEFAULT_DRAFT_PROFILE}: {default:.2f}x "
          f"(bar: >= 1.5x)")

    # where a speculative round spends its wall clock (telemetry phase
    # spans, DESIGN.md §10): one traced post-warmup pass of the default
    # profile. draft/verify spans are dispatch-side, the segment's sync
    # span holds the blocked device time — together the Table-6-style
    # stage decomposition. Not a per-call timing (timed=False).
    tel = Telemetry(trace=True)
    mphase, _ = runners[DEFAULT_DRAFT_PROFILE](tel)
    totals = tel.tracer.phase_totals()
    emit("spec_decode_phase_breakdown", 0.0,
         f"phase ms of a traced K={args.spec_k} "
         f"{DEFAULT_DRAFT_PROFILE} run: "
         + ", ".join(f"{k} {v['ms']:.0f}ms"
                     for k, v in sorted(totals.items(),
                                        key=lambda kv: -kv[1]["ms"])[:4]),
         timed=False, spec_k=args.spec_k,
         draft_profile=DEFAULT_DRAFT_PROFILE,
         acceptance_rate=mphase["acceptance_rate"],
         **{f"{k}_ms": v["ms"] for k, v in totals.items()})

    tree_results = tree_sweep(cfg, fp_params, target, prompts, args,
                              base_out)
    write_bench_json()
    return speedups, tree_results


def tree_sweep(cfg, fp_params, target, prompts, args, base_out):
    """Token-tree fanout sweep (DESIGN.md §8): for each fanout F (N
    nodes) run the tree AND the equal-verify-budget chain K = N with the
    same weak drafter, and emit accepted drafts per verify dispatch +
    decode tok/s. Greedy acceptance is deterministic, so the headline
    bar — tree accepted length STRICTLY above the chain at equal budget
    — needs no repeat averaging; wall-clock keeps the best of 2 passes
    (1 compile + 1 timed) like the profile sweep."""
    from repro.engine.spec import TreeTemplate

    draft = compress_draft(fp_params, cfg, profile=TREE_DRAFT_PROFILE)
    dl = draft_layers(cfg, TREE_DRAFT_PROFILE)
    kw = dict(slots=args.slots, max_new=args.max_new, max_seq=args.max_seq)
    results = {}
    for fanout in TREE_FANOUTS:
        n = TreeTemplate(fanout).n_nodes
        tree_once = make_runner(cfg, target, prompts, spec_fanout=fanout,
                                draft=draft, draft_layers=dl, **kw)
        chain_once = make_runner(cfg, target, prompts, spec_k=n,
                                 draft=draft, draft_layers=dl, **kw)
        mt, rt = best_of([tree_once() for _ in range(2)])
        mk, rk = best_of([chain_once() for _ in range(2)])
        assert {q["rid"]: list(q["tokens"]) for q in rt} == base_out, \
            f"tree spec output diverged from target ({fanout})"
        assert {q["rid"]: list(q["tokens"]) for q in rk} == base_out, \
            f"chain-{n} spec output diverged from target"
        name = "x".join(str(f) for f in fanout)
        results[fanout] = (mt["accepted_len_mean"], mk["accepted_len_mean"])
        emit(f"spec_tree_{name}", mt["itl_ms_mean"] * 1e3,
             f"{1e3 / mt['itl_ms_mean']:.1f} decode tok/s, "
             f"{mt['accepted_len_mean']:.2f} accepted/verify vs "
             f"{mk['accepted_len_mean']:.2f} chain-K{n} at equal "
             f"T={n + 1} verify budget ({TREE_DRAFT_PROFILE})",
             decode_tok_per_s=1e3 / mt["itl_ms_mean"],
             accepted_len_per_verify=mt["accepted_len_mean"],
             chain_equal_budget_accepted_len=mk["accepted_len_mean"],
             chain_equal_budget_tok_per_s=1e3 / mk["itl_ms_mean"],
             verify_tokens_per_round=n + 1,
             acceptance_rate=mt["acceptance_rate"],
             draft_profile=TREE_DRAFT_PROFILE, lossless=True)
    tree_alen, chain_alen = results[HEADLINE_FANOUT]
    print("# token-tree accepted/verify vs equal-budget chain "
          f"({TREE_DRAFT_PROFILE}): "
          + ", ".join(f"{f}={t:.2f} (chain {c:.2f})"
                      for f, (t, c) in results.items()))
    assert tree_alen > chain_alen, (
        f"tree {HEADLINE_FANOUT} accepted length {tree_alen:.3f} not above "
        f"equal-budget chain {chain_alen:.3f}")
    print(f"# headline {HEADLINE_FANOUT}: {tree_alen:.2f} accepted/verify "
          f"> chain {chain_alen:.2f} at equal verify token budget")
    return results


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
