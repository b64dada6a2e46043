"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus '#' comment lines)."""
import argparse
import importlib
import sys
import traceback

MODULES = [
    "table1_quality",      # Table 1/14/15: PPL vs W2 / 2:4 / sparsity grid
    "table6_stages",       # Table 6: BQPO vs +E2E-OQP
    "fig8_ablation",       # Figure 8: sparsity & group-size ablations
    "fig6_kernel",         # Figure 6: GEMV kernel vs sparsity/group
    "fig5_balance",        # Figure 5: task-centric load balance
    "table4_latency",      # Table 4/16: decode latency fp/w4/gqsa
    "table10_tradeoff",    # Table 10/11: quant-only vs sparse-only vs GQSA
    "table13_throughput",  # Table 13: serving tokens/s
    "tableC_wa_quant",     # Appendix C: W4A8S50
    "fig_saliency",        # beyond-paper: saliency-criterion ablation
    "roofline_report",     # EXPERIMENTS.md §Roofline source
]

# serving-regime group (--serve): engine-path benchmarks that write the
# BENCH_serve.json trajectory gated by benchmarks/check_bench.py. Their
# main() takes an argv list (defaults apply when given []).
SERVE_MODULES = [
    "serve_engine",        # engine vs seed loop, load sweep, SLO goodput
    "spec_decode",         # self-speculative serving ladder
    "paged_attn",          # paged decode-attention kernel
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of modules")
    ap.add_argument("--serve", action="store_true",
                    help="run the serving-regime group (engine, spec "
                         "decode, paged attention) instead of the "
                         "paper-table group")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mods = args.only.split(",") if args.only \
        else (SERVE_MODULES if args.serve else MODULES)
    print("name,us_per_call,derived")
    failures = 0
    for m in mods:
        try:
            fn = importlib.import_module(f"benchmarks.{m}").main
            fn([]) if m in SERVE_MODULES else fn()
        except Exception:
            failures += 1
            print(f"# FAILED {m}")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
