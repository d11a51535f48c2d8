# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
#   Table 3 / FSMOE column  -> bench_fsmoe      (naive vs optimized MoE, F+B)
#   Table 3 / EPSO column   -> bench_epso       (SO vs EPSO state bytes)
#   Figure 4 (scaling)      -> bench_scaling    (roofline-model efficiency)
#   Figure 1 (loss curves)  -> bench_loss       (dense vs MoE iso-compute)
#   kernels (Stage 2/4/5)   -> bench_kernels    (VMEM budgets + validation)
#
# Roofline tables (EXPERIMENTS §Dry-run/§Roofline) are produced by the
# dry-run sweep: PYTHONPATH=src python -m repro.launch.sweep
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of bench names (fsmoe epso scaling loss kernels)")
    args = ap.parse_args()

    from . import (bench_epso, bench_fsmoe, bench_kernels, bench_loss,
                   bench_scaling, bench_serve)
    benches = {"kernels": bench_kernels, "epso": bench_epso,
               "scaling": bench_scaling, "fsmoe": bench_fsmoe,
               "loss": bench_loss, "serve": bench_serve}
    if args.only:
        benches = {k: v for k, v in benches.items() if k in args.only}

    print("name,us_per_call,derived")
    failures = []

    def report(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}", flush=True)

    for name, mod in benches.items():
        try:
            mod.run(report)
        except Exception as e:
            traceback.print_exc()
            failures.append((name, repr(e)))
    if failures:
        print(f"FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
