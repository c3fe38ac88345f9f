# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
#   python benchmarks/run.py            full measurements
#   python benchmarks/run.py --smoke    tiny grids, 1 timing iteration — the
#                                       CI job that keeps these scripts alive
import argparse
import sys
import traceback
from pathlib import Path

# make `benchmarks.*` and `repro.*` importable for plain-script runs
# (no pip install -e, no PYTHONPATH)
_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 iteration per bench (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="run a single suite by name (e.g. table2_io)")
    args = ap.parse_args()

    from benchmarks import (bench_cfd_scaling, bench_hybrid, bench_io,
                            bench_kernels, bench_roofline, bench_rollout,
                            bench_scenarios)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    suites = [
        ("fig7_cfd_scaling", bench_cfd_scaling.run),
        ("table1_hybrid", bench_hybrid.run),
        ("table2_io", bench_io.run),
        ("fig10_components", bench_rollout.run),
        ("kernels", bench_kernels.run),
        ("roofline", bench_roofline.run),
        ("scenarios", bench_scenarios.run),
    ]
    if args.only and args.only not in {n for n, _ in suites}:
        names = ", ".join(n for n, _ in suites)
        raise SystemExit(f"unknown suite {args.only!r}; choose from: {names}")
    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites:
        if args.only and name != args.only:
            continue
        try:
            fn(smoke=args.smoke)
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == '__main__':
    main()
