#!/usr/bin/env python3
"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload cyl_re100_jets.paper --seed 7 \
        --seconds 30 --trace 0

``--trace 0`` measures the cell's end-to-end metrics over a window of
``--seconds``; ``--trace 1`` profiles the first episodes of the window and
reports the per-layer metrics read from the device trace.  Either way the
first episodes are checked against the plain reference after the window.
The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` when traced, and
``checks`` last: each compared number beside its limit).  Exits non-zero,
printing no result, when JAX finds no TPU or fewer chips than the cell
needs, or when the checkout lacks the program.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the trace's .xplane.pb into DIR")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    # the program's own placement: $JAX_COMPILATION_CACHE_DIR, else the
    # fixed <checkout>/.jax_cache; every executable is kept
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.harness import SetupError, run_cell
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process=T_PROCESS,
                          keep_trace=args.keep_trace)
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
