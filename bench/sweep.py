"""Find the highest open-loop rate a cell's server sustains: one process,
one server, one window per offered rate.

    python3 -m bench.sweep --workload <name> --seconds <s> --seed <n> \\
        --rates 0.5 1 2 4

Prints one JSON line per rate: arrivals, completions inside the window,
how long the backlog took to drain after it, and the latency quantiles.
"""
from __future__ import annotations

import argparse
import sys

from bench import harness, run as bench_run


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    harness.prepare_env()
    import jax

    try:
        harness.require_chips(
            cell.chips, "tpu" if require_tpu else jax.devices()[0].platform)
    except harness.NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    a = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=0,
                           smoke=args.smoke)
    ctx = bench_run.Ctx(cell, a, harness.CompileClock(), harness.Spans(),
                        jax.devices()[:cell.chips])
    cell.system().sweep(ctx, args.rates)
    return 0


if __name__ == "__main__":
    sys.exit(main())
