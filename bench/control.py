"""Readings for the limits of ``correct``: on each seed, one short run of
the cell (the program's numbers: the lower readings) and the control in
the program's place on the same requests (the upper readings).  All
seeds in one process, so set-up is paid once per seed and compiled once.

    python3 -m bench.control --workload <name> --seconds <s> --seeds 1 2 3 \\
        [--control-seeds 1 2 3]

Prints one JSON line per seed and a summary line: the largest program
reading and the smallest control reading of each number.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import harness, run as bench_run


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None)
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
    control_seeds = set(args.seeds if args.control_seeds is None
                        else args.control_seeds)
    clock, spans = harness.CompileClock(), harness.Spans()
    system = cell.system()
    lows, highs = {}, {}
    for seed in args.seeds:
        a = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                               smoke=args.smoke)
        ctx = bench_run.Ctx(cell, a, clock, spans,
                            jax.devices()[:cell.chips])
        res = system.run(ctx)
        line = {"seed": seed, "failed": res["failed"],
                "program": {k: c["value"] for k, c in res["checks"].items()}}
        for k, v in line["program"].items():
            lows[k] = max(lows.get(k, v), v)
        if seed in control_seeds:
            ctl = system.control_checks(ctx, res)
            line["control"] = {k: c["value"] for k, c in ctl.items()}
            for k, v in line["control"].items():
                highs[k] = min(highs.get(k, v), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"lower_readings": lows, "upper_readings": highs}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
