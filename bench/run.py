"""Run one cell of the benchmark once and print its result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (the tenants' registers made on the device from the seed, their
registration, the request pool, and a warm-up that compiles or loads
every program the traffic uses) is timed from process start to the first
timed request.  Then the window runs for ``--seconds``, the results are
compared with the plain reference, and the last stdout line is one JSON
object.  ``--trace 1`` profiles a short steady part of the window and
prints the cell's per-layer metrics instead of its end-to-end ones.

Off a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import harness  # noqa: E402


class Ctx:
    """What a system module gets: the cell, the run's arguments, and the tools."""

    def __init__(self, cell, args, clock, spans, devices):
        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.t_start = T_START
        self.clock, self.spans, self.devices = clock, spans, devices
        self.smoke = args.smoke
        self.trace_dir = os.path.join(harness.CACHE_DIR, "trace",
                                      cell.name)
        if self.smoke:
            self.config = dict(self.config, **self.config["smoke"])
            self.traffic = dict(self.traffic, **self.traffic.get("smoke", {}))

    def peak_bytes(self):
        return harness.peak_bytes(self.devices)


def per_layer(cell, layer: dict, tr, peak) -> dict:
    run = dict(layer, trace=tr, peak=peak, chips=cell.chips)
    out = {}
    for m in cell.per_layer():
        v = harness.Cell.reader(m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="the configuration's smoke sizes (CPU rehearsal)")
    ap.add_argument("--dump", metavar="DIR", default=None,
                    help="also write the reduced trace and run record here")
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload)
    harness.prepare_env()
    import jax

    from bench import peaks, trace

    try:
        device = harness.require_chips(
            cell.chips, "tpu" if require_tpu else jax.devices()[0].platform)
    except harness.NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"no result: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 4
    devices = jax.devices()[:cell.chips]
    # a CPU rehearsal borrows the v5e's peaks; it is never a device number
    peak = peaks.peaks(devices[0].device_kind if require_tpu
                       else "TPU v5 lite")
    clock = harness.CompileClock()
    spans = harness.Spans()
    ctx = Ctx(cell, args, clock, spans, devices)
    if args.trace:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    res = cell.system().run(ctx)

    dev = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    breakdown = None
    if args.trace:
        tr = trace.load(ctx.trace_dir) if os.path.isdir(ctx.trace_dir) \
            else None
        if tr is not None:
            busy = trace.busy_s(tr)
            dev["busy_s"] = sum(busy.values()) / max(1, len(busy))
            dev["window_s"] = trace.window_s(tr)
            breakdown = trace.breakdown(tr)
        metrics = per_layer(cell, res["layer"], tr, peak)
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, f"{cell.name}.{args.seed}"
                                   ".trace.json"), "w") as fh:
                json.dump(tr, fh)
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end()
                   if res["end_to_end"].get(m["name"]) is not None}
    checks = res["checks"]
    correct = res["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    info = dict(res.get("info", {}), end_to_end=res["end_to_end"])
    if args.dump:
        with open(os.path.join(args.dump, f"{cell.name}.{args.seed}"
                               f".t{args.trace}.info.json"), "w") as fh:
            json.dump(dict(info, layer=res["layer"], checks=checks), fh,
                      default=str)
    print(json.dumps(info, default=str), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(harness.result_line(
        correct=correct, attempted=res["attempted"], failed=res["failed"],
        metrics=metrics, device=dev, checks=checks, breakdown=breakdown),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
