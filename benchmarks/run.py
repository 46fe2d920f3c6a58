"""Benchmark runner: one bench per paper table/figure + the roofline readout.

Writes the full summary to ``BENCH_all.json`` (plus whatever per-bench
``BENCH_*.json`` files the individual benches emit) and exits nonzero if
any bench raises -- a crashed bench must fail CI, not vanish into a
printout (the old behaviour only printed the summary and swallowed
nothing explicitly, but gave the gate nothing to read either).

Every bench record carries a uniform ``_wall_s`` (runner-measured, not
bench-self-reported) and ``_platform`` (``jax.default_backend()``), so a
BENCH file read months later says what device produced it. ``--profile
DIR`` captures a ``jax.profiler`` trace of the whole run (the CI bench
job uploads it next to the BENCH_*.json artifacts).

Usage: PYTHONPATH=src python -m benchmarks.run [--fast] [--out BENCH_all.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the training-heavy benches")
    ap.add_argument("--out", default="BENCH_all.json")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a jax.profiler trace of the benches "
                         "into DIR")
    ap.add_argument("--skip-analysis", action="store_true",
                    help="skip the static-analysis pre-flight")
    args = ap.parse_args()

    # Before anything initializes a jax backend: the snn_scale sharded
    # section (and the analysis sweep's mesh programs) want a simulated
    # multi-device view of the CPU host.  The flag only splits the host
    # platform; on an accelerator the benches see the real devices.
    from repro.util.env import enable_compilation_cache, ensure_host_device_count
    enable_compilation_cache()
    ensure_host_device_count(8)

    import jax

    if not args.skip_analysis:
        # Pre-flight: trace-level invariants are seconds to check and a
        # violated one (host callback in the scan, W*C recomputed per
        # tick, retrace-per-call static) invalidates every number the
        # benches below would spend minutes producing.
        from repro.analysis import check as analysis_check

        print("=== static-analysis pre-flight ===", flush=True)
        report = analysis_check.run()
        if not report.ok():
            print(report.table(), file=sys.stderr)
            print(report.summary(), file=sys.stderr)
            print("analysis pre-flight failed: benchmark numbers would be "
                  "meaningless; fix the findings (or --skip-analysis to "
                  "measure anyway)", file=sys.stderr)
            sys.exit(report.exit_code())
        print(report.summary(), flush=True)

    from benchmarks import (
        bench_iris, bench_latency, bench_mnist, bench_snn_scale, bench_stdp,
        bench_uart,
    )
    from repro.obs import profile

    benches = [
        ("uart", bench_uart.run),
        ("latency", bench_latency.run),
        ("snn_scale", lambda: bench_snn_scale.run(fast=args.fast)),
        ("stdp", bench_stdp.run),
    ]
    if not args.fast:
        benches += [("iris", bench_iris.run), ("mnist", bench_mnist.run)]

    platform = jax.default_backend()
    results = {"_platform": platform}
    failures = []
    with profile(args.profile):
        for name, fn in benches:
            t0 = time.perf_counter()
            print(f"=== bench:{name} ===", flush=True)
            try:
                res = fn()
            except Exception as e:  # noqa: BLE001 -- recorded, fatal at exit
                traceback.print_exc()
                failures.append(name)
                results[name] = {"_error": f"{type(e).__name__}: {e}"}
                continue
            # perf_counter + 6 decimals: cost-model benches (e.g. uart)
            # finish in well under 10 ms, which the old time.time()/
            # round(_, 2) pair recorded as a flat (and wrong) 0.0.
            res["_wall_s"] = round(time.perf_counter() - t0, 6)
            res["_platform"] = platform
            results[name] = res
            for k, v in res.items():
                print(f"  {k}: {v}")
            # Per-bench artifact (what check_regression.py and CI read/
            # upload); same file the bench's own __main__ writes.
            with open(f"BENCH_{name}.json", "w") as f:
                json.dump(res, f, indent=2, default=str)

    # roofline summary if dry-run artifacts exist (best-effort readout of
    # OPTIONAL artifacts -- unlike the benches above, absence is not failure)
    try:
        from benchmarks import roofline
        recs = roofline.load_records()
        if recs:
            print("=== bench:roofline (from dry-run artifacts) ===")
            print(roofline.table(recs))
        else:
            print("=== roofline: no dry-run artifacts (run repro.launch.dryrun) ===")
    except Exception as e:  # noqa: BLE001
        print(f"roofline summary unavailable: {e}")

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2, default=str)
    print(f"wrote {args.out}")
    print("=== benchmark summary (json) ===")
    print(json.dumps(results, indent=2, default=str))
    if failures:
        print(f"FAILED benches: {', '.join(failures)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
