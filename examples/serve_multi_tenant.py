"""Multi-tenant SNN serving: many resident networks, one compiled program.

The paper's headline is that swapping a network is a *parameter
download* -- never a re-synthesis. The serving restatement: S tenant
networks (heterogeneous topologies, thresholds, leaks; some frozen, one
learning online) time-share one compiled chunk program, vmapped over a
slot axis, and slots retire and refill one by one as their requests
finish. Admitting a request = writing a slot's registers. The demo
asserts each resident program (the chunk step and the slot refill)
compiles exactly once, however the tenants churn.

  PYTHONPATH=src python examples/serve_multi_tenant.py [--fast]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.core import connectivity
from repro.core.registers import RegisterBank, WeightLayout
from repro.launch.serve import (
    SNNServer, make_demo_requests, make_demo_tenants,
)

jax.config.update("jax_platform_name", "cpu")


def iris_like_bank(seed: int = 0) -> RegisterBank:
    """The paper's Iris shape (4 input -> 3 output) as a register image."""
    n = 7
    bank = RegisterBank(n, weight_layout=WeightLayout.PER_SYNAPSE)
    c = connectivity.layered([4, 3])
    bank.set_connection_list(c)
    rng = np.random.default_rng(seed)
    bank.set_weights((rng.integers(60, 200, (n, n)) * c).astype(np.uint8))
    bank.set_thresholds(np.full((n,), 100, np.uint8))
    bank.set_refractory(2)
    return bank


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args(argv)

    n_requests = 12 if args.fast else 48
    server = SNNServer(n_max=24, slots=args.slots, max_ticks=12)

    # 8 heterogeneous demo tenants (last one plastic) + the paper's Iris net.
    names = make_demo_tenants(server, 8, seed=0)
    server.add_tenant("iris", iris_like_bank(), n_in=4, n_out=3)
    names.append("iris")
    plastic = [t.name for t in server.tenants.values() if t.plastic]
    print(f"fabric n_max={server.n_max}, slots={server.slots}: "
          f"{len(server.tenants)} resident tenants ({', '.join(names)}); "
          f"plastic: {plastic}")

    reqs = make_demo_requests(server, names, n_requests, seed=1)

    w_plastic0 = np.asarray(server.tenants[plastic[0]].params.w).copy()
    stats = server.serve_continuous(reqs)
    for k, v in stats.items():
        if k not in ("preds", "results"):
            print(f"  {k}: {v}")

    # One backend in use: its chunk program and its slot refill.
    assert stats["compiles"] == 2, "tenant swaps must not recompile"
    assert stats["recompiles_after_warmup"] == 0
    w_plastic1 = np.asarray(server.tenants[plastic[0]].params.w)
    drift = float(np.abs(w_plastic1 - w_plastic0).sum())
    print(f"  plastic tenant weight drift across requests: {drift:.1f} "
          "(frozen tenants: bit-identical by construction)")
    assert drift > 0, "the plastic tenant never learned"

    # Per-tenant activity from the telemetry riding each slot's carry:
    # spike rates, refractory occupancy, and (for the plastic tenant) the
    # accumulated |dw| -- all measured on-device, no extra rollouts.
    print("per-tenant activity:")
    for name, row in server.tenant_report().items():
        print(f"  {name:>10}: requests={row['requests']:>2} "
              f"spike_rate={row['spike_rate']:.3f} "
              f"refractory={row['refractory_occupancy']:.3f} "
              f"dw_l1={row['dw_l1']:.1f}"
              f"{'  [plastic]' if row['plastic'] else ''}")
    assert server.tenant_report()[plastic[0]]["dw_l1"] > 0

    # A second queue through the same resident programs: a short request
    # retires as soon as its own budget is spent, never waiting on the
    # longest one beside it, and nothing recompiles.
    more = server.serve_continuous(
        make_demo_requests(server, names, n_requests, seed=2))
    assert more["compiles"] == stats["compiles"], \
        "a second queue must reuse the compiled chunk program"
    print(f"second queue: served {more['requests_served']} more "
          f"requests, mean TTFT {more['mean_ttft_s'] * 1e3:.1f} ms, "
          f"p99 {more['p99_ttft_s'] * 1e3:.1f} ms, 0 recompiles")

    print("PASS - one compiled chunk program served "
          f"{stats['n_tenants']} networks / {stats['n_requests']} requests")
    return stats


if __name__ == "__main__":
    main()
