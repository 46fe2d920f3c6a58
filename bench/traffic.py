"""The one traffic generator: turns a traffic file (``bench/traffic/*.json``)
and a seed into requests and their arrival schedule.

A file holds parameters only:

* ``loop``: ``closed`` (``clients`` callers, each with one request
  outstanding and no think time), ``open`` (Poisson arrivals at
  ``rate_per_s``) or ``stream`` (one session, ``clients`` = 1, each
  request ``ticks`` ticks of input, the next sent when the last is read
  back).
* ``tenants``: the tenants drawn from, in Zipf rank order, with exponent
  ``zipf_s``; ``budgets``: ``[share, lo, hi]`` bands of tick budgets.
* ``input``: spike ``rate`` per input channel per tick and the impulse
  magnitude ``levels`` ``[lo, hi]`` (u8 steps of 2^-7).
* ``pool``: how many distinct requests one run holds, in blocks of
  ``block``.

Every block of the pool holds the same multiset of (tenant, budget), in
its own seeded order, and every seed gets the same set of inter-arrival
gaps, in another order; only the order and the input spikes change with
the seed.  So any stretch of a run serves nearly the same work whatever
its seed, and the order of that work is averaged over many blocks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench.fabric import LEVEL, host_rng


@dataclasses.dataclass
class Draw:
    """One request as drawn: who it is for, how long, and its input."""

    tenant: str
    budget: int
    ext: np.ndarray          # (ticks, n_in) float32


def _apportion(total: int, shares: Sequence[float]) -> List[int]:
    """Largest-remainder split of ``total`` by ``shares``."""
    s = np.asarray(shares, np.float64)
    raw = total * s / s.sum()
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[:total - out.sum()]:
        out[i] += 1
    return out.tolist()


def zipf_shares(n: int, s: float) -> List[float]:
    return [1.0 / (k + 1) ** s for k in range(n)]


def mix(traffic: Dict) -> List[tuple]:
    """The seed-independent multiset of (tenant, budget) of one block."""
    tenants = traffic["tenants"]
    per_tenant = _apportion(traffic["block"],
                            zipf_shares(len(tenants), traffic["zipf_s"]))
    bands = traffic["budgets"]
    out = []
    for name, count in zip(tenants, per_tenant):
        for (share, lo, hi), k in zip(
                bands, _apportion(count, [b[0] for b in bands])):
            span = hi - lo + 1
            out += [(name, lo + j % span) for j in range(k)]
    return out


def spikes(rng: np.random.Generator, ticks: int, n_in: int,
           inp: Dict) -> np.ndarray:
    """Input impulses: each channel fires with probability ``rate`` per
    tick, with a magnitude of ``levels`` u8 steps."""
    on = rng.random((ticks, n_in)) < inp["rate"]
    lo, hi = inp["levels"]
    ext = np.zeros((ticks, n_in), np.float32)
    ext[on] = rng.integers(lo, hi + 1, int(on.sum())) * LEVEL
    return ext


def pool(traffic: Dict, seed: int, n_in: Dict[str, int]) -> List[Draw]:
    """The run's requests in the seed's order (``n_in`` per tenant)."""
    items = mix(traffic)
    out = []
    for b in range(traffic["pool"] // len(items)):
        for j in host_rng(seed, 0, b).permutation(len(items)):
            name, budget = items[j]
            ext = spikes(host_rng(seed, 1, len(out)), budget, n_in[name],
                         traffic["input"])
            out.append(Draw(name, budget, ext))
    return out


def arrivals(traffic: Dict, seed: int, seconds: float) -> np.ndarray:
    """Open loop: due times (s after the window opens) of exactly
    ``round(rate * seconds)`` requests, their gaps the quantiles of an
    exponential distribution in the seed's order."""
    k = max(1, round(traffic["rate_per_s"] * seconds))
    gaps = -np.log1p(-(np.arange(k) + 0.5) / k)
    gaps = gaps[host_rng(seed, 2).permutation(k)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds * (k - 0.5) / k) / max(due[-1], 1e-12) \
        if k > 1 else due


def stream_input(traffic: Dict, seed: int, index: int, n_in: int,
                 ) -> np.ndarray:
    """The ``index``-th request of a stream session."""
    return spikes(host_rng(seed, 3, index), traffic["ticks"], n_in,
                  traffic["input"])


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (no interpolation): the value that ``q``%
    of the sample lies at or below."""
    if not len(values):
        return None
    v = sorted(values)
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])
