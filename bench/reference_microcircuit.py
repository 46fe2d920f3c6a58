"""The plain reference of the Potjans-Diesmann microcircuit, and the
bench's own generator of its synapse list.  It imports nothing of the
program.

Network (Potjans & Diesmann 2014; NEST's ``Potjans_2014`` example,
as ``bench/configs/pd14_microcircuit.json`` states it):

* ``K[i, j] = round(log(1 - p_ij) / log(1 - 1/(N_i N_j)))`` synapses
  from population ``j`` to population ``i`` (``fixed_total_number``):
  each source uniform over ``j``, each target uniform over ``i``,
  multapses allowed, autapses not.
* weight normal(mean_ij, 0.1 |mean_ij|) pA, as an integer level of
  ``weight_quantum_pa``; delay normal(mean, 0.5 mean) ms by the
  source's sign, redrawn outside ``[dt, ring_depth * dt]``, in whole
  ticks.

:func:`synapse_blocks` yields the list in fixed-length blocks, grouped
by source: entry ``e`` of the whole list belongs to the source whose
range ``[start[s], start[s] + count[s])`` holds it.  The same seed gives
the same list, block for block.

One tick of the reference, for every neuron at once (voltages relative
to ``E_L``; ``NEST iaf_psc_exp`` with exact propagators)::

    x      = ring[t % D] + poisson(fold_in(key, t), lam) * w_ext
    ring[t % D] = 0
    v~     = P22 v + P21 i + P20 I_e
    i'     = P11 i + x
    spike  = v~ >= theta  and  r == 0
    v'     = v_reset if spike or r > 0 else v~
    r'     = t_ref if spike else max(r - 1, 0)
    for each spiking source s, for each of its synapses (tgt, w, d):
        ring[(t + d) % D, tgt] += w

The ring holds integer weight levels, so delivery is exact; the neuron
runs in ``dtype`` (float32, or bfloat16 for the control that must fail).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.fabric import host_rng, seed_key

# Key streams drawn from one seed.
KEY_COUNTS, KEY_SYNAPSES, KEY_V0, KEY_POISSON = 1, 2, 3, 4


# -- the network the configuration states ------------------------------------------

def network(cfg: Dict) -> Dict:
    """Sizes, projections and the neuron's propagators, from the config
    (``scale`` scales every population and projection)."""
    scale = float(cfg.get("scale", 1.0))
    full = np.asarray(cfg["sizes"], np.float64)
    sizes = np.asarray([int(round(v * scale)) for v in cfg["sizes"]])
    prod = np.outer(full, full)
    p = np.asarray(cfg["conn_probs"], np.float64)
    k = np.round(np.log(1.0 - p) / np.log((prod - 1.0) / prod) * scale)
    nrn = cfg["neuron"]
    c_m, tau_m, tau_s, h = (nrn["c_m"], nrn["tau_m"], nrn["tau_syn"],
                            cfg["dt_ms"])
    sub = 1.0 / (tau_s - tau_m)
    frac = (tau_m / tau_s) ** sub
    psc = 1.0 / (tau_m * tau_s / c_m * sub * (frac ** tau_m - frac ** tau_s))
    w_e = psc * cfg["psp_mean_mv"]
    w_mean = np.tile([w_e, cfg["g"] * w_e] * 4, (8, 1))
    w_mean[0, 2] *= cfg["l23e_from_l4e"]
    q = cfg["weight_quantum_pa"]
    lam_pop = cfg["bg_rate_hz"] * np.asarray(cfg["k_ext"], np.float64) \
        * h / 1000.0
    starts = np.concatenate([[0], np.cumsum(sizes)])
    return {
        "sizes": sizes, "starts": starts, "n": int(starts[-1]),
        "k": k.astype(np.int64), "w_mean": w_mean, "q": q,
        "w_ext_level": int(np.round(w_e / q)),
        "lam": np.repeat(lam_pop, sizes).astype(np.float32),
        "pop": np.repeat(np.arange(8), sizes).astype(np.int32),
        "delay_mean": np.asarray(cfg["delay_mean_ms"], np.float64),
        "delay_rel_std": cfg["delay_rel_std"], "w_rel_std":
            cfg["weight_rel_std"], "dt": h, "depth": int(cfg["ring_depth"]),
        "p11": math.exp(-h / tau_s), "p22": math.exp(-h / tau_m),
        "p21": tau_m * tau_s / (c_m * (tau_m - tau_s))
        * (math.exp(-h / tau_m) - math.exp(-h / tau_s)),
        "theta": nrn["v_th"] - nrn["e_l"],
        "v_reset": nrn["v_reset"] - nrn["e_l"],
        "t_ref": int(round(nrn["t_ref"] / h)),
    }


def out_degrees(net: Dict, seed: int) -> np.ndarray:
    """``(8, n)`` synapses from each source into each target population:
    each projection's ``K`` sources drawn uniformly from its source
    population, i.e. a multinomial count per source (on the host)."""
    out = np.zeros((8, net["n"]), np.int64)
    for i in range(8):
        for j in range(8):
            a, b = net["starts"][j], net["starts"][j + 1]
            rng = host_rng(seed, KEY_COUNTS, 8 * i + j)
            out[i, a:b] = rng.multinomial(int(net["k"][i, j]),
                                          np.full(b - a, 1.0 / (b - a)))
    return out


@functools.partial(jax.jit, static_argnames=("size",))
def _block(key, e0, seg_start, pop, starts, w_mean, d_mean, spec, *, size):
    """Entries ``e0 .. e0 + size`` of the list: (src, tgt, level, delay),
    with ``src == n`` past the end.  The list is a run of segments, one
    per (source, target population) in that order; an entry's segment is
    its block's first segment plus the segment starts it has passed."""
    total, depth, w_sd, d_sd, dt, q = spec
    n = pop.shape[0]
    e = e0 + jnp.arange(size, dtype=jnp.int32)
    live = e < total
    rel = seg_start - e0
    marks = jnp.zeros((size,), jnp.int32).at[
        jnp.where((rel > 0) & (rel < size), rel, size)].add(1, mode="drop")
    g0 = jnp.searchsorted(seg_start, e0, side="right").astype(jnp.int32) - 1
    g = jnp.minimum(g0 + jnp.cumsum(marks), 8 * n - 1)
    s, i = g // 8, g % 8
    j = pop[s]
    k_t, k_w, k_d = jax.random.split(jax.random.fold_in(key, e0), 3)
    same = (i == j).astype(jnp.int32)
    width = starts[i + 1] - starts[i] - same
    u = (jax.random.bits(k_t, (size,), jnp.uint32)
         % jnp.maximum(width, 1).astype(jnp.uint32)).astype(jnp.int32)
    tgt = starts[i] + u
    tgt = tgt + (same * (tgt >= s)).astype(jnp.int32)
    mean = w_mean[i, j]
    w = mean + jnp.abs(mean) * w_sd * jax.random.normal(k_w, (size,))
    level = jnp.round(w / q).astype(jnp.int32)
    dm = d_mean[(j % 2)]
    lo, hi = dt, depth * dt

    def redraw(c):
        it, d = c
        bad = (d < lo) | (d > hi)
        fresh = dm + dm * d_sd * jax.random.normal(
            jax.random.fold_in(k_d, it), (size,))
        return it + 1, jnp.where(bad, fresh, d)

    d0 = jnp.full((size,), -1.0, jnp.float32)
    _, d = jax.lax.while_loop(
        lambda c: jnp.any((c[1] < lo) | (c[1] > hi)), redraw, (0, d0))
    steps = jnp.clip(jnp.round(d / dt), 1, depth).astype(jnp.int32)
    return (jnp.where(live, s, n), tgt, jnp.where(live, level, 0),
            jnp.where(live, steps, 1))


def list_layout(deg: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(start, count)`` of each source's entries in the whole list."""
    count = deg.sum(axis=0)
    start = np.concatenate([[0], np.cumsum(count)[:-1]])
    return start.astype(np.int64), count.astype(np.int64)


def synapse_blocks(net: Dict, seed: int, deg: np.ndarray, size: int,
                   ) -> Iterator[Tuple[jax.Array, ...]]:
    """The synapse list, ``size`` entries at a time, as device arrays
    ``(src, tgt, level, delay)`` (int32; ``src == n`` pads the last)."""
    seg = deg.T.reshape(-1)                      # (source, target pop)
    total = int(seg.sum())
    if total >= 2 ** 31 - size:
        raise ValueError(f"{total} synapses overflow int32 entry indices")
    seg_start = np.concatenate([[0], np.cumsum(seg)[:-1]]).astype(np.int32)
    key = jax.random.fold_in(seed_key(seed), KEY_SYNAPSES)
    args = (jnp.asarray(seg_start),
            jnp.asarray(net["pop"]), jnp.asarray(net["starts"], jnp.int32),
            jnp.asarray(net["w_mean"], jnp.float32),
            jnp.asarray(net["delay_mean"], jnp.float32),
            (total, net["depth"], float(net["w_rel_std"]),
             float(net["delay_rel_std"]), float(net["dt"]), float(net["q"])))
    for e0 in range(0, total, size):
        yield _block(key, jnp.int32(e0), *args, size=size)


def poisson_key(seed: int) -> jax.Array:
    """The background's raw ``uint32[2]`` key."""
    return jax.random.key_data(jax.random.fold_in(seed_key(seed),
                                                  KEY_POISSON))


def v0_key(seed: int) -> jax.Array:
    return jax.random.key_data(jax.random.fold_in(seed_key(seed), KEY_V0))


# -- the reference -----------------------------------------------------------------

class Synapses:
    """The whole list, resident for the reference: targets, integer
    levels and delays, with each source's ``start`` and ``count``."""

    def __init__(self, net: Dict, seed: int, size: int, *,
                 delay_one: bool = False):
        deg = out_degrees(net, seed)
        start, count = list_layout(deg)
        tgt, lev, dly = [], [], []
        for _, t, lv, d in synapse_blocks(net, seed, deg, size):
            tgt.append(t)
            lev.append(lv.astype(jnp.int16))
            dly.append(jnp.ones_like(d, jnp.uint8) if delay_one
                       else d.astype(jnp.uint8))
        self.window = int(max(1, count.max()))
        pad = lambda xs, dt: jnp.concatenate(
            xs + [jnp.zeros((self.window,), dt)])
        self.tgt = pad(tgt, jnp.int32)
        self.level = pad(lev, jnp.int16)
        self.delay = pad(dly, jnp.uint8)
        self.start = jnp.asarray(start, jnp.int32)
        self.count = jnp.asarray(count, jnp.int32)


@functools.partial(jax.jit, static_argnames=("ticks", "window", "dtype"))
def run_request(state: Dict, syn: Tuple, consts: Dict, *, ticks: int,
                window: int, dtype=jnp.float32):
    """``ticks`` ticks from ``state`` (``v``, ``i``, ``r``, the integer
    ring flattened row by row, and ``tick``); returns the final state and
    the spikes of each population over the request."""
    tgt, level, delay, start, count = syn
    c = consts
    n = state["v"].shape[0]
    depth = state["ring"].shape[0] // n
    lane = jnp.arange(window, dtype=jnp.int32)
    p11, p22, p21 = (jnp.asarray(c[k], dtype) for k in ("p11", "p22", "p21"))
    theta, v_reset = (jnp.asarray(c[k], dtype) for k in ("theta", "v_reset"))
    q = jnp.asarray(c["q"], dtype)

    def push(j, carry):
        ring, idx, t = carry
        s = idx[j]
        o = start[s]
        real = lane < count[s]
        tg = jax.lax.dynamic_slice(tgt, (o,), (window,))
        lv = jax.lax.dynamic_slice(level, (o,), (window,)).astype(jnp.int32)
        d = jax.lax.dynamic_slice(delay, (o,), (window,)).astype(jnp.int32)
        cell = jnp.where(real, ((t + d) % depth) * n + tg, depth * n)
        return ring.at[cell].add(lv, mode="drop"), idx, t

    def tick(st, _):
        t = st["tick"]
        row = (t % depth) * n
        arrived = jax.lax.dynamic_slice(st["ring"], (row,), (n,))
        ring = jax.lax.dynamic_update_slice(
            st["ring"], jnp.zeros((n,), jnp.int32), (row,))
        events = jax.random.poisson(jax.random.fold_in(c["key"], t),
                                    c["lam"], dtype=jnp.int32)
        x = (arrived + events * c["w_ext_level"]).astype(dtype) * q
        v_t = p22 * st["v"] + p21 * st["i"] + jnp.asarray(c["bias"], dtype)
        i_new = p11 * st["i"] + x
        spike = (v_t >= theta) & (st["r"] == 0)
        v_new = jnp.where(spike | (st["r"] > 0), v_reset, v_t)
        r_new = jnp.where(spike, c["t_ref"], jnp.maximum(st["r"] - 1, 0))
        idx = jnp.nonzero(spike, size=n, fill_value=0)[0].astype(jnp.int32)
        ring, _, _ = jax.lax.fori_loop(0, jnp.sum(spike), push, (ring, idx, t))
        pops = jnp.zeros((8,), jnp.int32).at[c["pop"]].add(
            spike.astype(jnp.int32))
        return {"v": v_new, "i": i_new, "r": r_new, "ring": ring,
                "tick": t + 1}, pops

    state = {"v": state["v"].astype(dtype), "i": state["i"].astype(dtype),
             "r": state["r"], "ring": state["ring"], "tick": state["tick"]}
    final, pops = jax.lax.scan(tick, state, None, length=ticks)
    return final, pops.sum(axis=0)


def consts(net: Dict, seed: int) -> Dict:
    return {"p11": net["p11"], "p22": net["p22"], "p21": net["p21"],
            "theta": net["theta"], "v_reset": net["v_reset"],
            "t_ref": net["t_ref"], "q": net["q"], "bias": 0.0,
            "w_ext_level": net["w_ext_level"],
            "lam": jnp.asarray(net["lam"]), "pop": jnp.asarray(net["pop"]),
            "key": poisson_key(seed)}


def replay(net: Dict, syn: Synapses, seed: int, state: Dict, ticks: int, *,
           dtype=jnp.float32):
    """One request of ``ticks`` ticks from ``state`` (host or device
    arrays: ``v``, ``i``, ``r`` and the ring in pA, ``tick``); returns
    ``(final state with the ring in pA, population spikes)``."""
    with jax.default_matmul_precision("highest"):
        ring_q = jnp.round(jnp.asarray(state["ring"]) / net["q"]).astype(
            jnp.int32).reshape(-1)
        st = dict(v=jnp.asarray(state["v"]), i=jnp.asarray(state["i"]),
                  r=jnp.asarray(state["r"]), ring=ring_q,
                  tick=jnp.asarray(state["tick"], jnp.int32))
        final, pops = run_request(
            st, (syn.tgt, syn.level, syn.delay, syn.start, syn.count),
            consts(net, seed), ticks=ticks, window=syn.window, dtype=dtype)
    final = dict(final, ring=final["ring"].astype(jnp.float32).reshape(
        -1, net["n"]) * net["q"])
    return final, pops
