"""A plain per-request reference for multi-tenant SNN serving.

The contract of ``SNNServer.serve_continuous``: however requests share
slots and chunks, each one is answered as if it ran alone on the core
engine -- from a zero state, on its tenant's registers as they stand
when it is admitted, counting spikes over its own tick budget -- and a
plastic tenant learns only inside that budget, its weights carried from
request to request in submission order.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import TickEngine
from repro.core.network import SNNState
from repro.kernels.ops import EventFanIn
from repro.plasticity import PlasticityState


def _run_one(engine, plastic, n_ticks, params, ext, plastic_c, rewards,
             budget, neighbors):
    """One request alone: ``(raster, final W, telemetry or None)``."""
    n = ext.shape[-1]
    if plastic:
        out = engine.learning_rollout(
            params, SNNState.zeros((), n), PlasticityState.zeros((), n), ext,
            n_ticks, rewards=rewards, plastic_c=plastic_c,
            learn_until=budget, neighbors=neighbors)
        (_, _, w), raster = out[:2]
    else:
        out = engine.rollout(params, SNNState.zeros((), n), ext, n_ticks,
                             neighbors=neighbors)
        w, raster = params.w, out[1]
    return raster, w, out[2] if engine.telemetry else None


def serve_reference(server, requests):
    """Serve ``requests`` one at a time, in submission order, on the
    unbatched engine with ``server``'s options and tenants.

    Sets each request's ``counts`` and ``pred``, writes each plastic
    tenant's learned ``W`` back into ``server.tenants``, and returns each
    tenant's summed telemetry ``dw_l1`` (empty without telemetry)."""
    T, N = server.max_ticks, server.n_max
    runs, dw_l1 = {}, {}
    for r in requests:
        t = server.tenants[r.tenant]
        if (t.backend, t.plastic) not in runs:
            engine = TickEngine(dataclasses.replace(
                server.engine.options, backend=t.backend))
            runs[t.backend, t.plastic] = jax.jit(
                functools.partial(_run_one, engine, t.plastic, T))
        budget = min(int(r.n_ticks), T)
        ext = np.zeros((T, N), np.float32)
        ext[:min(r.ext.shape[0], T), :r.ext.shape[1]] = r.ext[:T]
        rew = np.zeros((T,), np.float32)
        if r.rewards is not None:
            rew[:min(len(r.rewards), T)] = r.rewards[:T]
        nbrs = (EventFanIn(idx=t.fan_idx, mask=t.fan_mask)
                if t.backend == "event" else None)
        raster, w, telem = runs[t.backend, t.plastic](
            t.params, jnp.asarray(ext), t.plastic_c, jnp.asarray(rew),
            jnp.int32(budget), nbrs)
        r.counts = np.asarray(raster)[:budget].sum(0)[t.n - t.n_out:t.n]
        r.pred = int(r.counts.argmax())
        if t.plastic:
            t.params = dataclasses.replace(t.params, w=w)
        if telem is not None:
            dw_l1[t.name] = dw_l1.get(t.name, 0.0) + float(telem.dw_l1)
    return dw_l1
