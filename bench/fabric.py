"""Fabric data made from the seed, on the device: tenant registers, the
sharded all-to-all matrix, and the helpers both the program's inputs and
the plain reference are built from.

Every weight sits on a dyadic grid: a signed (or, for a plastic tenant,
unsigned) u8-style level times a power of two.  Every f32 sum of such
values is exact in any order, and every level is exact in bfloat16, so a
frozen fabric has one right answer, bit for bit.

Nothing here imports the program: the reference regenerates the same
arrays from the same seed.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LEVEL = 2.0 ** -7          # one u8 step of a threshold, leak or impulse


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed up to 64 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def pow2(x: float) -> float:
    return 2.0 ** round(math.log2(x))


# -- tenants of the multi-tenant fabric -----------------------------------------

def tenant_io(spec: Dict) -> Tuple[int, int]:
    """(n_in, n_out): a layered tenant's first third is its input layer and
    its last quarter its output layer; every other kind is driven and
    read on all of its neurons."""
    n = spec["n"]
    if spec["kind"] == "layered":
        return n // 3, n // 4
    return n, n


def layer_sizes(n: int) -> Tuple[int, int, int]:
    n_in, n_out = n // 3, n // 4
    return n_in, n - n_in - n_out, n_out


def synapses(spec: Dict) -> int:
    """Existing synapses of a tenant's topology (expected count for the
    random sparse kind).  The work counts are built on this number."""
    n, kind = spec["n"], spec["kind"]
    if kind == "dense":
        return n * (n - 1)
    if kind == "layered":
        a, h, b = layer_sizes(n)
        return a * h + h * b
    if kind == "ring":
        return n * spec["k"]
    if kind == "sparse":
        return round(spec["density"] * n * (n - 1))
    raise ValueError(f"unknown tenant kind {kind!r}")


def weight_scale(spec: Dict) -> float:
    """Power-of-two scale of a tenant's weight levels.

    Frozen tenants carry signed levels (-128..127): the scale keeps the
    recurrent drive of a neuron at about 0.6 of a threshold per tick at
    a 5% spike rate, so the fabric fires near its input rate instead of
    saturating.  The plastic tenant carries unsigned levels (0..255, the
    u8 domain its STDP clips to): the scale keeps its summed excitatory
    drive at about a fortieth of a threshold per tick at that rate, so
    the fabric cannot ignite into its saturated state."""
    fan_in = synapses(spec) / spec["n"]
    if spec.get("plastic"):
        return pow2(0.5 / fan_in)
    return pow2(16.0 / math.sqrt(20.0 * fan_in))


def _topology(key, spec: Dict) -> jax.Array:
    n, kind = spec["n"], spec["kind"]
    i = jnp.arange(n)
    if kind == "dense":
        return (i[:, None] != i[None, :]).astype(jnp.float32)
    if kind == "layered":
        a, h, _ = layer_sizes(n)
        layer = jnp.where(i < a, 0, jnp.where(i < a + h, 1, 2))
        return (layer[None, :] == layer[:, None] + 1).astype(jnp.float32)
    if kind == "ring":
        d = (i[None, :] - i[:, None]) % n
        return ((d >= 1) & (d <= spec["k"])).astype(jnp.float32)
    if kind == "sparse":
        c = jax.random.bernoulli(key, spec["density"], (n, n))
        return (c & (i[:, None] != i[None, :])).astype(jnp.float32)
    raise ValueError(f"unknown tenant kind {kind!r}")


def _tenant(key, spec: Dict) -> Dict[str, jax.Array]:
    n = spec["n"]
    kc, kw, kt, kl, kr = jax.random.split(key, 5)
    c = _topology(kc, spec)
    lo, hi = (0, 256) if spec.get("plastic") else (-128, 128)
    levels = jax.random.randint(kw, (n, n), lo, hi).astype(jnp.float32)
    w = levels * jnp.float32(LEVEL * weight_scale(spec)) * c
    return {
        "w": w,
        "c": c,
        "w_in": jnp.eye(n, dtype=jnp.float32),
        "v_th": jax.random.randint(kt, (n,), 64, 160).astype(jnp.float32)
        * LEVEL,
        "leak": jnp.full((n,), jax.random.randint(kl, (), 0, 8), jnp.float32)
        * LEVEL,
        "r_ref": jnp.full((n,), jax.random.randint(kr, (), 0, 3), jnp.int32),
    }


@functools.partial(jax.jit, static_argnums=1)
def _tenants(key, specs):
    keys = jax.random.split(key, len(specs))
    return tuple(_tenant(keys[i], dict(s)) for i, s in enumerate(specs))


def freeze_specs(specs):
    """Tenant specs as a hashable tuple (a static of the jitted maker)."""
    return tuple(tuple(sorted(s.items())) for s in specs)


def build_tenants(seed: int, specs) -> Dict[str, Dict[str, jax.Array]]:
    """Every tenant's registers, made on the device in one jitted call."""
    out = _tenants(seed_key(seed), freeze_specs(specs))
    return {s["name"]: t for s, t in zip(specs, out)}


# -- the sharded all-to-all fabric ----------------------------------------------

def stream_scales(n: int, n_in: int) -> Tuple[float, float]:
    """Weight of one level of the big fabric's (recurrent, input) signed
    levels: per tick, a neuron's recurrent drive at a 5% rate and its
    drive from the input channels at the input rate each have a spread
    of about half a threshold."""
    return (LEVEL * pow2(16.0 / math.sqrt(20.0 * n)),
            LEVEL * pow2(0.5 / (math.sqrt(0.05 * n_in) * 74 * LEVEL)))


def stream_levels(key, n: int, n_in: int, sharding=None):
    """Signed int8 levels of the (n, n) recurrent matrix and the (n_in, n)
    input matrix; with ``sharding`` the recurrent levels are generated
    shard-local (never whole on one device)."""
    kw, ki = jax.random.split(key)

    def gen(kw, ki):
        w = jax.random.randint(kw, (n, n), -128, 128, dtype=jnp.int8)
        w_in = jax.random.randint(ki, (n_in, n), -128, 128, dtype=jnp.int8)
        return w, w_in

    out = None if sharding is None else (sharding, sharding)
    return jax.jit(gen, out_shardings=out)(kw, ki)


def stream_lif(key, n: int) -> Dict[str, jax.Array]:
    kt = jax.random.fold_in(key, 1)
    return {
        "v_th": jax.random.randint(kt, (n,), 96, 160).astype(jnp.float32)
        * LEVEL,
        "leak": jnp.full((n,), 8 * LEVEL, jnp.float32),
        "r_ref": jnp.full((n,), 1, jnp.int32),
    }


def host_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), *stream])
