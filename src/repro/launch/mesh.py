"""Production mesh construction + per-cell sharding-rule assembly.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state -- the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization and only then builds meshes.
"""
from __future__ import annotations


import jax

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.parallel.sharding import AxisRules, BASE_RULES, fsdp_overrides, multipod_overrides


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_snn_mesh(n_devices: int | None = None, axis: str = "model") -> jax.sharding.Mesh:
    """1-D mesh the SNN fabric shards over (DESIGN.md §15).

    ``n_devices=None`` takes every visible device.  On a plain CPU host,
    call :func:`repro.util.env.ensure_host_device_count` BEFORE any jax
    op to simulate a mesh (this is a function, not a module constant,
    for exactly that reason -- importing this module must not initialize
    the backend).
    """
    if n_devices is None:
        n_devices = len(jax.devices())
    if n_devices < 1 or n_devices > len(jax.devices()):
        raise ValueError(
            f"n_devices={n_devices} out of range: {len(jax.devices())} "
            "devices visible (set XLA_FLAGS="
            "--xla_force_host_platform_device_count before jax init, "
            "e.g. via repro.util.env.ensure_host_device_count)")
    return jax.make_mesh((n_devices,), (axis,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def make_rules(
    mesh: jax.sharding.Mesh,
    cfg: ModelConfig,
    shape: ShapeConfig,
    pcfg: ParallelConfig,
    *,
    multi_pod: bool = False,
) -> AxisRules:
    """BASE_RULES + multipod + fsdp + shape-driven + per-cell overrides."""
    rules = AxisRules(BASE_RULES, mesh=mesh)
    over = {}
    if multi_pod:
        over.update(multipod_overrides())
    if pcfg.fsdp:
        over.update(fsdp_overrides())
    if pcfg.seq_shard_activations and shape.kind == "train":
        over.update({"seq": "model"})
    if shape.kind in ("prefill", "decode"):
        # KV caches shard along their sequence axis over "model"
        # (flash-decoding): decode computes shard-local partial attention,
        # combining with tiny collectives instead of gathering the cache.
        over["kv_seq"] = "model"
    if shape.global_batch == 1:
        # long_500k: nothing to shard on batch; shard the KV sequence over
        # every axis we have. The one-token query stays replicated.
        data_axes = ("pod", "data") if multi_pod else ("data",)
        over["batch"] = None
        over["seq"] = None
        over["kv_seq"] = tuple(data_axes) + ("model",)
    over.update(dict(pcfg.rule_overrides))
    return rules.with_overrides(over)
