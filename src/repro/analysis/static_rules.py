"""Recompile-hazard rules: static arguments must be hashable and stable.

The serving layer's zero-recompile guarantee rests on every jit static
being (a) hashable, (b) built from immutable parts, and (c) equal (and
equal-hashing) across independently-constructed instances describing the
same configuration.  A dict/list/ndarray smuggled into a static
dataclass, or a ``__hash__`` that varies per instance, silently turns
every call into a retrace -- the bench gate catches that *after the
fact* by counting cache misses; these rules catch it by inspection.
"""

from __future__ import annotations

import dataclasses
import enum
import inspect
from typing import Any, Callable, List, Sequence

from repro.analysis.findings import ERROR, Finding

__all__ = [
    "is_deeply_immutable", "check_hashable_static", "check_hash_stability",
    "check_static_argnames", "check_dispatch_plan",
]

_ATOMS = (str, int, float, bool, bytes, type(None))


def _mesh_types() -> tuple:
    """jax's own static-intended mesh types."""
    from jax.sharding import AbstractMesh, Mesh

    return (Mesh, AbstractMesh)


def is_deeply_immutable(value: Any) -> bool:
    """True when ``value`` is built purely from immutable parts (the only
    things safe to use as jit statics)."""
    if isinstance(value, _ATOMS) or isinstance(value, enum.Enum):
        return True
    if isinstance(value, _mesh_types()):
        # jax.sharding.Mesh is jax's own jit-static currency: hashable,
        # ==/hash keyed on (device assignment, axis names), and nothing
        # user-reachable mutates one after construction.  EngineOptions
        # carries one for the sharded engine (DESIGN.md §15).
        return True
    if isinstance(value, (tuple, frozenset)):
        return all(is_deeply_immutable(v) for v in value)
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        params = getattr(value, "__dataclass_params__", None)
        if params is None or not params.frozen:
            return False
        return all(is_deeply_immutable(getattr(value, f.name))
                   for f in dataclasses.fields(value))
    return False


def check_hashable_static(value: Any, program: str, *,
                          name: str = "") -> List[Finding]:
    """``value`` is about to be used as a jit static: it must hash, and
    every reachable field must be immutable."""
    label = name or type(value).__name__
    out: List[Finding] = []
    try:
        hash(value)
    except TypeError as e:
        out.append(Finding(
            rule="static.unhashable", severity=ERROR, program=program,
            location=label,
            message=f"static `{label}` is unhashable: {e}"))
        return out
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        for f in dataclasses.fields(value):
            v = getattr(value, f.name)
            if not is_deeply_immutable(v):
                out.append(Finding(
                    rule="static.mutable_field", severity=ERROR,
                    program=program, location=f"{label}.{f.name}",
                    message=f"static field `{f.name}` holds mutable "
                            f"{type(v).__name__}: hash may drift or "
                            f"collide across calls"))
    elif not is_deeply_immutable(value):
        out.append(Finding(
            rule="static.mutable_field", severity=ERROR, program=program,
            location=label,
            message=f"static `{label}` ({type(value).__name__}) is not "
                    f"deeply immutable"))
    return out


def check_hash_stability(make: Callable[[], Any], program: str, *,
                         name: str = "") -> List[Finding]:
    """Two fresh instances of the same configuration must be ``==`` and
    hash-equal -- otherwise every independently-built request retraces.
    """
    a, b = make(), make()
    label = name or type(a).__name__
    out: List[Finding] = []
    try:
        if a != b:
            out.append(Finding(
                rule="static.unstable_eq", severity=ERROR, program=program,
                location=label,
                message=f"two fresh `{label}` instances compare unequal: "
                        f"per-call retrace"))
        elif hash(a) != hash(b):
            out.append(Finding(
                rule="static.unstable_hash", severity=ERROR,
                program=program, location=label,
                message=f"equal `{label}` instances hash differently "
                        f"(identity-based __hash__?): per-call retrace"))
    except TypeError as e:
        out.append(Finding(
            rule="static.unhashable", severity=ERROR, program=program,
            location=label, message=f"`{label}` is unhashable: {e}"))
    return out


def check_static_argnames(fn: Callable, static_argnames: Sequence[str],
                          program: str, *, name: str = "") -> List[Finding]:
    """Every declared static must exist in the (unwrapped) function
    signature as a keyword-bindable parameter -- a typo'd static name is
    silently ignored by jax until a shape under it changes, then every
    call retraces."""
    label = name or getattr(fn, "__name__", str(fn))
    out: List[Finding] = []
    try:
        sig = inspect.signature(inspect.unwrap(fn))
    except (TypeError, ValueError):
        out.append(Finding(
            rule="static.no_signature", severity=ERROR, program=program,
            location=label,
            message=f"cannot inspect signature of `{label}` to validate "
                    f"static_argnames"))
        return out
    kinds_ok = (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                inspect.Parameter.KEYWORD_ONLY)
    has_var_kw = any(p.kind == inspect.Parameter.VAR_KEYWORD
                     for p in sig.parameters.values())
    for s in static_argnames:
        p = sig.parameters.get(s)
        if p is None:
            if not has_var_kw:
                out.append(Finding(
                    rule="static.unknown_argname", severity=ERROR,
                    program=program, location=f"{label}({s})",
                    message=f"static_argnames entry `{s}` is not a "
                            f"parameter of `{label}`"))
        elif p.kind not in kinds_ok:
            out.append(Finding(
                rule="static.positional_only", severity=ERROR,
                program=program, location=f"{label}({s})",
                message=f"static `{s}` is {p.kind.description} in "
                        f"`{label}`: jax matches statics by keyword"))
    return out


def check_dispatch_plan(plan: Any, program: str) -> List[Finding]:
    """A :class:`~repro.core.dispatch_policy.DispatchPlan` carries arrays
    (neighbor lists) next to statics -- the plan object itself must NEVER
    be a jit static; only ``plan.engine_kwargs()`` may cross that
    boundary, and every value it exposes must be a stable static."""
    out: List[Finding] = []
    try:
        hash(plan)
        out.append(Finding(
            rule="static.plan_hashable", severity=ERROR, program=program,
            location=type(plan).__name__,
            message="DispatchPlan hashes -- someone could pass the whole "
                    "plan (arrays included) as a jit static, keying the "
                    "cache on array identity"))
    except TypeError:
        pass   # unhashable is the contract: arrays never become statics
    kwargs = plan.engine_kwargs()
    for k, v in kwargs.items():
        out.extend(check_hashable_static(
            v, program, name=f"engine_kwargs[{k}]"))
    return out
