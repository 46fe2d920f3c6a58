"""Jaxpr-level structural rules: purity, dtype discipline, hoist contracts.

These rules walk *closed jaxprs* (``jax.make_jaxpr``) rather than lowered
HLO text: the jaxpr is a stable, typed IR where "is this primitive a
callback", "what dtype is this aval", and "is this eqn inside a scan
body" are direct queries instead of regexes over a pretty-printer whose
output shifts between jax releases.

Nothing here isinstance-checks jaxpr types: sub-jaxprs hiding in
``eqn.params`` are recognized *structurally* (an object with ``.eqns``,
or wrapping one via ``.jaxpr``).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Iterable, Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.findings import ERROR, WARNING, Finding

__all__ = [
    "EqnSite", "iter_eqns", "closed_jaxpr_of",
    "check_hot_loop_purity", "check_dtype_discipline", "check_hoist",
    "CALLBACK_PRIMS", "TRANSFER_PRIMS", "DEFAULT_UPCAST_ALLOWLIST",
]

# Primitive names that call back into the host Python process.  Any of
# these inside a jitted tick program means a device->host sync (and on
# TPU, a buffer round-trip) per firing -- the exact thing the paper's
# "runtime reconfiguration without resynthesis" pitch forbids in our
# software analogue.  `debug_print` lowers through `debug_callback`; both
# names are listed because the primitive name differs across jax versions.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "outside_call", "host_callback_call",
})

# Primitives that move buffers between devices or to/from the host.
TRANSFER_PRIMS = frozenset({
    "device_put", "infeed", "outfeed",
    "transfer_to_host", "transfer_from_host",
})

# Loop-body primitives: an eqn inside one of these runs once per tick
# (or per chunk iteration), not once per program.
LOOP_PRIMS = frozenset({"scan", "while"})

# name_stack patterns under which a uint8 -> float convert is sanctioned
# (register decode / quantization boundaries -- the places u8 weights are
# *supposed* to widen, once, outside the hot loop).
DEFAULT_UPCAST_ALLOWLIST: Tuple[str, ...] = (
    r"decode_u8", r"quant", r"registers", r"encode",
)

_64BIT = (jnp.float64, jnp.complex128, jnp.int64, jnp.uint64)


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One eqn plus its structural context in the walk."""

    eqn: Any
    in_loop: bool
    path: str

    @property
    def name(self) -> str:
        return self.eqn.primitive.name

    @property
    def scope(self) -> str:
        """The ``jax.named_scope`` stack the eqn was traced under
        (empty string when source info is unavailable)."""
        try:
            return str(self.eqn.source_info.name_stack)
        except Exception:
            return ""


def _as_jaxpr(obj: Any) -> Any:
    """Duck-typed unwrap: a Jaxpr has ``.eqns``; a ClosedJaxpr wraps one
    via ``.jaxpr``.  Returns None for anything else."""
    if hasattr(obj, "eqns"):
        return obj
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return None


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    """Yield every jaxpr-like value reachable from an eqn's params
    (scan/pjit ``jaxpr``, cond ``branches`` tuples, while
    ``cond_jaxpr``/``body_jaxpr``, custom_* ``call_jaxpr`` ...)."""
    for val in params.values():
        j = _as_jaxpr(val)
        if j is not None:
            yield j
            continue
        if isinstance(val, (tuple, list)):
            for item in val:
                j = _as_jaxpr(item)
                if j is not None:
                    yield j


def iter_eqns(jaxpr: Any, *, in_loop: bool = False, path: str = "",
              recurse_pallas: bool = True) -> Iterator[EqnSite]:
    """Depth-first walk over every eqn in ``jaxpr`` and its sub-jaxprs.

    ``in_loop`` is True for eqns inside a ``scan``/``while`` body (at any
    nesting depth).  ``recurse_pallas=False`` treats ``pallas_call`` as
    opaque -- kernel-internal arithmetic is then the Pallas lint's
    problem (:mod:`repro.analysis.pallas_rules`), not this walk's.
    """
    j = _as_jaxpr(jaxpr)
    if j is None:
        raise TypeError(f"not a jaxpr-like object: {type(jaxpr)!r}")
    for i, eqn in enumerate(j.eqns):
        name = eqn.primitive.name
        here = f"{path}.{name}[{i}]" if path else f"{name}[{i}]"
        yield EqnSite(eqn, in_loop, here)
        if name == "pallas_call" and not recurse_pallas:
            continue
        child_in_loop = in_loop or name in LOOP_PRIMS
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, in_loop=child_in_loop, path=here,
                                 recurse_pallas=recurse_pallas)


def closed_jaxpr_of(fn: Callable, *args: Any, **kwargs: Any) -> Any:
    """``jax.make_jaxpr`` with kwargs threaded through."""
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)


# ---------------------------------------------------------------------------
# Rule (a): hot-loop purity
# ---------------------------------------------------------------------------

def check_hot_loop_purity(cj: Any, program: str, *,
                          allow: Sequence[str] = ()) -> List[Finding]:
    """No callback primitives in the program, no transfer primitives, and
    in particular no ``io_callback`` inside any scan/while body.

    ``allow`` lists primitive names exempted for this program (none of
    the shipped programs need one; fixtures use it to scope teeth tests).
    """
    out: List[Finding] = []
    for site in iter_eqns(cj):
        name = site.name
        if name in allow:
            continue
        if name in CALLBACK_PRIMS:
            if site.in_loop:
                out.append(Finding(
                    rule="purity.callback_in_loop", severity=ERROR,
                    program=program, location=site.path,
                    message=f"host callback `{name}` inside a scan/while "
                            f"body: one device->host sync per tick"))
            elif name == "io_callback":
                out.append(Finding(
                    rule="purity.io_callback", severity=WARNING,
                    program=program, location=site.path,
                    message="io_callback outside the loop: ordered host "
                            "effect serializes dispatch"))
            else:
                out.append(Finding(
                    rule="purity.callback", severity=ERROR,
                    program=program, location=site.path,
                    message=f"host callback `{name}` in a jitted program"))
        elif name in TRANSFER_PRIMS:
            out.append(Finding(
                rule="purity.transfer", severity=ERROR, program=program,
                location=site.path,
                message=f"transfer primitive `{name}` in a jitted program "
                        f"{'(inside loop body)' if site.in_loop else ''}"
                        .strip()))
    return out


# ---------------------------------------------------------------------------
# Rule (b): dtype discipline
# ---------------------------------------------------------------------------

def _avals_of(eqn: Any) -> Iterable[Any]:
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None:
            yield aval


def check_dtype_discipline(
        cj: Any, program: str, *,
        upcast_allowlist: Sequence[str] = DEFAULT_UPCAST_ALLOWLIST,
) -> List[Finding]:
    """No 64-bit avals anywhere (weak-typed or not), and every
    ``uint8 -> float`` widen sits under a sanctioned name scope.

    u8 is the paper's wire format (RegisterBank / UART); the SNN compute
    path is f32.  A u8 widen *inside* a jitted program is only legal at
    the register-decode / quantization boundary -- anywhere else it means
    register bytes leaked into the hot path and are being re-decoded per
    call (or worse, per tick).
    """
    out: List[Finding] = []
    pats = [re.compile(p) for p in upcast_allowlist]
    for aval in getattr(cj, "in_avals", ()):
        dt = getattr(aval, "dtype", None)
        if dt is not None and dt in _64BIT:
            out.append(Finding(
                rule="dtype.x64_input", severity=ERROR, program=program,
                location="in_avals",
                message=f"64-bit program input ({dt})"))
    for site in iter_eqns(cj):
        for aval in _avals_of(site.eqn):
            dt = getattr(aval, "dtype", None)
            if dt is not None and dt in _64BIT:
                weak = " (weak-type promotion)" if getattr(
                    aval, "weak_type", False) else ""
                out.append(Finding(
                    rule="dtype.x64", severity=ERROR, program=program,
                    location=site.path,
                    message=f"64-bit intermediate `{site.name}` -> "
                            f"{dt}{weak}"))
        if site.name == "convert_element_type":
            src = getattr(getattr(site.eqn.invars[0], "aval", None),
                          "dtype", None)
            dst = site.eqn.params.get("new_dtype")
            if (src == jnp.uint8 and dst is not None
                    and jnp.issubdtype(dst, jnp.floating)):
                scope = site.scope
                if not any(p.search(scope) for p in pats):
                    out.append(Finding(
                        rule="dtype.u8_upcast", severity=ERROR,
                        program=program, location=site.path,
                        message=f"uint8 -> {jnp.dtype(dst).name} widen "
                                f"outside sanctioned scopes (scope="
                                f"{scope or '<none>'})"))
    return out


# ---------------------------------------------------------------------------
# Rule (c): hoist contract
# ---------------------------------------------------------------------------

# What a program promises about the premasked W*C product:
HOIST_HOISTED = "hoisted"    # frozen weights: mul outside every loop body
HOIST_IN_LOOP = "in_loop"    # learning: weights change per tick, mul in body
HOIST_KERNEL = "kernel"      # mul lives inside a Pallas kernel; only assert
                             # no stray dense mul leaked outside the kernel
HOIST_SKIP = "skip"          # rule not applicable (no W*C in this program)


def _square_muls(cj: Any, n: int) -> Tuple[int, int]:
    """Count elementwise ``mul`` eqns whose operands are both (n, n):
    returns (inside-loop, outside-loop).  ``pallas_call`` bodies are
    opaque here -- a mul inside a kernel block is per-launch by
    construction and is judged by the kernel lint instead."""
    in_loop = hoisted = 0
    for site in iter_eqns(cj, recurse_pallas=False):
        if site.name != "mul":
            continue
        shapes = [getattr(getattr(v, "aval", None), "shape", None)
                  for v in site.eqn.invars]
        if all(s == (n, n) for s in shapes):
            if site.in_loop:
                in_loop += 1
            else:
                hoisted += 1
    return in_loop, hoisted


def check_hoist(cj: Any, program: str, *, n: int,
                expect: str = HOIST_HOISTED) -> List[Finding]:
    """The W*C premask contract, as a jaxpr-level structural assertion.

    The (n, n) elementwise product of weights and connectivity is the
    single largest intermediate in a tick.  Frozen-weight programs must
    materialize it ONCE per rollout (outside every scan body); learning
    programs must recompute it per tick (weights are loop-variant, a
    hoisted stale product would be a silent correctness bug) -- the rule
    has teeth in both directions.
    """
    if expect == HOIST_SKIP:
        return []
    in_loop, hoisted = _square_muls(cj, n)
    out: List[Finding] = []
    if expect == HOIST_HOISTED:
        if in_loop:
            out.append(Finding(
                rule="hoist.wc_in_loop", severity=ERROR, program=program,
                location=f"{in_loop} eqn(s)",
                message=f"frozen-weight program materializes ({n},{n}) "
                        f"W*C inside a loop body {in_loop}x"))
        if not hoisted:
            out.append(Finding(
                rule="hoist.wc_missing", severity=ERROR, program=program,
                message=f"no hoisted ({n},{n}) W*C multiply found -- "
                        f"premask was optimized away or never formed"))
    elif expect == HOIST_IN_LOOP:
        if not in_loop:
            out.append(Finding(
                rule="hoist.wc_not_in_loop", severity=ERROR,
                program=program,
                message=f"learning program has no in-loop ({n},{n}) W*C "
                        f"multiply: a hoisted stale premask would miss "
                        f"per-tick weight updates"))
    elif expect == HOIST_KERNEL:
        if in_loop:
            out.append(Finding(
                rule="hoist.wc_in_loop", severity=ERROR, program=program,
                location=f"{in_loop} eqn(s)",
                message=f"({n},{n}) W*C multiply leaked outside the "
                        f"kernel into a loop body"))
    else:
        raise ValueError(f"unknown hoist expectation {expect!r}")
    return out
