"""Pallas kernel lint, driven by the kernels' own launch descriptors.

Every kernel in :mod:`repro.kernels` builds its ``pallas_call`` from a
:class:`repro.kernels.launch_spec.KernelLaunch`; this module lints that
same descriptor, so the checks can never drift from what actually
launches.  Crucially the BlockSpec index maps in a descriptor are plain
Python lambdas -- the lint *evaluates them directly* at every concrete
grid point (substituting worst-case example values for the
scalar-prefetch operands), instead of parsing
``pallas_call`` jaxpr params whose internal layout changes between jax
releases.

Rules:

* ``pallas.oob``      -- an index map selects a block outside its operand
  (an out-of-bounds DMA on real hardware: silent garbage or a fault).
* ``pallas.align``    -- a VMEM block whose last two dimensions are not
  divisible by the TPU's (8, 128) tiling and not equal to the operand's
  own: the TPU lowering refuses it, though interpret mode runs it.
* ``pallas.vmem``     -- estimated VMEM footprint (all tiled blocks
  double-buffered by the pipeline, plus scratch) exceeds the per-platform
  budget.
* ``pallas.alias``    -- an ``input_output_aliases`` entry pairs operands
  of different shape/dtype (or out-of-range indices).
* ``pallas.dma.*``    -- the manual-DMA protocol (``dma_schedule`` twin)
  violates semaphore pairing: start on a busy semaphore, use before
  wait, wait without start, a copy never waited, or a live spike never
  consumed.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

from repro.analysis.findings import ERROR, WARNING, Finding
from repro.kernels.launch_spec import KernelLaunch, Operand

__all__ = [
    "TPU_VMEM_BUDGET", "TPU_TILE", "check_index_maps", "check_alignment",
    "check_vmem", "check_aliasing", "check_dma_schedule", "check_launch",
]

# ~16 MiB of VMEM per TPU core; the budget the pipeline's working set
# must fit in (see DESIGN.md §14 for the estimator model).
TPU_VMEM_BUDGET = 16 * 1024 * 1024

# (sublanes, lanes): the last two dimensions of a VMEM block must be
# multiples of these, or span the whole operand dimension.
TPU_TILE = (8, 128)


def _grid_points(grid: Sequence[int]):
    """All concrete grid index tuples (row-major)."""
    points = [()]
    for extent in grid:
        points = [p + (i,) for p in points for i in range(extent)]
    return points


def check_index_maps(launch: KernelLaunch, program: str) -> List[Finding]:
    """Evaluate every BlockSpec index map at every grid point (with the
    worst-case prefetch example) and reject blocks that fall outside
    their operand -- the static form of an out-of-bounds DMA."""
    out: List[Finding] = []
    points = _grid_points(launch.grid)
    for op in launch.tiled_operands():
        bad = _oob_for_operand(op, points, launch.prefetch_example)
        if bad is not None:
            point, idx = bad
            out.append(Finding(
                rule="pallas.oob", severity=ERROR, program=program,
                location=f"{launch.name}:{op.name}",
                message=f"index map selects block {idx} at grid point "
                        f"{point}: exceeds operand shape {op.shape} with "
                        f"block {op.block_shape}"))
    return out


def _oob_for_operand(op: Operand, points, prefetch) -> Optional[Any]:
    assert op.index_map is not None and op.block_shape is not None
    for point in points:
        idx = op.index_map(*point, *prefetch)
        if not isinstance(idx, tuple):
            idx = (idx,)
        idx = tuple(int(i) for i in idx)
        if len(idx) != len(op.block_shape):
            return point, idx
        for i, b, extent in zip(idx, op.block_shape, op.shape):
            if i < 0 or (i + 1) * (b or 1) > extent:
                return point, idx
    return None


def check_alignment(launch: KernelLaunch, program: str) -> List[Finding]:
    """Every VMEM block's trailing two dimensions against the (8, 128)
    tiling -- the rule the TPU lowering enforces at compile time."""
    out: List[Finding] = []
    for op in launch.tiled_operands():
        if op.memory_space != "vmem":
            continue
        trailing = zip(op.block_shape[-2:], op.shape[-2:],
                       TPU_TILE[-len(op.block_shape[-2:]):])
        for b, extent, tile in trailing:
            if b % tile and b != extent:
                out.append(Finding(
                    rule="pallas.align", severity=ERROR, program=program,
                    location=f"{launch.name}:{op.name}",
                    message=f"block {op.block_shape} of operand {op.shape}: "
                            f"trailing dims must be multiples of "
                            f"{TPU_TILE} or equal the operand's"))
                break
    return out


def check_vmem(launch: KernelLaunch, program: str, *,
               budget: int = TPU_VMEM_BUDGET) -> List[Finding]:
    """Estimated peak VMEM (2x every tiled block + scratch) vs budget."""
    est = launch.vmem_bytes()
    out: List[Finding] = []
    if est > budget:
        out.append(Finding(
            rule="pallas.vmem", severity=ERROR, program=program,
            location=launch.name,
            message=f"estimated VMEM {est / 2 ** 20:.2f} MiB exceeds the "
                    f"{budget / 2 ** 20:.0f} MiB budget: shrink blocks"))
    elif est > budget * 0.75:
        out.append(Finding(
            rule="pallas.vmem", severity=WARNING, program=program,
            location=launch.name,
            message=f"estimated VMEM {est / 2 ** 20:.2f} MiB is within "
                    f"25% of the {budget / 2 ** 20:.0f} MiB budget"))
    return out


def check_aliasing(launch: KernelLaunch, program: str) -> List[Finding]:
    """``input_output_aliases`` pairs must exist and agree on shape+dtype
    (an aliased buffer is reused in place: a mismatch corrupts memory)."""
    out: List[Finding] = []
    for in_idx, out_idx in launch.input_output_aliases.items():
        loc = f"{launch.name}:alias {in_idx}->{out_idx}"
        if not (0 <= in_idx < len(launch.inputs)
                and 0 <= out_idx < len(launch.outputs)):
            out.append(Finding(
                rule="pallas.alias", severity=ERROR, program=program,
                location=loc, message="alias index out of range"))
            continue
        a, b = launch.inputs[in_idx], launch.outputs[out_idx]
        if a.shape != b.shape or str(a.dtype) != str(b.dtype):
            out.append(Finding(
                rule="pallas.alias", severity=ERROR, program=program,
                location=loc,
                message=f"aliased operands disagree: {a.name} "
                        f"{a.shape}/{a.dtype} vs {b.name} "
                        f"{b.shape}/{b.dtype}"))
    return out


def simulate_dma_schedule(ops, n_slots: int = 2):
    """Run one DMA op list through the semaphore state machine; returns a
    list of (rule, message) violations.

    Model: each buffer slot has one DMA semaphore.  ``start`` puts a copy
    in flight on the slot (illegal while one is already in flight --
    the second completion would double-signal the semaphore and corrupt
    the pairing); ``wait`` consumes the in-flight copy (illegal with
    nothing in flight: deadlock); ``use`` reads the buffer and must see
    exactly the spike the last completed copy delivered.
    """
    in_flight = [None] * n_slots   # spike id being copied into slot
    ready = [None] * n_slots       # spike id whose data sits in slot
    used = set()
    bad = []
    for op_kind, slot, k in ops:
        if not (0 <= slot < n_slots):
            bad.append(("pallas.dma.bad_slot",
                        f"op {op_kind} addresses slot {slot}"))
            continue
        if op_kind == "start":
            if in_flight[slot] is not None:
                bad.append((
                    "pallas.dma.start_busy",
                    f"start(spike {k}) on slot {slot} while spike "
                    f"{in_flight[slot]}'s copy is still in flight"))
            in_flight[slot] = k
        elif op_kind == "wait":
            if in_flight[slot] is None:
                bad.append(("pallas.dma.wait_without_start",
                            f"wait on slot {slot} with no copy in flight"))
            else:
                ready[slot] = in_flight[slot]
                in_flight[slot] = None
        elif op_kind == "use":
            if ready[slot] != k:
                have = ("in-flight (use before wait)"
                        if in_flight[slot] == k else
                        f"holds {ready[slot]}")
                bad.append(("pallas.dma.use_before_wait",
                            f"use(spike {k}) on slot {slot} but buffer "
                            f"{have}"))
            used.add(k)
        else:
            bad.append(("pallas.dma.bad_op", f"unknown op {op_kind!r}"))
    for slot, k in enumerate(in_flight):
        if k is not None:
            bad.append(("pallas.dma.dangling",
                        f"copy of spike {k} into slot {slot} never "
                        f"waited on"))
    return bad, used


def check_dma_schedule(launch: KernelLaunch, program: str, *,
                       max_live: int = 8) -> List[Finding]:
    """Simulate the kernel's manual-DMA protocol for every live-spike
    count up to ``max_live`` (plus 0: the quiet-row case must issue no
    DMA at all)."""
    if launch.dma_schedule is None:
        return []
    out: List[Finding] = []
    for nb in range(max_live + 1):
        ops = launch.dma_schedule(nb)
        bad, used = simulate_dma_schedule(ops)
        for rule, msg in bad:
            out.append(Finding(
                rule=rule, severity=ERROR, program=program,
                location=f"{launch.name}:nb={nb}", message=msg))
        missing = set(range(nb)) - used
        if missing:
            out.append(Finding(
                rule="pallas.dma.missing_spike", severity=ERROR,
                program=program, location=f"{launch.name}:nb={nb}",
                message=f"live spikes {sorted(missing)} never accumulated "
                        f"-- silent spike drop"))
        if nb == 0 and ops:
            out.append(Finding(
                rule="pallas.dma.quiet_row", severity=ERROR,
                program=program, location=f"{launch.name}:nb=0",
                message="quiet row issues DMA ops: the zero-cost-silence "
                        "contract is broken"))
    return out


def check_launch(launch: KernelLaunch, program: str, *,
                 vmem_budget: int = TPU_VMEM_BUDGET) -> List[Finding]:
    """All kernel-lint rules on one launch descriptor."""
    out = check_index_maps(launch, program)
    out += check_alignment(launch, program)
    out += check_vmem(launch, program, budget=vmem_budget)
    out += check_aliasing(launch, program)
    out += check_dma_schedule(launch, program)
    return out
