"""Kernel launch descriptors: ONE structure drives both the
``pallas_call`` and the static lint.

Every Pallas kernel in this package builds its grid / BlockSpecs /
scratch through a :class:`KernelLaunch` returned by a module-level
``*_launch(...)`` builder.  The kernel entry point materializes real
``pl.BlockSpec`` objects from it; :mod:`repro.analysis.pallas_rules`
reads the *same* descriptor to evaluate index maps at concrete grid
points (out-of-bounds DMA detection), estimate the VMEM footprint, and
check aliasing declarations -- so the lint can never drift from what the
kernel actually launches, and never needs to parse ``pallas_call`` eqn
params (whose layout churns between jax releases).

Index maps here are the plain Python lambdas handed to ``pl.BlockSpec``:
the analyzer calls them directly with integer grid indices (plus example
scalar-prefetch values), no tracing involved.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Operand", "Scratch", "KernelLaunch"]

# Memory-space tags (strings, not pltpu enums, so the analyzer can reason
# about them without importing TPU-only symbols).
VMEM = "vmem"
SMEM = "smem"
ANY = "any"    # stays in HBM; the kernel DMAs slices manually


@dataclasses.dataclass(frozen=True)
class Operand:
    """One kernel input/output: full shape + the BlockSpec that tiles it.

    ``block_shape``/``index_map`` are None for ``memory_space="any"``
    operands (no automatic pipelining -- the kernel issues its own DMAs,
    described by :attr:`KernelLaunch.dma_schedule`).  A None entry of
    ``block_shape`` is a dimension of one, squeezed out of the kernel's
    view of the block.
    """

    name: str
    shape: Tuple[int, ...]
    dtype: Any
    block_shape: Optional[Tuple[int, ...]] = None
    index_map: Optional[Callable[..., Tuple[int, ...]]] = None
    memory_space: str = VMEM

    def block_spec(self):
        """The real ``pl.BlockSpec`` this descriptor stands for."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        if self.memory_space == ANY:
            return pl.BlockSpec(memory_space=pl.ANY)
        if self.memory_space == SMEM:
            return pl.BlockSpec(self.block_shape, self.index_map,
                                memory_space=pltpu.SMEM)
        return pl.BlockSpec(self.block_shape, self.index_map)

    @property
    def block_bytes(self) -> int:
        if self.block_shape is None:
            return 0   # HBM-resident; manual DMAs are scratch-accounted
        return (math.prod(b for b in self.block_shape if b is not None)
                * np.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class Scratch:
    """One scratch allocation: ``kind`` is ``vmem`` | ``sem_dma`` |
    ``sem``; semaphores carry shape only."""

    kind: str
    shape: Tuple[int, ...] = ()
    dtype: Any = np.float32

    def shape_obj(self):
        from jax.experimental.pallas import tpu as pltpu

        if self.kind == "vmem":
            return pltpu.VMEM(self.shape, self.dtype)
        if self.kind == "sem_dma":
            return pltpu.SemaphoreType.DMA(self.shape)
        if self.kind == "sem":
            return pltpu.SemaphoreType.REGULAR
        raise ValueError(f"unknown scratch kind {self.kind!r}")

    @property
    def bytes(self) -> int:
        if self.kind != "vmem":
            return 0
        return math.prod(self.shape) * np.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class KernelLaunch:
    """Everything the ``pallas_call`` and the lint both need to know.

    ``prefetch_example`` holds concrete example values for the
    scalar-prefetch operands (worst-case indices included): the analyzer substitutes them for ``s`` when it
    evaluates index maps.  ``dma_schedule`` is the manual-DMA protocol
    twin for kernels that stream from ``ANY``-space operands (see
    :func:`repro.kernels.event_dispatch.db_dma_schedule`).
    """

    name: str
    grid: Tuple[int, ...]
    inputs: Tuple[Operand, ...]
    outputs: Tuple[Operand, ...]
    scratch: Tuple[Scratch, ...] = ()
    num_scalar_prefetch: int = 0
    prefetch_example: Tuple[np.ndarray, ...] = ()
    input_output_aliases: Dict[int, int] = dataclasses.field(
        default_factory=dict)
    dma_schedule: Optional[Callable[..., List[Tuple]]] = None

    # -- pallas_call construction -----------------------------------------

    def grid_spec(self):
        from jax.experimental.pallas import tpu as pltpu

        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=self.num_scalar_prefetch,
            grid=self.grid,
            in_specs=[op.block_spec() for op in self.inputs],
            out_specs=[op.block_spec() for op in self.outputs],
            scratch_shapes=[s.shape_obj() for s in self.scratch],
        )

    def out_shapes(self):
        import jax

        return [jax.ShapeDtypeStruct(op.shape, op.dtype)
                for op in self.outputs]

    def gather(self, arrays: Dict[str, Any]) -> List[Any]:
        """Order a name->array dict into positional pallas_call operands
        (the descriptor's input order is THE order)."""
        return [arrays[op.name] for op in self.inputs]

    # -- lint-facing views -------------------------------------------------

    def tiled_operands(self) -> Sequence[Operand]:
        return [op for op in tuple(self.inputs) + tuple(self.outputs)
                if op.block_shape is not None]

    def vmem_bytes(self) -> int:
        """Estimated peak VMEM: every tiled block double-buffered by the
        Pallas pipeline (x2), plus explicit scratch."""
        tiles = sum(op.block_bytes for op in self.tiled_operands())
        return 2 * tiles + sum(s.bytes for s in self.scratch)
