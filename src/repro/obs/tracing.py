"""Tracing hooks: named scopes in traced code, profiler spans on the host.

Two different tools for two different views of the same program:

* :func:`trace_scope` -- ``jax.named_scope``: labels ops while *tracing*,
  so HLO dumps and profiler op breakdowns read ``tick/event`` instead of
  ``while/body/dot_general.42``. Free at runtime (pure metadata; the
  telemetry-off HLO-identity pin in tests/test_obs.py proves named
  scopes do not perturb the lowered program).

* :func:`span` -- ``jax.profiler.TraceAnnotation``: marks *host wall
  time* regions (slot refill, encode/decode) so a captured profiler
  trace shows where serving time actually went. Optionally observes the
  elapsed seconds into a :class:`~repro.obs.metrics.Histogram`.

* :func:`profile` -- capture a ``jax.profiler`` trace into a directory
  (the ``--profile <dir>`` flag on the serve and bench CLIs); viewable
  with TensorBoard or Perfetto. A no-op when the directory is None, and
  capture failures degrade to a logged warning, never a crash.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax

from repro.obs.log import log_event


def trace_scope(name: str):
    """Label ops in traced code (``with trace_scope("tick/event"): ...``)."""
    return jax.named_scope(name)


@contextlib.contextmanager
def span(name: str, histogram=None, **labels) -> Iterator[None]:
    """Host wall-time span: profiler annotation + optional histogram sink.

    ``labels`` ride the profiler event as its metadata (``rid=3``,
    ``slot=1``: Perfetto and TensorBoard show them beside the span).

    Args:
      histogram: optional :class:`repro.obs.metrics.Histogram`; the span's
        elapsed seconds are observed into it with ``labels``.
    """
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, **labels):
        try:
            yield
        finally:
            if histogram is not None:
                histogram.observe(time.perf_counter() - t0, **labels)


@contextlib.contextmanager
def profile(outdir: Optional[str]) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace into ``outdir`` (None -> no-op).

    Wraps ``jax.profiler.trace``; start/stop failures (sandboxed CI,
    missing profiler backend) are logged and swallowed so a profiling
    flag can never take down a serving run.
    """
    if not outdir:
        yield
        return
    try:
        ctx = jax.profiler.trace(outdir)
        ctx.__enter__()
    except Exception as e:  # noqa: BLE001 -- observability must not crash serving
        log_event("profile_failed", outdir=outdir, error=repr(e))
        yield
        return
    try:
        yield
    finally:
        try:
            ctx.__exit__(None, None, None)
            log_event("profile_captured", outdir=outdir)
        except Exception as e:  # noqa: BLE001
            log_event("profile_failed", outdir=outdir, error=repr(e))
