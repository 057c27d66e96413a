"""Wall-clock spans on the profiler's clock.

The serving engine (``cluster/engine.PipelinedServingLoop``) opens
``jax.profiler.TraceAnnotation`` spans around the host work of each step,
so that a profiler capture shows them on the same clock as the device's
ops:

  span                 opened around                          metadata
  ``seifer.step``      one ``step()`` of the engine           --
  ``seifer.reconcile`` a rebind or reconcile at its top       ``kind``
  ``seifer.admit``     taking a batch off admission and       ``batch``
                       stacking its inputs
  ``seifer.stage``     one stage executor's call              ``stage first stop batch``
  ``seifer.codec``     one link codec's ``transcode`` or      ``hop codec op``
                       fused-path ``encode``
  ``seifer.complete``  handing each request its row of the    ``batch``
                       batch's output
  ``seifer.gc``        one Python garbage collection          ``generation``

The metadata travels as the event's stats (``ProfileEvent.stats``).  A span
measures host time only, waits inside a dispatch included: none of them
synchronises with the device.  Outside a profiler session each span costs
about a microsecond and records nothing.

Everything else in ``repro.obs`` (``SpanTracer``, the journal, the metrics
registry) runs on the engine's virtual clock.
"""

from __future__ import annotations

import gc

from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

_gc_span: list = []  # the open ``seifer.gc`` span, while a collection runs


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if not _profiler.TraceMe.is_enabled():
            return
        span = TraceAnnotation("seifer.gc", generation=info["generation"])
        span.__enter__()
        _gc_span.append(span)
    elif _gc_span:
        _gc_span.pop().__exit__(None, None, None)


def install_gc_span() -> None:
    """Open a ``seifer.gc`` span around every garbage collection while a
    profiler session records; idempotent, once per process."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
