"""Host spans and step counters for the profiler's trace.

Tracing is on while a JAX profiler trace records (``jax.profiler.trace``
or ``start_trace``), and only then.  :func:`span` then opens a
``jax.profiler.TraceAnnotation`` named ``uep.<name>``, carrying its
arguments (``rid``: a request; ``pos``: a prefill chunk's offset; ``n``:
the rows of a decode batch), so the program's host work lies on the
profiler's clock beside the device's ops.  The profiler holds the spans
and writes them out when the trace ends.  Off, :func:`span` hands back
one shared no-op context: it reads no clock and allocates nothing.

:func:`count` keeps the MoE counters a jitted step returns
(``models.model.MoECounters``), with the capacity rows of the step's
expert slots and the top-k pairs of its valid tokens, while tracing is on.  The arrays
stay on the device until a reader takes them once, after the traced
window, with :func:`counts`, and then calls :func:`reset`.
"""

from __future__ import annotations

import contextlib
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation

__all__ = ["active", "span", "count", "counts", "reset", "StepCounts",
           "PREFIX"]

PREFIX = "uep."
_OFF = contextlib.nullcontext()
_COUNTS: list = []
# A profiler session records TraceMe events; a cheap native check.
active = TraceAnnotation.is_enabled


def span(name: str, *, rid: int | None = None, pos: int | None = None,
         n: int | None = None):
    """A host span ``uep.<name>`` in the profiler's trace while it records."""
    if not active():
        return _OFF
    args = {k: v for k, v in (("rid", rid), ("pos", pos), ("n", n))
            if v is not None}
    return TraceAnnotation(PREFIX + name, **args)


class StepCounts(NamedTuple):
    """One jitted step's MoE counters, per layer."""

    kind: str                    # "prefill" or "decode"
    counters: Any                # models.model.MoECounters (device arrays)
    slot_rows: tuple[int, ...]   # capacity rows of the layer's expert slots
    valid_pairs: tuple[int, ...]  # top-k pairs of the input's valid tokens


def count(kind: str, counters, slot_rows: tuple[int, ...],
          valid_pairs: tuple[int, ...]) -> None:
    """Keep one step's counters (device arrays, not read here) while
    tracing is on."""
    if active():
        _COUNTS.append(StepCounts(kind, counters, slot_rows, valid_pairs))


def counts() -> list[StepCounts]:
    """The steps kept since the last :func:`reset`."""
    return list(_COUNTS)


def reset() -> None:
    _COUNTS.clear()
