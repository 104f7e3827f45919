"""Program spans: named host intervals in the profiler's trace.

``span(name)`` marks a stretch of host work as a
``jax.profiler.TraceAnnotation``, which a running ``jax.profiler`` session
writes into its host plane on the same clock as the device's ``XLA Ops``
events; outside a session nothing is recorded.  Spans are off by default
and ``enable`` is the only switch.  While they are off, ``span`` returns one
shared no-op context and imports nothing, so the ``"python"`` backends
still never import JAX.  Spans touch no array and no RNG stream: answers
are bit-identical with them on or off.

The spans, from the outside in (docs/DESIGN.md section 17):

* ``repro.sa.start`` / ``repro.sa.finish`` - a fleet's encode and decode;
* ``repro.sa.seed`` - the NFD chain seeding inside ``start``, and inside it
  ``repro.nfd.kinds``, each seed's greedy RAM-kind assignment, and inside
  that ``repro.nfd.kinds.walk``, its sorted walk of moves, open only when
  some bounded kind starts over its count;
* ``repro.sa.propose`` / ``repro.sa.accept`` - one annealing step before
  and after its delta-cost request;
* ``repro.dispatch.h2d`` / ``.launch`` / ``.d2h`` - one kernel call of
  ``sa_step_deltas``: the one host-to-device put of the stacked plane
  block (4 or 6 planes, stacked on the host inside the span), the jitted
  call, and the blocking read-back.
* ``repro.ga.mutate`` - one GA generation's mutations: every NFD repack or
  buffer swap, and the mutated rows' geometry update.
"""
from __future__ import annotations

import contextlib

_OFF = contextlib.nullcontext()
_annotation = None  # jax.profiler.TraceAnnotation while spans are on


def enable(on: bool) -> None:
    """Turn program spans on or off (off at import)."""
    global _annotation
    if on:
        import jax.profiler

        _annotation = jax.profiler.TraceAnnotation
    else:
        _annotation = None


def span(name: str):
    """A context that marks ``name`` in the profiler's trace while spans are on."""
    if _annotation is None:
        return _OFF
    return _annotation(name)
