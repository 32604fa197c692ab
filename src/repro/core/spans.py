"""Named host spans of the compiler, for the JAX profiler's trace.

``span(name, **ints)`` is a ``jax.profiler.TraceAnnotation`` once jax
is loaded, so the span lands in the profiler's own trace on the same
clock as the device's programs; in a process that never imported jax
it is a shared no-op context, and importing this module loads nothing.
With no trace running a span costs a fraction of a microsecond, so
spans sit at stage and round boundaries only, never inside a per-task
or per-layer loop.
"""

from __future__ import annotations

import contextlib
import sys

COMPILE_MANY = "pfdnn.compile_many"     # one compile_many batch
CONTEXT = "pfdnn.context"               # one context_for call
SWEEP = "pfdnn.sweep"                   # run_stacked_sweeps' round loop
ROUND = "pfdnn.round"                   # one round of that loop
ROUND_DISPATCH = "pfdnn.round.dispatch"  # staging and kernel dispatch
ROUND_MOVES = "pfdnn.round.moves"       # host move scoring (in dispatch)
ROUND_BARRIER = "pfdnn.round.barrier"   # device wait and device→host read
ROUND_EVAL = "pfdnn.round.eval"         # path costs and the task machines
ROUND_ADMIT = "pfdnn.round.admit"       # completions, cuts, admission
EMIT = "pfdnn.emit"                     # selection and the artifact
LANES_UPLOAD = "pfdnn.lanes.upload"     # lane mirror growth or upload

_OFF = contextlib.nullcontext()


def span(name: str, **ints: int):
    """A context manager that records ``name`` (with ``ints`` as its
    arguments) while a profiler trace runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **ints)
