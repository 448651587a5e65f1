"""The solver's own spans and counters: one module records both.

Spans. ``span(name, **args)`` opens ``jax.profiler.TraceAnnotation`` as
``repro.<name>``: a host event in the profiler's trace, on the clock of
the device planes, with ``args`` as its stats; with the profiler off it
costs about a microsecond. Spans belong in host code only (inside a
jitted function they would time the trace, not the run). ``stage`` is
the one stage timer: it opens the stage's span and adds the host seconds
to ``stage_times[name]``; ``timed`` runs a stage in it and waits for the
result.

Counters. ``counting()`` opens a fresh ``{"host_syncs", "dispatches",
"compiles", "split_operator"}`` dict of ints for the calling thread
(``gsyeig.solve`` opens one per solve and returns it as
``info["counters"]``); while it is open:

* ``host_syncs`` counts every wait of the calling thread on the device,
  which the solver routes through ``wait`` (``block_until_ready``) and
  ``fetch`` (``device_get``);
* ``dispatches`` counts the jitted-program invocations routed through a
  ``DispatchCounter`` (each hot module also keeps its own count, the
  ``dispatch_count()`` / ``reset_dispatch_count()`` pair the regression
  tests pin);
* ``compiles`` counts programs lowered on the thread, by a
  ``jax.monitoring`` listener on the jaxpr-to-MLIR lowering, which
  precedes both a backend compile and a persistent-cache load;
* ``split_operator`` counts the KE iterations that ran on the int8
  split form of C (``core.operators.SplitC``): 1 a solve there, else 0.

Counters nest: an inner ``counting()`` counts into the outer ones too.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict

import jax

PREFIX = "repro."
#: the monitoring event of one jaxpr lowered to an MLIR module
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COUNTERS = ("host_syncs", "dispatches", "compiles", "split_operator")

_local = threading.local()


def span(name: str, **args):
    """Host span ``repro.<name>`` in the profiler's trace."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def _open() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def count(key: str) -> None:
    """Count one ``key`` in every counter open on this thread."""
    for c in _open():
        c[key] += 1


@contextmanager
def counting():
    """A fresh per-call counter dict, open on this thread until exit."""
    c = dict.fromkeys(COUNTERS, 0)
    stack = _open()
    stack.append(c)
    try:
        yield c
    finally:
        stack.remove(c)


def wait(x):
    """``jax.block_until_ready(x)``, counted as one host sync."""
    count("host_syncs")
    return jax.block_until_ready(x)


def fetch(x):
    """``jax.device_get(x)``, counted as one host sync."""
    count("host_syncs")
    return jax.device_get(x)


@contextmanager
def stage(times: Dict[str, float], name: str):
    """The stage ``name``: ``span(name)``, its host seconds added to
    ``times[name]``. The block ends with a ``wait`` on its result."""
    with span(name):
        t0 = time.perf_counter()
        yield
        times[name] = times.get(name, 0.0) + (time.perf_counter() - t0)


def timed(times: Dict[str, float], name: str, fn, /, *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` as the stage ``name`` and wait for its
    result."""
    with stage(times, name):
        return wait(fn(*args, **kwargs))


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == LOWERING_EVENT:
        count("compiles")


jax.monitoring.register_event_duration_secs_listener(_on_duration)


class DispatchCounter:
    """Callable counter: ``counter(fn, *args)`` counts 1 dispatch, here
    and in every open ``counting()``, and calls ``fn``. When tracing
    inside an outer jit the count reflects the trace, which is the number
    of programs the host would dispatch."""

    def __init__(self) -> None:
        self._count = 0

    def count(self) -> int:
        return self._count

    def reset(self) -> None:
        self._count = 0

    def __call__(self, fn, *args, **kwargs):
        self._count += 1
        count("dispatches")
        return fn(*args, **kwargs)
