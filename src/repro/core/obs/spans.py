"""The program's one span primitive, on the device trace's clock.

``span(kind, **meta)`` opens ``jax.profiler.TraceAnnotation("asyncflow."
+ kind, **meta)``, so the span lands on its thread's line of the
profiler's host plane, on the same clock as the device's ops, with
``meta`` as the event's stats. On exit it records the same span into
the run's active :class:`~repro.core.workflow.events.EventLog` (host
``time.monotonic`` stamps, as the log's readers expect), with the kind
of the enclosing span on this thread as ``parent``. Outside a run (no
active log) a span only annotates; with the profiler off the annotation
costs about a microsecond.

A span's instance (the log's track) is given, or is the enclosing
span's, or the one bound to this thread with :func:`bind_instance`, or
the thread's name.

Compiles are counted here too: one ``jax.monitoring`` listener on the
backend-compile event keeps ``jit_compiles_total`` and
``jit_compile_seconds`` in the default registry and records each
compile (a program built or loaded from the persistent cache) into the
active log as a ``compile`` event, with its program name.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import jax
from jax.profiler import TraceAnnotation

from repro.core.obs.registry import get_registry

PREFIX = "asyncflow."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_local = threading.local()
_active = None          # the running StageRunner's EventLog, if any


def set_log(log):
    """Make ``log`` the active log (None: none); returns the previous."""
    global _active
    prev, _active = _active, log
    return prev


def bind_instance(instance: Optional[str]) -> None:
    """Name the log track of this thread's spans that have no instance
    and no enclosing span (None: the thread's name)."""
    _local.instance = instance


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _current():
    """(instance, parent kind) a span opened here now would get."""
    stack = _stack()
    if stack:
        return stack[-1]
    return (getattr(_local, "instance", None)
            or threading.current_thread().name), None


class span:
    """``with span("update.grad", step=3): ...`` — see the module doc.
    ``log`` records into that log in place of the active one."""

    __slots__ = ("kind", "meta", "instance", "log", "parent", "start",
                 "_note")

    def __init__(self, kind: str, *, instance: Optional[str] = None,
                 log=None, **meta):
        self.kind, self.meta = kind, meta
        self.instance, self.log = instance, log

    def __enter__(self) -> "span":
        inst, self.parent = _current()
        if self.instance is None:
            self.instance = inst
        _stack().append((self.instance, self.kind))
        self._note = TraceAnnotation(PREFIX + self.kind, **self.meta)
        self._note.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        end = time.monotonic()
        self._note.__exit__(*exc)
        _stack().pop()
        log = self.log if self.log is not None else _active
        if log is not None:
            log.record(self.instance, self.kind, self.start, end,
                       parent=self.parent, **self.meta)


def _on_compile(event: str, duration: float, fun_name: str = "?",
                **_) -> None:
    if event != COMPILE_EVENT:
        return
    reg = get_registry()
    reg.counter("jit_compiles_total",
                "programs compiled or loaded from the compile cache").inc()
    reg.histogram("jit_compile_seconds",
                  "seconds per program compiled or loaded").observe(duration)
    log = _active
    if log is not None:
        end = time.monotonic()
        inst, parent = _current()
        log.record(inst, "compile", end - duration, end, parent=parent,
                   program=fun_name)


# registered once: a module is imported once per process
jax.monitoring.register_event_duration_secs_listener(_on_compile)
