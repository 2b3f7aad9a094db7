"""Bounded jax.profiler capture window for ``--profile-dir``.

The window starts a ``jax.profiler`` trace on construction and stops it
after ``max_spans`` instrumented spans have passed through — an
unconditional bound so a long training run can't fill the disk with
profile data.  The window hands out nothing: every telemetry span opens
its own TraceMe, which lands in whatever trace is active.  A trace that
was asked for and cannot start (no profiler backend, another trace
already active) raises: a run that silently drops its profile would look
like a profiled run.
"""

from __future__ import annotations

import threading

import jax

__all__ = ["ProfileWindow"]


class ProfileWindow:
    def __init__(self, logdir: str, max_spans: int = 64):
        self.logdir = logdir
        self.max_spans = max_spans
        self._spans = 0
        self._lock = threading.Lock()
        jax.profiler.start_trace(logdir)
        self._active = True

    @property
    def active(self) -> bool:
        return self._active

    def tick(self) -> None:
        """Count one completed span; close the window at the bound."""
        if not self._active:
            return
        with self._lock:
            self._spans += 1
            if self._spans >= self.max_spans:
                self._stop_locked()

    def stop(self) -> None:
        with self._lock:
            self._stop_locked()

    def _stop_locked(self) -> None:
        if not self._active:
            return
        self._active = False
        jax.profiler.stop_trace()
