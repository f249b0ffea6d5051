"""A trace guard that also names the engine's programs.

The engine jits each of its programs around ``trace_guard.wrap(name, fn)``.
This guard gives the wrapped function that name, so the compiled programs
carry it (``jit_decode_step``, ``jit_prefill_chunk``,
``jit_decode_step_packed_b8``) and the profiler trace tells them apart.
Like the program's guard it counts traces, and after ``seal()`` a retrace
raises: nothing compiles inside the measured window.
"""
from __future__ import annotations

import re

from repro.analysis.trace_guard import TraceGuard


class NamedGuard(TraceGuard):
    def wrap(self, name, fn):
        traced = super().wrap(name, fn)
        traced.__name__ = traced.__qualname__ = \
            re.sub(r"\W+", "_", name).strip("_")
        return traced
