"""Device milliseconds per call of the engine's decode program (masked or
packed), from the profiler trace of the window's traced part."""
import numpy as np

from bench.lib import trace


def read(run):
    if run.trace is None:
        return None
    calls = trace.module_calls(run.trace, "jit_decode_step")
    return 1e3 * float(np.mean(calls)) if calls else None
