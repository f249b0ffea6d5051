"""Device milliseconds per call of the engine's prefill program
(``jit_prefill_chunk``), from the profiler trace of the window's traced
part."""
import numpy as np

from bench.lib import trace


def read(run):
    if run.trace is None:
        return None
    calls = trace.module_calls(run.trace, "jit_prefill_chunk")
    return 1e3 * float(np.mean(calls)) if calls else None
