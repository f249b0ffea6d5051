"""The decode step's share of the chip's bf16 peak: its model FLOPs
(``flops.decode_step_flops`` at the live context lengths of each traced
step), averaged over the traced steps, over the device time per decode
call from the trace, over the peak (percent)."""
import numpy as np

from bench.lib import flops, trace


def read(run):
    if run.trace is None or run.peaks is None or not run.traced.decode_steps:
        return None
    calls = trace.module_calls(run.trace, "jit_decode_step")
    if not calls:
        return None
    work = np.mean([flops.decode_step_flops(run.dims, c)
                    for c in run.traced.decode_steps])
    return 100.0 * work / np.mean(calls) / run.peaks["bf16_flops"]
