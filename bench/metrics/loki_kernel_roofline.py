"""Roofline share of the fused Loki decode kernel: the least time the chip
needs for the kernel's FLOPs and bytes per decode step
(``flops.loki_kernel_cost`` at each traced step's context lengths,
averaged), over the kernel's summed device time per decode call in the
trace (percent). The entry names the roof that bounds it."""
import numpy as np

from bench.lib import flops, trace

# how the kernel's operations are named in a TPU trace
KERNEL = r"loki_decode_fused|fused_loki"


def read(run):
    if run.trace is None or run.peaks is None or not run.traced.decode_steps:
        return None
    secs, n_ops = trace.op_seconds(run.trace, KERNEL)
    calls = trace.module_calls(run.trace, "jit_decode_step")
    if not n_ops or not calls:
        return None
    cost = np.mean([flops.loki_kernel_cost(run.dims, c)
                    for c in run.traced.decode_steps], axis=0)
    share, bound = flops.roofline_share(
        cost[0], cost[1], secs / len(calls), run.peaks["bf16_flops"],
        run.peaks["hbm_bytes_per_s"])
    return {"value": share, "bound": bound}
