"""Share of the chip's bf16 peak reached by the prefill program: the
model FLOPs of the prefill chunks that began in the traced window
(``flops.prefill_chunk_flops`` at each chunk's prompt tokens and place in
its prompt), averaged, over the device time per ``jit_prefill_chunk``
call, over the peak (percent).

A chunk's tokens and request come from the engine's ``engine.prefill``
spans (``bench/lib/spans.py``). A request's chunks are consecutive and
all but its last are full, so they count from the prompt's start where
its ``engine.admit`` span is in the window, or back from the prompt's
end where its first token came inside the window; the chunks of a
request mid-prefill at both ends of the window are left out."""
import numpy as np

from bench.lib import flops, spans, trace


def chunks(run, red):
    """(start, tokens) of each chunk whose place is known."""
    c = run.cfg["serving"]["prefill_chunk"]
    admitted = {rid for rid, _ in red["admits"]}
    seen = {s.req.rid: s for s in run.window.seen}
    by_rid = {}
    for rid, n in red["prefills"]:
        by_rid.setdefault(rid, []).append(n)
    out = []
    for rid, ns in by_rid.items():
        s = seen.get(rid)
        if s is None:
            continue
        if rid in admitted:
            first = 0
        elif s.tokens and s.tokens[0] <= run.traced.t1:
            first = -(-(len(s.req.prompt) - 1) // c) - len(ns)
        else:
            continue
        out += [((first + i) * c, n) for i, n in enumerate(ns)]
    return out


def read(run):
    red = spans.of_run(run)
    if red is None or run.peaks is None or run.trace is None:
        return None
    calls = trace.module_calls(run.trace, "jit_prefill_chunk")
    work = [flops.prefill_chunk_flops(run.dims, s, n)
            for s, n in chunks(run, red)]
    if not calls or not work:
        return None
    return 100.0 * np.mean(work) / np.mean(calls) / run.peaks["bf16_flops"]
