"""Mean milliseconds from a request's due time to its admission into a
slot, over the requests first admitted inside the traced window: how late
the harness submitted it (the harness's clock) plus the ``queued_ms`` of
the engine's ``engine.admit`` span (submission to admission on the
engine's clock, in whole ms; ``bench/lib/spans.py``). None where the
traced window admitted nothing or the program records no such span."""
import numpy as np

from bench.lib import spans


def read(run):
    red = spans.of_run(run)
    if not red or not red["admits"]:
        return None
    seen = {s.req.rid: s for s in run.window.seen}
    waits = {}
    for rid, queued_ms in red["admits"]:
        if rid in seen and rid not in waits:
            s = seen[rid]
            waits[rid] = 1e3 * (s.submitted - s.due) + queued_ms
    return float(np.mean(list(waits.values()))) if waits else None
