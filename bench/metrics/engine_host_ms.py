"""Host milliseconds per decode tick outside the device sync: the mean,
over the window's untraced ticks that decoded, of the engine's own tick
time minus its ``engine.decode.sync`` phase (the program's tick log,
``serving/tracing.py``, on the engine's clock; ``spans.tick_rows``).
Untraced ticks only: the profiler's Python tracer inflates host time.
The entry also gives ``longest_tick_phases``: the window's five longest
ticks, each split by the engine's phases. None where the program keeps
no tick log."""
import numpy as np

from bench.lib import spans


def read(run):
    got = spans.tick_rows(run)
    if got is None:
        return None
    rows, n_traced = got
    first_traced = len(rows) - n_traced
    host = [r["ms"]["engine.tick"] - r["ms"]["engine.decode.sync"]
            for r in rows[:first_traced] if r["decode_rows"]]
    if not host:
        return None
    longest = sorted(range(len(rows)),
                     key=lambda i: -rows[i]["ms"]["engine.tick"])[:5]
    return {"value": float(np.mean(host)),
            "longest_tick_phases": [
                {"tick": rows[i]["tick"], "traced": i >= first_traced,
                 "ms": {k: v for k, v in rows[i]["ms"].items() if v}}
                for i in longest]}
