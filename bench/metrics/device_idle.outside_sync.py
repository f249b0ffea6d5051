"""Share of the traced window in which no operation ran on the device
and the engine's thread was not inside ``engine.decode.sync`` (waiting
for the decode step's tokens), integrated over each idle interval
(``bench/lib/spans.py``), in percent. The entry also gives
``idle_by_span_ms``: the window's idle device time by the innermost host
span the engine's thread was in. None where the trace holds no device
or no engine spans."""
from bench.lib import spans


def read(run):
    red = spans.of_run(run)
    if not red or not red["devices"] or not red["engine_spans"]:
        return None
    return {"value": 100.0 * red["idle_outside_sync_s"] / red["window_s"],
            "idle_by_span_ms": {k: 1e3 * v
                                for k, v in red["idle_by_span"].items()}}
