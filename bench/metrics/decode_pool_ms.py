"""Device milliseconds per call of the engine's decode program spent in
ops under the device scope ``pool``: the layer scan's cache carry and
its u16 views, the packed slot gather/scatter, K/V row writes and
logical gathers (``bench/lib/spans.py``, from the profiler trace of the
window's traced part). The entry also gives ``scopes_ms``: the decode
call's device ms split by every scope, ``unscoped`` included. None
where no op carries a scope."""
from bench.lib import spans


def read(run):
    red = spans.of_run(run)
    ms = spans.scopes_ms(red) if red else None
    return {"value": ms.get("pool", 0.0), "scopes_ms": ms} if ms else None
