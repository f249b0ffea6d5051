"""95th percentile of every gap between consecutive tokens of every
request inside the window, taken on the harness's clock after each tick
returns (host clock)."""
from bench.lib import drive


def read(run):
    gaps = drive.itls(run.window)
    return 1e3 * drive.tail(gaps, 95) if gaps else None
