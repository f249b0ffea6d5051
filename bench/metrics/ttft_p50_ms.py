"""Median, over every request due inside the window, of the milliseconds
from its due time to its first token, seen on the harness's clock after
the tick that produced it returns; a request with no first token by the
window's end counts to the window's end (host clock)."""
from bench.lib import drive


def read(run):
    t = drive.ttfts(run.window)
    return 1e3 * drive.tail(t, 50) if t else None
