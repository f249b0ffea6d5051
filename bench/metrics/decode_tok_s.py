"""Every token generated in the window over the window's seconds (host
clock; the window closes after the tick that crosses its end)."""
from bench.lib import drive


def read(run):
    w = run.window
    return drive.tokens_in(w) / w.seconds if w.seconds > 0 else None
