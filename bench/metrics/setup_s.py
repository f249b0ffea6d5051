"""Seconds from the process's start to the window's start: imports,
weights, calibration (a file after a checkout's first run), the engine,
warm-up and, where the cell needs it, its sessions' prefill; in an open
loop, to the start of its arrivals, whose warm stretch before the window
is traffic, not set-up (host clock)."""


def read(run):
    return run.setup_s
