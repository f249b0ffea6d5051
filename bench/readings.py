"""Read the output check's numbers over many seeds in one process: for each
seed one run of the cell (its own window length and load), the program's
reading, and the control's reading on the same prompts and served tokens.
The control is the reference computed in float8 e4m3 put in the program's
place: at each position, the gap of the token it puts first.

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds 1,2,3

Prints one JSON line per seed. A cell's limit (``bench/limits/<cell>.json``)
is set between the program's largest reading and the control's smallest.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--no-control", action="store_true")
    a = ap.parse_args()
    from bench.lib import harness
    for seed in (int(s) for s in a.seeds.split(",")):
        keep = {}
        t = time.time()
        res = harness.run(a.workload, seed, a.seconds, False, t_start=t,
                          keep=keep, control=not a.no_control)
        print(json.dumps({
            "seed": seed, "program": keep["check"]["value"],
            "control": keep["check"].get("control"),
            "tokens": keep["check"]["tokens"], "correct": res["correct"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "window": res["window"], "run_s": time.time() - t}),
            flush=True)


if __name__ == "__main__":
    main()
