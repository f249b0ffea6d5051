"""Find a cell's knee: the highest arrival rate of its open mix at which
the queue does not grow through the window. One run of the cell per rate
and seed, in one process, each at the given rate in place of the traffic
file's.

    python3 bench/sweep.py --workload <cell> --seconds <s> \\
        --rates 0.5,1,1.5 --seeds 1,2

Prints one JSON line per run: the rate, the seed, the end-to-end metrics,
the requests due in the window, and how many waited for a slot after each
tick (means over the window's first and last quarters, the most, at the
end). A queue that grows through the window is above the knee.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    from bench.lib import harness
    for rate in (float(r) for r in a.rates.split(",")):
        for seed in (int(s) for s in a.seeds.split(",")):
            t = time.time()
            res = harness.run(a.workload, seed, a.seconds, False,
                              t_start=t,
                              overrides={"traffic": {"rate": rate}})
            print(json.dumps({
                "rate": rate, "seed": seed, "correct": res["correct"],
                "failed": res["failed"],
                "metrics": {k: v["value"]
                            for k, v in res["metrics"].items()},
                "arrivals": res["window"]["arrivals"],
                "ticks": res["window"]["ticks"],
                "tokens": res["window"]["tokens"],
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "run_s": time.time() - t}), flush=True)


if __name__ == "__main__":
    main()
