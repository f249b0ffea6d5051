"""The one traffic generator: turns a traffic file's parameters and a seed
into the requests of a run.

Every seed of a mix gets the same multiset of sizes, drawn at fixed
quantiles of the file's distributions; the seed only orders them and
draws the token ids. So two seeds ask for the same work in another order,
and a run's spread is the system's, not the traffic's.

Kinds:
  sessions  ``sessions`` prompts, all due at set-up (a closed loop: the
            window decodes them together); ``max_new: "fill"`` lets each
            run to the cell's context cap.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the plan."""
    rid: int
    prompt: np.ndarray
    max_new: int


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the mid-quantiles (i + 0.5) / n of a
    ``uniform`` length distribution over [min, max]."""
    if spec["dist"] != "uniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    u = (np.arange(n) + 0.5) / n
    x = spec["min"] + u * (spec["max"] - spec["min"])
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def plan(traffic: dict, seed: int, vocab: int) -> List[Planned]:
    """The requests a run sends, from ``seed``."""
    rng = np.random.Generator(np.random.PCG64(seed))
    smax = traffic["engine"]["smax"]
    if traffic["kind"] != "sessions":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    n = traffic["sessions"]
    lens = rng.permutation(quantiles(traffic["prompt_len"], n))
    out = []
    for i, ln in enumerate(lens):
        max_new = (smax - int(ln) if traffic["max_new"] == "fill"
                   else int(traffic["max_new"]))
        out.append(Planned(i, rng.integers(0, vocab, int(ln)).astype(
            np.int32), max_new))
    return out
