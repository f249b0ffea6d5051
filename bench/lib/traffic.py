"""The one traffic generator: turns a traffic file's parameters and a seed
into the requests of a run.

Every seed of a mix gets the same multiset of sizes, drawn at fixed
quantiles of the file's distributions; the seed only orders them and
draws the token ids. So two seeds ask for the same work, and a run's
spread is the system's, not the traffic's. An open mix orders its sizes
and gaps once, from its file's ``schedule_seed``: in a window of a few
tens of requests the order alone moves the quantiles of time to first
token by tens of percent, so there the seed draws the token ids alone.

Kinds:
  sessions  ``sessions`` prompts, all due at set-up (a closed loop: the
            window decodes them together); ``max_new: "fill"`` lets each
            run to the cell's context cap.
  open      an open loop: requests due at ``rate`` per second, the gaps
            between them at the quantiles of the exponential distribution
            (Poisson arrivals), prompt lengths and answer lengths
            (``max_new``) each from its own distribution. Two blocks, each
            its own multiset: ``warm_seconds`` of arrivals before the
            window, then the window's ``seconds``; ``due`` is seconds
            after the arrivals start, and the window opens at
            ``warm_seconds``.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the plan."""
    rid: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0        # seconds into the arrival schedule (open)


def _mid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the mid-quantiles (i + 0.5) / n of a length
    distribution, clipped to [min, max]: ``uniform`` over [min, max], or
    ``lognormal`` with its ``median`` and ``sigma`` (of the log)."""
    u = _mid(n)
    if spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps between Poisson arrivals at ``rate`` per second: the
    exponential distribution at the mid-quantiles (i + 0.5) / n."""
    return -np.log1p(-_mid(n)) / rate


def plan(traffic: dict, seed: int, vocab: int,
         seconds: Optional[float] = None) -> List[Planned]:
    """The requests a run sends, from ``seed``; an open mix plans for a
    window of ``seconds``."""
    rng = np.random.Generator(np.random.PCG64(seed))
    smax = traffic["engine"]["smax"]
    if traffic["kind"] == "sessions":
        n = traffic["sessions"]
        lens = rng.permutation(quantiles(traffic["prompt_len"], n))
        out = []
        for i, ln in enumerate(lens):
            max_new = (smax - int(ln) if traffic["max_new"] == "fill"
                       else int(traffic["max_new"]))
            out.append(Planned(i, rng.integers(0, vocab, int(ln)).astype(
                np.int32), max_new))
        return out
    if traffic["kind"] != "open":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    if seconds is None:
        raise ValueError("an open mix plans for a window of given seconds")
    warm = float(traffic["warm_seconds"])
    order = np.random.Generator(np.random.PCG64(traffic["schedule_seed"]))
    out: List[Planned] = []
    for t0, span in ((0.0, warm), (warm, float(seconds))):
        lens, news, due = _block(traffic, order, span)
        if int((lens + news).max()) > smax:
            raise ValueError(f"a request of the mix exceeds smax {smax}")
        out += [Planned(len(out) + i, rng.integers(0, vocab, int(ln)).astype(
            np.int32), int(mn), t0 + float(t))
            for i, (ln, mn, t) in enumerate(zip(lens, news, due))]
    return out


def _block(traffic: dict, order, span: float):
    """Prompt lengths, answer lengths and due times (from 0) of the
    ``round(rate * span)`` requests of ``span`` seconds of an open mix,
    each multiset in the order ``order`` draws. The gaps are scaled to
    sum to ``span``; the first request is due at 0."""
    n = max(round(float(traffic["rate"]) * span), 1)
    g = gaps(float(traffic["rate"]), n)
    g = order.permutation(g * (span / g.sum()))
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return (order.permutation(quantiles(traffic["prompt_len"], n)),
            order.permutation(quantiles(traffic["max_new"], n)), due)
