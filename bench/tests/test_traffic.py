import json
import os

import numpy as np
import pytest

from bench.lib import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def mix(name):
    with open(os.path.join(ROOT, "bench/traffic", name + ".json")) as f:
        return json.load(f)


def test_sessions_same_sizes_any_seed():
    tr = mix("longctx_decode")
    a = traffic.plan(tr, 7, 151936)
    b = traffic.plan(tr, 2 ** 31 + 12345, 151936)
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert all(4096 <= len(p.prompt) <= 7168 for p in a)
    assert all(len(p.prompt) + p.max_new == tr["engine"]["smax"] for p in a)
    again = traffic.plan(tr, 7, 151936)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
    assert not np.array_equal(a[0].prompt[:64], b[0].prompt[:64])


def test_unknown_kind_and_distribution_raise():
    tr = mix("longctx_decode")
    with pytest.raises(ValueError):
        traffic.plan({**tr, "kind": "bursts"}, 1, 1000)
    with pytest.raises(ValueError):
        traffic.plan({**tr, "prompt_len": {"dist": "zipf", "min": 1,
                                           "max": 2}}, 1, 1000)
