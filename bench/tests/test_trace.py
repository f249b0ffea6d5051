"""The trace reduction against a small recorded trace: the first 30 ms of
a traced window of ``qwen2.5-3b.longctx_decode`` on one TPU v5 lite."""
import json
import os

import numpy as np
import pytest

from bench.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "data", "trace_longctx_30ms.json")) as f:
        return json.load(f)


def test_busy_share_against_a_brute_force_count(events):
    red = trace.reduce_events(events)
    t0, t1 = [e for e in events["host"] if e[0] == "bench.window"][0][1:]
    bins = np.zeros(int((t1 - t0) // 100) + 1, bool)       # 100 ns bins
    for rows in events["device"].values():
        for line, _, a, b in rows:
            if line == "XLA Ops" and b > t0 and a < t1:
                bins[int((max(a, t0) - t0) // 100):
                     int(np.ceil((min(b, t1) - t0) / 100))] = True
    assert red["window_s"] == pytest.approx(0.030, rel=1e-9)
    assert red["busy_s"] == pytest.approx(bins.sum() * 1e-7, abs=2e-6)
    assert 0 < red["busy_s"] <= red["window_s"]


def test_programs_and_innermost_ops(events):
    red = trace.reduce_events(events)
    assert list(red["modules"]) == ["jit_decode_step"]
    # the layer loop contains the kernel: only the kernel counts
    assert not any(":while" in k for k in red["ops"])
    secs, calls = trace.op_seconds(red, r"loki_decode_fused")
    assert calls == 3 and secs == pytest.approx(2.627169e-3, rel=1e-6)
    # the two pool copies lead the device time in this piece
    names = [k for k, _ in red["device_ops"][:2]]
    assert names == ["jit_decode_step:copy.35", "jit_decode_step:copy.34"]


def test_idle_gaps_are_labelled_by_the_host(events):
    red = trace.reduce_events(events)
    assert red["idle_gaps"] and all(
        label.startswith(("bench.", "outside")) for label, _ in
        red["idle_gaps"])
    widest = red["idle_gaps"][0][1]
    assert widest == pytest.approx(red["window_s"] - red["busy_s"]
                                   if len(red["idle_gaps"]) == 1 else widest)


def test_a_trace_without_its_window_or_device_is_refused(events):
    no_window = {**events, "host": [e for e in events["host"]
                                    if e[0] != "bench.window"]}
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_events(no_window)
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce_events({**events, "device": {}})


def test_op_names():
    assert trace.op_name("%copy.34 = bf16[36,2] copy(x)") == "copy.34"
    assert trace.op_name("fusion.3") == "fusion.3"
