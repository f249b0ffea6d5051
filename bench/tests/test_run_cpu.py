"""Whole runs at a tiny size on the CPU, through the same files as a chip
run: the harness's look for a chip is skipped, the rest is driven, for
each traffic mix of ``tiny.SIZES``. A run whose timed path is broken must
read not correct."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import tiny
from bench.lib import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CASES = sorted(tiny.SIZES)
KEYS = ("correct", "attempted", "failed", "metrics", "device", "checks")


@pytest.mark.parametrize("case", CASES)
def test_sound_run_is_correct(case):
    # all blocks selected: exact attention, so no selection near-tie can
    # flip between the bf16 engine and the float32 reference
    res = tiny.run(case, 2 ** 31 + 17, config={"serving": {"k_f": 1.0}})
    assert tuple(res)[:5] == KEYS[:5] and list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    cell = spec.load_cell(tiny.CELLS[case])
    assert {m["name"] for m in cell.metrics(False)} == set(res["metrics"])
    json.dumps(res)


@pytest.mark.parametrize("case", CASES)
def test_token_altered_where_produced_is_caught(case, monkeypatch):
    from repro.serving import scheduler
    real = scheduler.sample_next
    calls = []

    def altered(logits, **kw):
        calls.append(1)
        nxt = real(logits, **kw)
        # every third decode step after warm-up serves, in every row, a
        # token other than the one the logits pick
        bad = len(calls) > 20 and len(calls) % 3 == 0
        return (nxt + 1) % logits.shape[-1] if bad else nxt

    monkeypatch.setattr(scheduler, "sample_next", altered)
    res = tiny.run(case, 5, seconds=3.0)
    assert len(calls) > 30
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("case", CASES)
def test_step_that_keeps_its_state_is_caught(case, monkeypatch):
    from repro.models import lm
    real = lm.decode_step

    def stale(params, cfg, cache, *a, **kw):
        logits, new = real(params, cfg, cache, *a, **kw)
        return logits, cache          # the step returns its cache unchanged

    monkeypatch.setattr(lm, "decode_step", stale)
    res = tiny.run(case, 6)
    assert not res["correct"], res["checks"]


def test_no_chip_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", tiny.CELL, "--seed",
         "1", "--seconds", "1"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
    assert "TPU" in out.stderr


def test_bench_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".out",
                                                  "__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", tiny.CELL, "--seed",
         "1", "--seconds", "1"], cwd=tmp_path, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
