"""Runs of the benchmark at a size the CPU runs in seconds: two layers of
256 wide, heads of the published 128, a vocabulary of 4096, a short
context. The group is one query head per KV head, so the jnp route the
CPU serves (per-head selection) and the Pallas kernel's group selection
agree.

Each case is a traffic mix of ``bench/traffic`` at tiny sizes, served by
the configuration of its cell (``CELLS``)."""
import json
import os
import time

from bench.lib import harness, spec

CELLS = {"longctx_decode": "qwen2.5-3b.longctx_decode",
         "chat_poisson": "qwen2.5-14b-pp4.chat_poisson"}
CELL = CELLS["longctx_decode"]
CONFIG = {"num_hidden_layers": 2, "hidden_size": 256,
          "num_attention_heads": 2, "num_key_value_heads": 2,
          "intermediate_size": 512, "vocab_size": 4096,
          "serving": {"backend": "xla"}, "calibration": {"length": 256}}
SIZES = {
    "longctx_decode": {
        "sessions": 3, "prompt_len": {"min": 300, "max": 600},
        "engine": {"n_slots": 3, "smax": 1024}},
    "chat_poisson": {
        "rate": 5.0, "warm_seconds": 1.0,
        "prompt_len": {"median": 200, "min": 32, "max": 600},
        "max_new": {"median": 24, "min": 8, "max": 64},
        "engine": {"n_slots": 4, "smax": 1024}},
}


def mix(case):
    """The traffic file of ``case`` at its tiny sizes."""
    with open(os.path.join(spec.BENCH, "traffic", case + ".json")) as f:
        return harness._merge(json.load(f), SIZES[case])


def run(case, seed, seconds=2.0, config=None, traffic=None, **kw):
    """One run of ``case`` at the tiny size, on whatever JAX finds;
    ``kw`` goes to ``harness.run`` (``keep``, ``control``)."""
    over = {"config": harness._merge(CONFIG, config),
            "traffic": harness._merge(mix(case), traffic)}
    return harness.run(CELLS[case], seed, seconds, False,
                       t_start=time.time(), require_chip=False,
                       overrides=over, **kw)
