"""The control of the output check, at a size a test run holds: the plain
reference computed in float8 e4m3 (the precision below the configuration's
bfloat16), put in the program's place, has to read above the cell's limit.
On the chip the same control ran at the cell's own size
(``bench/readings.py``; readings in PERF.md)."""
import pytest

import tiny
from bench.lib import harness, spec


@pytest.mark.parametrize("case", sorted(tiny.SIZES))
def test_float8_control_fails_the_limit(case):
    limit = spec.load_cell(tiny.CELLS[case]).limits[harness.CHECK]["limit"]
    keep = {}
    # every served request is compared: a tiny model's logits spread less
    # than the cell's, so the control needs as many positions as it gets
    tiny.run(case, 2 ** 32 + 5, 6.0, traffic={"check": {"requests": 20}},
             keep=keep, control=True)
    check = keep["check"]
    assert check["tokens"] > 300
    assert check["control"] > limit, check
