"""Each traffic mix rehearsed for a second at a tiny size on the CPU,
through the harness's internal entry: the run completes, checks what the
timed path produced against the reference, and reports the cell's
end-to-end metrics. The cells that only test fixtures define (a field
with a density and a colour MLP) run through the same harness."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_small import (FIXTURE_CELLS, assert_sound, harness,  # noqa: E402
                         rehearse, small_cell)

SPEC = harness.load_spec()
ONE_CHIP = ([w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
            + sorted(FIXTURE_CELLS))


@pytest.mark.parametrize("name", ONE_CHIP)
def test_rehearse_one_chip_cell(name):
    cell = small_cell(name)
    assert_sound(rehearse(cell), cell)
