"""The comparison that decides `correct`: a sound run passes, the control
and every planted fault fail.

Drives the rest of a run (`run.run_cell`: set-up, a window of one
assembly, the reference check) on the CPU, skipping the harness's look for
a chip, at a size a test run holds.
"""
import pytest

import run
from lib import plants

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def run_once(cell, seed):
    return run.run_cell(cell, seed, 1e-3, False, DEVICE, {})


def failing(line):
    return sorted(k for k, c in line["checks"].items()
                  if not (c["value"] <= c["limit"] if c["rule"] == "<="
                          else c["value"] >= c["limit"]))


def test_sound_run_is_correct(tiny_cell):
    line = run_once(tiny_cell, 2**31 + 11)
    assert line["correct"], failing(line)
    assert line["attempted"] == 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"read_bases_per_s",
                                    "genome_fraction_10x", "setup_s"}
    assert list(line)[-1] == "checks"


def test_control_is_not_correct(tiny_cell):
    with plants.skip_verify():
        line = run_once(tiny_cell, 2**31 + 11)
    assert not line["correct"]
    assert "alignment_errors" in failing(line)


@pytest.mark.parametrize("fault", sorted(plants.FAULTS))
def test_planted_fault_is_not_correct(tiny_cell, fault):
    with plants.FAULTS[fault]():
        line = run_once(tiny_cell, 2**31 + 11)
    assert not line["correct"], line["checks"]
    assert line["failed"] == line["attempted"] == 1
