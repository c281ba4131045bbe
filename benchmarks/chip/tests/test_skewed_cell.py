"""The skewed configuration at a size a test run holds: a sound run is
correct, the control is not."""
import copy
import os

import pytest

from conftest import BENCH, ROOT

import run
from lib import plants

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def tiny_skewed():
    """The skewed configuration (`configs/mgsim_phage_skewed.json`, kept
    out of `BENCHMARK.json` until its sound runs hold its limits), with
    six genomes of 1.5-3 kbp at sigma 2 in 800 pairs (~18x over them),
    every guarantee and limit as stated."""
    cell = copy.deepcopy(run.load_cell("phage_cami.s3000", ROOT))
    cell["config"] = run.load_json(os.path.join(
        BENCH, "configs", "mgsim_phage_skewed.json"))
    cell["config"]["community"].update(genome_len=[1500, 3000],
                                       pairs_per_genome=133)
    cell["traffic"] = {"pairs_per_sample": 800}
    return cell


def test_sound_run_of_the_skewed_cell_is_correct(tiny_skewed):
    line = run.run_cell(tiny_skewed, 2**31 + 13, 1e-3, False, DEVICE, {})
    assert line["correct"], line["checks"]
    assert line["attempted"] == 1 and line["failed"] == 0


def test_control_of_the_skewed_cell_is_not_correct(tiny_skewed):
    with plants.skip_verify():
        line = run.run_cell(tiny_skewed, 2**31 + 13, 1e-3, False, DEVICE,
                            {})
    assert not line["correct"]
    assert line["checks"]["alignment_errors"]["value"] > 0
