"""Shared set-up of the benchmark's own tests (run on the CPU):

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


@pytest.fixture(scope="session")
def tiny_cell():
    """The even configuration at a size a test run holds: 400 pairs of
    2 genomes of 3-4 kbp (~17x), every guarantee and limit as stated."""
    import run

    cell = run.load_cell("phage_cami.s3000", ROOT)
    cell = copy.deepcopy(cell)
    cell["config"]["community"].update(genome_len=[3000, 4000],
                                       pairs_per_genome=200)
    cell["traffic"] = {"pairs_per_sample": 400}
    return cell
