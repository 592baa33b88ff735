"""The comparison that decides ``correct``, at a size a CPU test holds.

Runs every cell's tiny copy through the harness as the chip would run it
(minus the look for a chip) and checks that the program as configured
comes out correct, that the precision control does not, and that each
fault a training cell can have, planted in the timed path, turns
``correct`` false.
"""

import json

import pytest

from chipbench import faults, harness
from chipbench.tests import tiny

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
TRAIN = [w["name"] for w in BENCH["workloads"]
         if harness.Cell(BENCH, w["name"]).traffic["kind"] == "train"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tiny")
    return tmp, tiny.bench_copy(tmp)


@pytest.mark.parametrize("cell", TRAIN)
def test_program_as_configured_is_correct(copy, cell):
    run = tiny.run_cell(*copy, cell)
    assert run.correct, run.checks


@pytest.mark.parametrize("cell", TRAIN)
def test_train_control_is_not_correct(copy, cell):
    control = harness.Cell(copy[1], cell, copy[0], copy[0]).config[
        "control_policy"]
    run = tiny.run_cell(*copy, cell, program_policy=control)
    assert not run.correct, run.checks


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_train_fault_is_not_correct(copy, cell, fault):
    with faults.TRAIN[fault]():
        run = tiny.run_cell(*copy, cell)
    assert not run.correct, run.checks
