"""A whole run, its look for a chip skipped, with the timed path broken
underneath: ``correct`` has to come out false for every fault a cell can
have, and true for the program as it is.  Also the control, the reference
at three bfloat16 passes in the program's place, which the limits have to
reject."""

import pytest

from bench.tests import tiny

TRAIN = ["airq_latent.train_b1024", "ou_gan.train_b1024"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("faults"))


@pytest.mark.parametrize("cell", TRAIN)
def test_sound_run_is_correct(root, cell):
    r = tiny.measure(root, cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_training_fault_is_caught(root, cell, fault):
    r = tiny.measure(root, cell, variant=fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", TRAIN)
def test_control_fails(root, cell):
    r = tiny.measure(root, cell, variant="control")
    assert not r["correct"], r["checks"]
