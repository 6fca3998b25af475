"""A whole serving run, its look for a chip skipped, with the rows broken
where they are produced: ``correct`` has to come out false; and true for
the program as it is.  Also the control (the reference in bfloat16 in the
program's place), which the limit has to reject."""

import pytest

from bench.tests import tiny

CELL = "ou_gan.serve_poisson"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("serve_faults"))


def test_sound_serving_run_is_correct(root):
    r = tiny.measure(root, CELL)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert {"serve_p95_ms", "serve_p50_ms", "setup_s"} <= set(r["metrics"])
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["altered", "unchanged"])
def test_serving_fault_is_caught(root, fault):
    r = tiny.measure(root, CELL, variant=fault)
    assert not r["correct"], r["checks"]


def test_serving_control_fails(root):
    r = tiny.measure(root, CELL, variant="control")
    assert not r["correct"], r["checks"]
