"""The ``latent_sde_air`` configuration and its cell on the CPU: at a few
units and rows, but on the cell's own grid of 24 observations and 23
solver steps, so the posterior drift reads the encoder's context at every
row by float time.  The program against the plain reference, a whole run
through the harness, and the faults and the control its limits reject."""

import json

import jax
import numpy as np
import pytest

from bench import compare
from bench.layout import Layout
from bench.tests import tiny

CONFIG, CELL = "latent_sde_air", "latent_sde_air.train_b1024"
#: The cut: widths and rows only; ``num_steps`` and ``seq_len`` stay.
CUT = {"hidden_dim": 2, "context_dim": 3, "initial_noise_dim": 2, "width": 4}
BATCH = 8
#: Between what the program and the control read at this size on the CPU
#: (seeds ``2**31 + 5``, 7, 11, 123456789, 3000001601): the program reads
#: loss1 / grad1 / dparam3_median at most 8.3e-8 / 3.8e-7 / 6.1e-6; the
#: control (three bf16 passes) grad1 3.8e-6 to 1.7e-5; half the batch
#: loss1 0.080 to 0.15 and dparam3_median 0.054 to 0.13.
LIMITS = {"loss1": 1e-5, "grad1": 1.2e-6, "dparam3_median": 1e-3}
#: The metrics ``BENCHMARK.json`` reports in the cell.
METRICS = {"train_paths_per_s", "setup_s", "train_mfu",
           "kernel_roofline.train", "idle_share.train"}


def _edit(path, change):
    data = json.loads(path.read_text())
    change(data)
    path.write_text(json.dumps(data))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.checkout(tmp_path_factory.mktemp("latent_sde_air"))
    bench = root / "bench"
    _edit(bench / "configs" / f"{CONFIG}.json",
          lambda d: d["model"].update(CUT))
    _edit(bench / "workloads" / f"{CELL}.json",
          lambda d: (d["params"].update(batch=BATCH),
                     d["limits"].update(LIMITS)))
    return root


def test_every_piece_the_entries_name_is_found():
    layout = Layout(tiny.REPO)
    entry = next(c for c in layout.spec["configs"] if c["name"] == CONFIG)
    config = layout.config(CONFIG)
    assert (tiny.REPO / entry["file"]).is_file()
    assert config["name"] == CONFIG and config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] == []
    layout.reference(CONFIG)
    layout.model(config["kind"])
    layout.flops(config["kind"])
    cell = layout.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    layout.driver(cell["driver"])
    assert set(cell["limits"]) == set(LIMITS)
    assert cell["params"]["seq_len"] - 1 == config["model"]["num_steps"]
    named = ({m["name"] for m in layout.end_to_end(CELL)}
             | {m["name"] for m in layout.per_layer(CELL)})
    assert named == METRICS
    for m in layout.per_layer(CELL):
        layout.reader(m["name"])


def test_reference_data_and_weights_are_the_programs(root):
    from repro.core.sde import LatentSDEConfig, latent_sde_init
    from repro.data.synthetic import air_quality_like

    layout = Layout(root)
    ref = layout.reference(CONFIG)
    config = layout.config(CONFIG)
    k = jax.random.PRNGKey(2**31 + 9)
    assert np.array_equal(ref.data(k, 16, 24), air_quality_like(k, 16, 24)[0])
    mine = ref.init(k, config)
    theirs = latent_sde_init(k, LatentSDEConfig(**config["model"]))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)))


def test_two_steps_match_the_reference(root):
    """Seeded random weights, two ELBO steps through the cell's own
    program (``make_latent_sde_step``, exact adjoint, fused kernels' jnp
    oracles): the losses, the first gradient and every leaf after the
    second step agree.

    Adam's second step divides by the gradient's own size, so a gradient
    element far smaller than its leaf's others moves by its relative
    round-off: on the seed ``2**31 + 1`` the encoder's ``h0`` (one element
    at 1% of the leaf's others; first gradients equal to 6.5e-13) reads a
    leaf gap of 1.2e-5 after two steps.  The first-gradient check holds
    on every seed."""
    layout = Layout(root)
    c = layout.cell(CELL)
    config = layout.config(CONFIG)
    ref = layout.reference(CONFIG)
    prog = layout.model(config["kind"]).train_program(config, c["params"],
                                                      "highest")
    key = jax.random.PRNGKey(2**31 + 2)
    params = ref.init(jax.random.fold_in(key, 0), config)
    keys = [jax.random.fold_in(key, 1 + i) for i in range(2)]
    state = prog.state(params)
    losses = []
    for k in keys:
        state, loss = prog.step(state, k)
        losses.append(loss)
        if len(losses) == 1:
            grads = compare.leaf_norms(prog.first_grads(state))
    with jax.default_matmul_precision("highest"):
        want = ref.train(params, config, c["params"], keys, BATCH)
    assert compare.loss_gap(losses, want["losses"]) < 1e-5
    gap, leaf = compare.worst_leaf_gap(grads, compare.leaf_norms(want["grads"]))
    assert gap < 1e-6, leaf
    gap, leaf = compare.worst_leaf_gap(compare.leaf_norms(prog.params(state)),
                                       compare.leaf_norms(want["params"]))
    assert gap < 1e-5, leaf


def test_sound_run_is_correct(root):
    r = tiny.measure(root, CELL)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("variant", ["unchanged", "half_batch", "control"])
def test_fault_and_control_fail(root, variant):
    r = tiny.measure(root, CELL, variant=variant)
    assert not r["correct"], r["checks"]
    if variant == "half_batch":
        assert r["checks"]["loss1"]["value"] > LIMITS["loss1"], r["checks"]
