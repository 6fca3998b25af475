"""Serving subsystem tests (DESIGN.md §9).

The bucket-padding invariant (padding a request batch up to a compiled
bucket must not change the rows a client asked for), the train→serve
checkpoint handshake, the quarantined LM path, the streamed rollout's
chunk continuity, and the launch CLI on 1 and 2 (simulated) devices.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core.sde import (LatentSDEConfig, NeuralSDEConfig, generator_init,
                            generator_initial_state, latent_sde_init)
from repro.launch.steps import (make_sample_step, make_stream_chunk_step)

GAN_CFG = dict(data_dim=1, hidden_dim=8, noise_dim=4, width=16, num_steps=8)
LATENT_CFG = dict(data_dim=2, hidden_dim=8, context_dim=8, width=16,
                  num_steps=16)


def _sampler(workload, key, **kw):
    if workload == "sde-gan":
        cfg = NeuralSDEConfig(**GAN_CFG)
        params = generator_init(key, cfg)
    else:
        cfg = LatentSDEConfig(**LATENT_CFG)
        params = latent_sde_init(key, cfg)
    return cfg, params, jax.jit(make_sample_step(workload, cfg, **kw))


# -----------------------------------------------------------------------------
# bucket padding: the determinism invariant the AOT cache relies on
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("workload,kw", [
    ("sde-gan", {}),
    ("latent-sde", {}),
    ("latent-sde", dict(latent_mode="posterior", obs_len=9)),
])
def test_bucket_padding_preserves_unpadded_rows(key, workload, kw):
    """The same 3 request keys inside a 4-bucket and an 8-bucket produce
    bitwise-identical trajectories — every row is a pure function of its
    own key, so off-size batches pad up without perturbing real rows."""
    _, params, step = _sampler(workload, key, **kw)
    real = jax.random.split(jax.random.fold_in(key, 1), 3)
    out = {}
    for bucket in (4, 8):
        pad = jax.random.split(jax.random.fold_in(key, 2), bucket - 3)
        ys = step(params, jnp.concatenate([real, pad]))
        assert ys.shape[1] == bucket
        assert np.isfinite(np.asarray(ys)).all()
        out[bucket] = np.asarray(ys[:, :3])
    np.testing.assert_array_equal(out[4], out[8])


def test_sampler_rejects_bad_workload_and_grid(key):
    cfg = NeuralSDEConfig(**GAN_CFG)
    with pytest.raises(ValueError, match="workload"):
        make_sample_step("lm", cfg)
    lcfg = LatentSDEConfig(**LATENT_CFG)
    with pytest.raises(ValueError, match="latent_mode"):
        make_sample_step("latent-sde", lcfg, latent_mode="magic")
    with pytest.raises(ValueError, match="obs_len"):
        make_sample_step("latent-sde", lcfg, latent_mode="posterior")
    # posterior observation grid must align with the solver grid
    with pytest.raises(ValueError, match=r"num_steps \(16\).*T \(6"):
        make_sample_step("latent-sde", lcfg, latent_mode="posterior",
                         obs_len=7)


# -----------------------------------------------------------------------------
# streamed rollout: chunk continuity
# -----------------------------------------------------------------------------


def test_stream_chunks_are_continuous(key):
    """Chunk c's first emitted row equals chunk c-1's last — the carried
    hidden state stitches the stream into one trajectory.  One compiled
    program serves every chunk (t_start is traced)."""
    cfg = NeuralSDEConfig(**GAN_CFG)
    params = generator_init(key, cfg)
    chunks, steps_per = 4, cfg.num_steps // 4
    span = cfg.t1 / chunks
    chunk_fn = jax.jit(make_stream_chunk_step(cfg, span, steps_per))
    keys = jax.random.split(jax.random.fold_in(key, 1), 3)
    x = generator_initial_state(params, cfg, keys)
    prev_last = None
    for c in range(chunks):
        ckeys = jax.vmap(lambda k, c=c: jax.random.fold_in(k, 1000 + c))(keys)
        ys, x = chunk_fn(params, ckeys, x, jnp.asarray(c * span, cfg.dtype))
        assert ys.shape == (steps_per + 1, 3, cfg.data_dim)
        if prev_last is not None:
            np.testing.assert_allclose(np.asarray(ys[0]), prev_last,
                                       rtol=1e-6, atol=1e-6)
        prev_last = np.asarray(ys[-1])


# -----------------------------------------------------------------------------
# checkpoint handshake: train -> serve round trip, named failure modes
# -----------------------------------------------------------------------------


def test_checkpoint_roundtrip_train_to_serve(key, tmp_path):
    """train_sde_gan writes the serving bundle alongside its checkpoints;
    restore_for_serving rebuilds the config and restores bitwise-equal
    generator params, and the restored model samples finite trajectories."""
    from repro.launch.serve import restore_for_serving
    from repro.launch.train import train_sde_gan

    trained, _ = train_sde_gan(steps=2, batch=8, ckpt_dir=str(tmp_path),
                               ckpt_every=1, num_steps=8, seq_len=9,
                               log_every=100)
    params, cfg, step = restore_for_serving("sde-gan", str(tmp_path))
    assert step == 2
    assert cfg.num_steps == 8
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(trained["gen"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ys = jax.jit(make_sample_step("sde-gan", cfg))(
        params, jax.random.split(key, 2))
    assert np.isfinite(np.asarray(ys)).all()


def test_serving_handshake_named_errors(key, tmp_path):
    from repro.launch.serve import restore_for_serving

    # no bundle at all -> a named pointer at train.py / --smoke
    with pytest.raises(FileNotFoundError, match="serving bundle"):
        ckpt.load_serving_meta(tmp_path)
    # bundle for the other workload -> named mismatch, not a pytree error
    cfg = LatentSDEConfig(**LATENT_CFG)
    ckpt.save_serving_bundle(tmp_path, 3, latent_sde_init(key, cfg),
                             "latent-sde", cfg)
    with pytest.raises(ValueError, match="workload"):
        restore_for_serving("sde-gan", str(tmp_path))
    # the happy path restores the config dataclass, dtype included
    params, cfg2, step = restore_for_serving("latent-sde", str(tmp_path))
    assert step == 3 and cfg2.num_steps == cfg.num_steps
    assert jnp.dtype(cfg2.dtype) == jnp.dtype(cfg.dtype)


# -----------------------------------------------------------------------------
# the quarantined LM path
# -----------------------------------------------------------------------------


def test_sde_serving_never_imports_transformer_stack():
    """`--workload sde-gan` must not touch repro.models (the seed scaffold's
    LM decode loop lives behind --workload lm only)."""
    from repro.launch import serve

    for m in [m for m in sys.modules if m.startswith("repro.models")]:
        del sys.modules[m]
    serve.main(["--workload", "sde-gan", "--smoke", "--requests", "2",
                "--max-batch", "2", "--sde-steps", "8"])
    assert not any(m.startswith("repro.models") for m in sys.modules)


# -----------------------------------------------------------------------------
# the launch CLI, 1 and 2 (simulated) devices
# -----------------------------------------------------------------------------


def _run_serve_cli(extra_args=(), extra_env=None):
    repo = Path(__file__).resolve().parents[1]
    # pin XLA_FLAGS: the subprocess sees exactly the devices its own
    # --host-devices flag asks for, whatever the caller's environment sets
    env = dict(os.environ, PYTHONPATH=str(repo / "src"), XLA_FLAGS="")
    env.update(extra_env or {})
    cmd = [sys.executable, "-m", "repro.launch.serve", "--smoke",
           "--requests", "6", "--max-batch", "4", "--sde-steps", "8",
           *extra_args]
    return subprocess.run(cmd, cwd=repo, env=env, capture_output=True,
                          text=True, timeout=600)


def test_serve_cli_single_device():
    r = _run_serve_cli(["--workload", "sde-gan"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "traj/s" in r.stdout
    assert "latency p50" in r.stdout


def test_serve_cli_two_simulated_devices():
    r = _run_serve_cli(["--workload", "sde-gan", "--host-devices", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "data-parallel over 2 devices" in r.stdout
    assert "traj/s" in r.stdout


def test_serve_cli_latent_and_stream():
    r = _run_serve_cli(["--workload", "latent-sde", "--sde-steps", "16"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "traj/s" in r.stdout
    r = _run_serve_cli(["--workload", "sde-gan", "--stream-chunks", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "first-chunk latency" in r.stdout


def test_serve_cli_scheduler_modes():
    """--scheduler runs the continuous-batching path (and its fifo
    baseline) through the same CLI; both report the usual latency lines
    plus the scheduler's pool summary."""
    for mode in ("continuous", "fifo"):
        r = _run_serve_cli(["--workload", "sde-gan", "--scheduler", mode])
        assert r.returncode == 0, r.stderr[-2000:]
        assert f"scheduler-{mode}" in r.stdout
        assert "traj/s" in r.stdout
        assert "latency p50" in r.stdout
        assert "admission at chunk boundaries" in r.stdout


def test_serve_cli_scheduler_two_simulated_devices():
    """The scheduler's re-stacked batch operands must agree with the AOT
    input shardings under a data-parallel mesh (Scheduler._put pins both
    sides)."""
    r = _run_serve_cli(["--workload", "sde-gan", "--scheduler", "continuous",
                        "--host-devices", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "data-parallel over 2 devices" in r.stdout
    assert "scheduler-continuous" in r.stdout
    assert "traj/s" in r.stdout


def test_serve_cli_async_preempt_pool_budget():
    """The PR 10 scheduler extras through the CLI: --async-front drives
    the asyncio ingestion path, --preempt and --pool-budget-mb thread to
    the scheduler/registry (a generous budget evicts nothing but prints
    its accounting), and all three are rejected by name without
    --scheduler."""
    r = _run_serve_cli(["--workload", "sde-gan", "--scheduler", "continuous",
                        "--async-front", "--preempt",
                        "--pool-budget-mb", "4096"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "scheduler-continuous" in r.stdout
    assert "pool budget 4096 MB" in r.stdout
    assert "0 evictions" in r.stdout
    assert "latency p50" in r.stdout
    r = _run_serve_cli(["--workload", "sde-gan", "--async-front"])
    assert r.returncode != 0
    assert "--scheduler" in r.stderr


def test_serve_cli_adaptive_per_request_tolerance():
    """--adaptive terminal sampling: several distinct request tolerances
    must be served by exactly one compiled program per bucket (rtol is
    traced, never a cache key), and the latent workload is rejected by
    name (no fixed output grid to serve)."""
    r = _run_serve_cli(["--workload", "sde-gan", "--adaptive"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "traj/s" in r.stdout
    assert "distinct tolerances" in r.stdout
    assert "no recompiles" in r.stdout
    r = _run_serve_cli(["--workload", "latent-sde", "--adaptive"])
    assert r.returncode != 0
    assert "terminal samples" in r.stderr
