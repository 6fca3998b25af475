"""Optimizer + schedule property tests (hypothesis where it pays)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest  # noqa: F401

from _hypothesis_compat import given, settings, st

from repro import optim
from repro.core.clipping import clip_mlp


def test_adam_bias_correction_first_step(key):
    """After one step from zero state, Adam's update is -lr·sign-ish of g
    (bias correction makes m̂ = g exactly)."""
    oi, ou = optim.adam(lr=1e-2, eps=0.0)
    p = {"w": jax.random.normal(key, (16,))}
    g = {"w": jax.random.normal(jax.random.fold_in(key, 1), (16,))}
    upd, _ = ou(g, oi(p), p)
    want = -1e-2 * np.sign(np.asarray(g["w"]))
    np.testing.assert_allclose(np.asarray(upd["w"]), want, rtol=1e-5)


def test_adam_moment_dtype_override(key):
    oi, _ = optim.adam(1e-3, moment_dtype="bfloat16")
    p = {"w": jnp.zeros((8,), jnp.bfloat16)}
    st_ = oi(p)
    assert st_.m["w"].dtype == jnp.bfloat16
    assert st_.v["w"].dtype == jnp.bfloat16


@given(st.floats(0.1, 10.0), st.integers(1, 64))
@settings(max_examples=20, deadline=None)
def test_clip_by_global_norm_bound(max_norm, size):
    g = {"a": jnp.ones((size,)) * 3.0, "b": jnp.full((2,), -4.0)}
    clipped, gnorm = optim.clip_by_global_norm(g, max_norm)
    new_norm = float(jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(clipped))))
    assert new_norm <= max_norm * (1 + 1e-4) or new_norm <= float(gnorm) + 1e-4


def test_cosine_schedule_shape():
    lr = optim.cosine_schedule(1.0, warmup=10, total=100, floor=0.1)
    assert float(lr(jnp.int32(0))) == 0.0
    assert abs(float(lr(jnp.int32(10))) - 1.0) < 0.11      # end of warmup
    assert float(lr(jnp.int32(100))) >= 0.1 - 1e-6          # floor
    assert float(lr(jnp.int32(50))) < float(lr(jnp.int32(12)))  # decays


def test_swa_is_running_mean(key):
    ps = [{"w": jnp.full((3,), float(i))} for i in range(5)]
    avg = ps[0]
    for n, p in enumerate(ps[1:], start=1):
        avg = optim.swa_update(avg, p, n)
    np.testing.assert_allclose(np.asarray(avg["w"]), np.full(3, 2.0), rtol=1e-6)


@given(st.floats(0.5, 100.0))
@settings(max_examples=20, deadline=None)
def test_clipping_idempotent(scale):
    """clip(clip(W)) == clip(W) — projection property (paper §5)."""
    key = jax.random.PRNGKey(0)
    p = {"layers": [{"w": jax.random.normal(key, (8, 4)) * scale,
                     "b": jnp.ones((4,))}]}
    c1 = clip_mlp(p)
    c2 = clip_mlp(c1)
    np.testing.assert_array_equal(np.asarray(c1["layers"][0]["w"]),
                                  np.asarray(c2["layers"][0]["w"]))
    bound = 1.0 / 8
    assert float(jnp.max(jnp.abs(c1["layers"][0]["w"]))) <= bound + 1e-9


def test_adadelta_updates_move_params(key):
    oi, ou = optim.adadelta(lr=1.0)
    p = {"w": jax.random.normal(key, (8,))}
    g = {"w": jnp.ones((8,))}
    state = oi(p)
    upd, state = ou(g, state, p)
    assert float(jnp.max(jnp.abs(upd["w"]))) > 0.0
    assert np.all(np.asarray(upd["w"]) < 0)   # descent direction


# -----------------------------------------------------------------------------
# flat optimiser state (optim.flatten): the SDE-GAN's Adadelta
# -----------------------------------------------------------------------------


def _gan_disc(key):
    from repro.core.sde import NeuralSDEConfig, discriminator_init

    return discriminator_init(key, NeuralSDEConfig(num_steps=4))


def _per_leaf_and_flat(chained: bool):
    from repro.core.clipping import clip_lipschitz

    def build(adadelta):
        if not chained:
            return adadelta
        return optim.chain(adadelta,
                           optim.lipschitz_projection(clip_lipschitz))

    return (build(optim.adadelta(lr=1.0)),
            build(optim.flatten(optim.adadelta(lr=1.0))))


def _moments(state, chained: bool):
    s = state[0] if chained else state
    return s.step, s.m, s.v


@pytest.mark.parametrize("chained", [False, True], ids=["alone", "projected"])
def test_flat_adadelta_bitwise_equals_per_leaf(key, chained):
    """Five steps of the flat Adadelta give the per-leaf updates, moments
    and step counter bit for bit, alone and with the Lipschitz projection
    chained after it."""
    (ri, ru), (fi, fu) = _per_leaf_and_flat(chained)
    params = _gan_disc(key)
    p_ref, s_ref = params, ri(params)
    p_flat, s_flat = params, fi(params)
    for i in range(5):
        grads = jax.tree.map(
            lambda x, k=jax.random.fold_in(key, i): jax.random.normal(
                k, x.shape, x.dtype), params)
        u_ref, s_ref = jax.jit(ru)(grads, s_ref, p_ref)
        u_flat, s_flat = jax.jit(fu)(grads, s_flat, p_flat)
        p_ref = optim.apply_updates(p_ref, u_ref)
        p_flat = optim.apply_updates(p_flat, u_flat)
        got = (u_flat, p_flat, _moments(s_flat, chained))
        want = (u_ref, p_ref, _moments(s_ref, chained))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"step {i}")


def test_flat_state_is_three_arrays_read_back_as_trees(key):
    """The state is three device arrays; ``.m`` and ``.v`` read back as
    trees of the parameters' structure, shapes and dtypes."""
    oi, ou = optim.flatten(optim.adadelta(lr=1.0))
    params = _gan_disc(key)
    grads = jax.tree.map(jnp.ones_like, params)
    _, state = ou(grads, oi(params), params)
    size = sum(x.size for x in jax.tree.leaves(params))
    assert [x.shape for x in jax.tree.leaves(state)] == [(), (size,), (size,)]
    for tree in (state.m, state.v):
        assert jax.tree.structure(tree) == jax.tree.structure(params)
        for a, p in zip(jax.tree.leaves(tree), jax.tree.leaves(params)):
            assert (a.shape, a.dtype) == (p.shape, p.dtype)
    # Adadelta's first squared-gradient accumulator: (1 - rho) * g**2
    for a in jax.tree.leaves(state.m):
        np.testing.assert_allclose(np.asarray(a), 0.1, rtol=1e-6)
    names = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(state)[0]]
    assert names == [".step", ".m_flat", ".v_flat"]


def test_gan_state_round_trips_through_checkpoint(key, tmp_path):
    """A GAN trainer's ``(params, g_state, d_state)`` saves and restores
    bitwise, flat optimiser states included, under stable leaf names."""
    from repro import checkpoint as ckpt
    from repro.core.sde import NeuralSDEConfig, generator_init
    from repro.launch.steps import make_gan_optimizers

    (gi, gu), (di, du) = make_gan_optimizers(lr=1.0, constraint="clip")
    params = {"gen": generator_init(key, NeuralSDEConfig(num_steps=4)),
              "disc": _gan_disc(jax.random.fold_in(key, 1))}
    grads = jax.tree.map(jnp.ones_like, params)
    _, g_state = gu(grads["gen"], gi(params["gen"]), params["gen"])
    _, d_state = du(grads["disc"], di(params["disc"]), params["disc"])
    state = (params, g_state, d_state)
    ckpt.save_checkpoint(tmp_path, 7, state)
    fresh = (params, gi(params["gen"]), di(params["disc"]))
    restored, step = ckpt.restore_checkpoint(tmp_path, fresh)
    assert step == 7
    assert jax.tree.structure(restored) == jax.tree.structure(state)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    names = {jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(state)[0]}
    assert {"[1].m_flat", "[2][0].v_flat", "[2][0].step"} <= names
