"""Kernel-parity differential suite: every Pallas kernel in the fused
reversible-Heun adjoint pipeline, interpret mode vs the jnp oracle
(:mod:`repro.kernels.ref`), asserted BITWISE.

This is the gate the fused exact adjoint rests on (DESIGN.md §3): the
backward kernels are registered as *the* derivative of the forward step
through ``custom_vjp``, so "fused gradient == unfused gradient" reduces to
per-kernel bit-equality, which is what these tests pin.

Methodology (the three rules that make bitwise comparison meaningful —
each was found the hard way, see the module docstring of
:mod:`repro.kernels.reversible_heun_step`):

1. **jit both sides.** An un-jitted pallas interpret call executes with
   different FMA-contraction choices than a jit'd jnp graph; the public
   kernel wrappers are jit'd, so the oracle side must be too.
2. **Trace every scalar.** A constant-folded ``dt`` contracts differently
   than a traced one — ``dt`` (and ``t``) are passed as jit *arguments* on
   both sides, never closed over as Python floats.
3. **Whole-array blocks under interpret.** Multi-cell interpreter grids
   compile each block as a separate subcomputation with different
   contraction at block boundaries; ``_call_elementwise`` runs interpret
   mode as one block, and these tests would catch a regression of that.

Fuzzing is seeded-sweep based: ``hypothesis`` is an optional extra this
environment does not ship, so the same case matrix is generated from a
fixed PRNG seed — deterministic, and wide enough (shapes × dtypes × signs
× dt scales) to have caught every contraction bug found while deriving
the kernels.  If ``hypothesis`` is available the sweep still runs as-is
(no skip): the seeded matrix IS the contract.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.brownian import BrownianPath
from repro.kernels import brownian as bk
from repro.kernels import ops, prng, ref
from repro.kernels import reversible_heun_step as rh


@pytest.fixture(autouse=True)
def _x64_scope():
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


# Fuzzed case matrix: shapes exercise 1-D states, non-divisible dims, >2-D
# batching, and a VPU-aligned tile; dt scales exercise sub-ulp and O(1)
# magnitudes against state values of O(1).
SHAPES = [(4, 4), (8, 128), (4, 3), (5, 7), (1, 17), (2, 3, 8), (16,)]
DTYPES = [jnp.float32, jnp.float64]
SIGNS = [1.0, -1.0]
DTS = [0.01, 0.3]


def _fuzz(seed, shape, dtype, n_arrays):
    """Deterministic operand draw — the seeded stand-in for hypothesis."""
    ks = jax.random.split(jax.random.PRNGKey(seed), n_arrays)
    return [0.5 * jax.random.normal(k, shape, dtype) for k in ks]


def _assert_bitwise(a, b, label):
    a = a if isinstance(a, tuple) else (a,)
    b = b if isinstance(b, tuple) else (b,)
    for i, (x, y) in enumerate(zip(a, b)):
        ulps = 0 if bool(jnp.all(x == y)) else "nonzero"
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{label} output {i} not bitwise (ulp drift: {ulps})")


def _both(kernel_fn, ref_fn, arrays, dt, dtype):
    """jit-to-jit comparison with dt traced on BOTH sides (rules 1+2)."""
    dt = jnp.asarray(dt, dtype)
    got = jax.jit(lambda d: kernel_fn(*arrays, d))(dt)
    want = jax.jit(lambda d: ref_fn(*arrays, d))(dt)
    return got, want


# -----------------------------------------------------------------------------
# forward phases
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_phase1_bitwise(shape, dtype):
    z, zh, mu, sig, dw = _fuzz(11, shape, dtype, 5)
    for sign in SIGNS:
        for dt in DTS:
            got, want = _both(
                lambda *a: rh.rev_heun_phase1(*a, sign=sign, interpret=True),
                lambda *a: ref.rev_heun_phase1(*a, sign),
                (z, zh, mu, sig, dw), dt, dtype)
            _assert_bitwise(got, want, f"phase1 {shape} {dtype} {sign} {dt}")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_phase2_bitwise(shape, dtype):
    z, mu, mu1, sig, sig1, dw = _fuzz(13, shape, dtype, 6)
    for sign in SIGNS:
        for dt in DTS:
            got, want = _both(
                lambda *a: rh.rev_heun_phase2(*a, sign=sign, interpret=True),
                lambda *a: ref.rev_heun_phase2(*a, sign),
                (z, mu, mu1, sig, sig1, dw), dt, dtype)
            _assert_bitwise(got, want, f"phase2 {shape} {dtype} {sign} {dt}")


# -----------------------------------------------------------------------------
# backward (cotangent) phases — the hand-derived adjoint transpose
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_bwd_phase1_bitwise(shape, dtype):
    g_z1, g_mu1, g_sig1, dw = _fuzz(17, shape, dtype, 4)
    for dt in DTS:
        got, want = _both(
            lambda *a: rh.rev_heun_bwd_phase1(*a, interpret=True),
            ref.rev_heun_bwd_phase1,
            (g_z1, g_mu1, g_sig1, dw), dt, dtype)
        _assert_bitwise(got, want, f"bwd_phase1 {shape} {dtype} {dt}")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_bwd_phase2_bitwise(shape, dtype):
    g_z1, ghat, dw = _fuzz(19, shape, dtype, 3)
    for dt in DTS:
        got, want = _both(
            lambda *a: rh.rev_heun_bwd_phase2(*a, interpret=True),
            ref.rev_heun_bwd_phase2,
            (g_z1, ghat, dw), dt, dtype)
        _assert_bitwise(got, want, f"bwd_phase2 {shape} {dtype} {dt}")


def test_bwd_phases_are_the_vjp_transpose(key):
    """The backward kernels ARE jax.vjp of the reference step — bitwise.

    This is the identity the fused adjoint substitutes kernels into plain
    AD on: seed the unfused phase-1/phase-2 composition with cotangents and
    check the kernel pipeline reproduces ``jax.vjp``'s outputs exactly.
    """
    dtype = jnp.float64
    shape = (4, 8)
    z, zh, mu, sig, dw, g_z1 = _fuzz(23, shape, dtype, 6)
    dt = jnp.asarray(0.07, dtype)

    def phase2(z_, mu_, mu1, sig_, sig1, dw_, dt_):
        return ref.rev_heun_phase2(z_, mu_, mu1, sig_, sig1, dw_, dt_, 1.0)

    # unfused: AD transpose of phase 2 w.r.t. (z, mu1, sig1) — the pieces
    # _fused_local_vjp routes through the field VJP
    mu1, sig1 = _fuzz(29, shape, dtype, 2)
    _, vjp = jax.vjp(lambda z_, mu1_, sig1_: phase2(z_, mu, mu1_, sig, sig1_,
                                                    dw, dt), z, mu1, sig1)
    d_z_ad, c_mu1_ad, c_sig1_ad = vjp(g_z1)

    c_mu1_k, c_sig1_k = jax.jit(
        lambda d: rh.rev_heun_bwd_phase1(g_z1, jnp.zeros_like(mu),
                                         jnp.zeros_like(sig), dw, d,
                                         interpret=True))(dt)
    c_mu1_ref, c_sig1_ref = jax.jit(
        lambda d: ref.rev_heun_bwd_phase1(g_z1, jnp.zeros_like(mu),
                                          jnp.zeros_like(sig), dw, d))(dt)
    _assert_bitwise((c_mu1_k, c_sig1_k), (c_mu1_ref, c_sig1_ref),
                    "bwd_phase1 vs ref under vjp seeds")
    np.testing.assert_allclose(np.asarray(c_mu1_k), np.asarray(c_mu1_ad),
                               rtol=0, atol=1e-15)
    np.testing.assert_allclose(np.asarray(c_sig1_k), np.asarray(c_sig1_ad),
                               rtol=0, atol=1e-15)


# -----------------------------------------------------------------------------
# in-kernel Brownian generation
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(4, 4), (5, 7), (2, 3, 8), (16,)])
def test_brownian_increment_kernel_bitwise(shape, dtype):
    k1, k2 = prng.key_data_pair(jax.random.PRNGKey(42))
    for n in (0, 5, 63):
        for dt in DTS:
            dt = jnp.asarray(dt, dtype)
            got = jax.jit(lambda d: bk.brownian_increment(
                k1, k2, n, shape, dtype, d, interpret=True))(dt)
            want = jax.jit(lambda d: ref.brownian_increment(
                k1, k2, n, shape, dtype, d))(dt)
            _assert_bitwise(got, want, f"brownian_increment {shape} {dtype} {n}")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(4, 4), (2, 3, 8), (16,)])
def test_brownian_value_kernel_bitwise(shape, dtype):
    k1, k2 = prng.key_data_pair(jax.random.PRNGKey(43))
    for t in (0.125, 0.3, 0.77):
        t = jnp.asarray(t, dtype)
        got = jax.jit(lambda t_: bk.brownian_value(
            k1, k2, t_, 0.0, 1.0, shape, dtype, interpret=True))(t)
        want = jax.jit(lambda t_: ref.brownian_value(
            k1, k2, t_, 0.0, 1.0, shape, dtype))(t)
        _assert_bitwise(got, want, f"brownian_value {shape} {dtype} {float(t)}")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(4, 4), (5, 7), (2, 3, 8), (16,)])
def test_phase1_gen_kernel_bitwise(shape, dtype):
    k1, k2 = prng.key_data_pair(jax.random.PRNGKey(44))
    z, zh, mu, sig = _fuzz(31, shape, dtype, 4)
    dt_grid = jnp.asarray(1.0 / 64, dtype)
    for sign in SIGNS:
        for dt in DTS:
            dt = jnp.asarray(dt, dtype)
            got = jax.jit(lambda dg, d: bk.rev_heun_phase1_gen(
                z, zh, mu, sig, k1, k2, 5, dg, d, sign=sign,
                interpret=True))(dt_grid, dt)

            def want_fn(dg, d):
                dw = ref.brownian_increment(k1, k2, 5, shape, dtype, dg)
                return ref.rev_heun_phase1(z, zh, mu, sig, dw, d, sign), dw

            want = jax.jit(want_fn)(dt_grid, dt)
            _assert_bitwise(got, want, f"phase1_gen {shape} {dtype} {sign}")


# -----------------------------------------------------------------------------
# PRNG primitives: the in-kernel Threefry port vs jax.random itself
# -----------------------------------------------------------------------------


def test_threefry_port_matches_jax_random():
    """The hand-ported counter-based PRNG reproduces jax.random draws
    bitwise — the foundation of the in-kernel generation contract."""
    key = jax.random.PRNGKey(123)
    folded = jax.random.fold_in(key, 7)
    k1, k2 = prng.key_data_pair(key)
    for shape in [(4, 4), (5, 7), (33,)]:
        for dtype in DTYPES:
            want = jax.random.normal(folded, shape, dtype)
            fk1, fk2 = prng.fold_in(k1, k2, 7)
            got = prng.normal_like(fk1, fk2, shape, dtype)
            _assert_bitwise(got, want, f"normal {shape} {dtype}")


@pytest.mark.parametrize("dtype", DTYPES)
def test_increment_matches_brownianpath_contract(dtype):
    """PRNG contract, grid half: the in-kernel increment is bitwise the
    ``BrownianPath.increment`` draw for the same ``(key, n, grid)`` — the
    noise a fused fixed-step solve generates in-kernel is the noise the
    unfused solve reads off the path object."""
    key = jax.random.PRNGKey(9)
    shape = (3, 5)
    num_steps = 16
    bm = BrownianPath(key, 0.0, 1.0, shape, dtype)
    dt = jnp.asarray((bm.t1 - bm.t0) / num_steps, dtype)
    k1, k2 = prng.key_data_pair(key)
    for n in (0, 3, 15):
        path_inc = bm.increment(n, num_steps)
        kern_inc = jax.jit(lambda d: bk.brownian_increment(
            k1, k2, n, shape, dtype, d, interpret=True))(dt)
        _assert_bitwise(kern_inc, path_inc, f"increment n={n} {dtype}")


@pytest.mark.parametrize("dtype", DTYPES)
def test_value_kernel_matches_evaluate_contract(dtype):
    """PRNG contract, bridge half: in-kernel ``brownian_value`` differences
    are bitwise ``BrownianPath.evaluate(s, t)`` — the noise the fused
    adaptive driver consumes per attempt is exactly what the unfused
    driver (and the backward replay) query through the bridge API.  (Grid
    increments and bridge queries are different refinements of the path by
    design — this test deliberately compares bridge-to-bridge.)"""
    key = jax.random.PRNGKey(9)
    shape = (3, 5)
    bm = BrownianPath(key, 0.0, 1.0, shape, dtype)
    k1, k2 = prng.key_data_pair(key)
    for s, t in [(0.0, 0.25), (0.125, 0.3), (0.5, 0.77)]:
        ev = bm.evaluate(s, t)
        vs = jax.jit(lambda x: bk.brownian_value(
            k1, k2, x, 0.0, 1.0, shape, dtype, interpret=True))
        kern = vs(jnp.asarray(t, dtype)) - vs(jnp.asarray(s, dtype))
        _assert_bitwise(kern, ev, f"value-diff vs evaluate ({s},{t}) {dtype}")


def test_increment_contract_under_vmap():
    """The contract holds lane-wise under vmap over keys (batched
    multi-trajectory solving draws per-lane paths this way)."""
    dtype = jnp.float64
    shape = (4,)
    keys = jax.random.split(jax.random.PRNGKey(77), 5)
    num_steps = 8
    dt = jnp.asarray(1.0 / num_steps, dtype)

    def kern(key, d):
        k1, k2 = prng.key_data_pair(key)
        return bk.brownian_increment(k1, k2, 3, shape, dtype, d,
                                     interpret=True)

    def oracle(key, d):
        k1, k2 = prng.key_data_pair(key)
        return ref.brownian_increment(k1, k2, 3, shape, dtype, d)

    got = jax.jit(jax.vmap(kern, in_axes=(0, None)))(keys, dt)
    want = jax.jit(jax.vmap(oracle, in_axes=(0, None)))(keys, dt)
    _assert_bitwise(got, want, "vmapped increment")
    # and lane-wise against the path object's own draw.  bm.increment runs
    # the oracle EAGERLY on CPU, where XLA's contraction choices can drift
    # 1 ulp from the jit'd kernel (methodology rule 1) — so this linkage
    # assert is 1-ulp-tolerant; the bitwise gates above are jit-to-jit.
    lane = jax.jit(functools.partial(kern, keys[2]))(dt)
    path = BrownianPath(keys[2], 0.0, 1.0, shape, dtype)
    np.testing.assert_allclose(np.asarray(lane),
                               np.asarray(path.increment(3, num_steps)),
                               rtol=0, atol=5e-16)


# -----------------------------------------------------------------------------
# dispatch-layer equivalence: ops routes both paths to the same bits
# -----------------------------------------------------------------------------


def test_ops_forced_kernel_equals_oracle_path(key):
    """ops.* with use_kernel=True (interpret off-TPU) is bitwise the
    use_kernel=False oracle under jit — callers cannot observe the
    dispatch choice.  (The solver hot loops always run these inside
    compiled scans/whiles, so jit is the operative context.)"""
    dtype = jnp.float64
    shape = (4, 8)
    z, zh, mu, sig, dw = _fuzz(37, shape, dtype, 5)
    dt = jnp.asarray(0.05, dtype)

    def pipeline(uk, d):
        return (
            ops.rev_heun_phase1(z, zh, mu, sig, dw, d, use_kernel=uk),
            ops.rev_heun_phase2(z, mu, zh, sig, mu, dw, d, use_kernel=uk),
            ops.rev_heun_bwd_phase1(z, zh, mu, dw, d, use_kernel=uk),
            ops.rev_heun_bwd_phase2(z, zh, dw, d, use_kernel=uk),
            ops.brownian_increment(key, 2, shape, dtype, d, use_kernel=uk),
        )

    kernel_out = jax.jit(functools.partial(pipeline, True))(dt)
    oracle_out = jax.jit(functools.partial(pipeline, False))(dt)
    for i, (a, b) in enumerate(zip(jax.tree.leaves(kernel_out),
                                   jax.tree.leaves(oracle_out))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"ops dispatch leaf {i}")


# -----------------------------------------------------------------------------
# data-parallel dispatch: per-shard kernels draw the one-device bits
# -----------------------------------------------------------------------------

_SHARDED_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.sharding import make_mesh
from repro.kernels import ops

key = jax.random.PRNGKey(3)
z = jax.random.normal(key, (16, 17))

def draws():
    return (
        ops.brownian_increment(key, 5, (16, 17), jnp.float32, 0.1,
                               use_kernel=True),
        ops.brownian_increment(key, 5, (8, 2, 3), jnp.float32, 0.1,
                               use_kernel=True),
        ops.brownian_value(key, 0.3, 0.0, 1.0, (16, 17), jnp.float32,
                           use_kernel=True),
        ops.rev_heun_phase1_gen(z, z, z, z, key, 5, 0.1, 0.1,
                                use_kernel=True)[1],
        ops.brownian_increment(key, 5, (5, 3), jnp.float32, 0.1,
                               use_kernel=True))

one = jax.jit(draws)()
with jax.set_mesh(make_mesh((4,), ("data",))):
    compiled = jax.jit(draws).lower().compile()
    four = compiled()
assert "all-gather" not in compiled.as_text()
for a, b in zip(one, four):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("SHARDED-DRAWS-OK")
"""


def test_sharded_draws_equal_one_device_draws():
    """Under a 4-device data-parallel mesh every Brownian kernel runs per
    shard at its global row offset: the draws are bitwise the one-device
    draws (rows split 4 ways, a 3-D path, and a batch that does not divide
    and runs replicated), with no all-gather.  Runs in a child process:
    the device count is fixed when JAX starts."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src), JAX_PLATFORMS="cpu",
               REPRO_FORCE_PALLAS_INTERPRET="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "SHARDED-DRAWS-OK" in proc.stdout, (
        proc.stdout[-2000:] + proc.stderr[-4000:])
