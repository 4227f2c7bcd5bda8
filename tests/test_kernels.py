"""Pallas kernel sweeps (interpret mode) against the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import relay as core_relay
from repro.kernels import ops, ref
from repro.kernels import relay_mix as k


@pytest.mark.parametrize("n", [4, 10, 16, 32])
@pytest.mark.parametrize("D", [64, 100, 4096, 5000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_relay_mix_2d_sweep(n, D, dtype):
    rng = np.random.default_rng(hash((n, D)) % 2**31)
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    d = jnp.asarray(rng.standard_normal((n, D)), dtype)
    got = k.relay_mix_2d(A, d, interpret=True)
    want = ref.relay_mix_2d(A, d)
    tol = 1e-4 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("n", [4, 16])
@pytest.mark.parametrize("D", [100, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_aggregate_2d_sweep(n, D, dtype):
    rng = np.random.default_rng(hash((n, D, 1)) % 2**31)
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    tau = jnp.asarray(rng.random(n) < 0.5, jnp.float32)
    c = (1.0 / n) * tau @ A
    d = jnp.asarray(rng.standard_normal((n, D)), dtype)
    got = k.fused_aggregate_2d(c, d, interpret=True)
    want = ref.fused_aggregate_2d(c, d)
    tol = 1e-4 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("block_d", [128, 512, 4096])
def test_block_size_invariance(block_d):
    rng = np.random.default_rng(7)
    A = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    d = jnp.asarray(rng.standard_normal((8, 1000)), jnp.float32)
    got = k.relay_mix_2d(A, d, block_d=block_d, interpret=True)
    want = ref.relay_mix_2d(A, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_pytree_wrapper_matches_core_relay():
    rng = np.random.default_rng(3)
    n = 10
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    upd = {
        "w": jnp.asarray(rng.standard_normal((n, 33, 7)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((n, 257)), jnp.float32),
    }
    got = ops.relay_mix(A, upd, interpret=True)
    want = core_relay.relay(A, upd)
    for key in upd:
        np.testing.assert_allclose(
            np.asarray(got[key]), np.asarray(want[key]), atol=1e-4
        )


def test_pytree_fused_matches_core():
    rng = np.random.default_rng(4)
    n = 10
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    tau = jnp.asarray(rng.random(n) < 0.5, jnp.float32)
    upd = {"w": jnp.asarray(rng.standard_normal((n, 65)), jnp.float32)}
    got = ops.fused_aggregate(A, tau, upd, w=0.1, interpret=True)
    want = core_relay.fused_aggregate(A, tau, upd, w=0.1)
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(want["w"]), atol=1e-4)


def test_kernel_under_jit_and_grad():
    """The kernel wrapper composes with jit (and is linear, so its vjp must
    reproduce Aᵀ on cotangents)."""
    n, D = 6, 300
    rng = np.random.default_rng(5)
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    d = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)

    def f(d):
        return ref.relay_mix_2d(A, d).sum()

    def f_kernel(d):
        return k.relay_mix_2d(A, d, interpret=True).sum()

    np.testing.assert_allclose(float(f(d)), float(f_kernel(d)), rtol=1e-5)
    g_ref = jax.grad(f)(d)
    g_k = jax.grad(f_kernel)(d)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_ref), atol=1e-4)


# ----------------------------------------------------------------------
# ISSUE 7: flat-buffer dispatch parity (ops.mix_flat / ops.reduce_flat)
# ----------------------------------------------------------------------


def _flat_case(n, dtype, masked, salt=0):
    """One (A, buf, active, coeffs) draw for the flat-path sweep.  D=1000 is
    deliberately not a multiple of block_d=256 so the kernels pad a tail
    block; A and coeffs are scaled by 1/√n to keep outputs O(1) across n."""
    D = 1000
    rng = np.random.default_rng(hash((n, D, masked, salt)) % 2**31)
    scale = 1.0 / max(1.0, np.sqrt(n))
    A = jnp.asarray(rng.standard_normal((n, n)) * scale, jnp.float32)
    buf = jnp.asarray(rng.standard_normal((n, D)), dtype)
    active = None
    if masked:
        act = rng.random(n) < 0.6
        act[rng.integers(n)] = True  # at least one live client
        active = jnp.asarray(act, jnp.float32)
    coeffs = jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)
    if active is not None:
        coeffs = coeffs * active
    return A, buf, active, coeffs


@pytest.mark.parametrize("n", [1, 7, 64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("masked", [False, True])
def test_mix_flat_backend_parity(n, dtype, masked):
    """The streaming mix kernel vs the einsum oracle through the ops
    dispatch: degenerate n=1 up to n=128, f32/bf16 buffers, tail padding,
    with and without the churn active mask."""
    A, buf, active, _ = _flat_case(n, dtype, masked)
    got = ops.mix_flat(
        A, buf, active=active, backend="pallas", block_d=256, interpret=True
    )
    want = ops.mix_flat(A, buf, active=active, backend="einsum")
    assert got.shape == want.shape == buf.shape
    tol = 1e-4 if dtype == jnp.float32 else 0.25
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("n", [1, 7, 64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("masked", [False, True])
def test_reduce_flat_backend_parity(n, dtype, masked):
    """The fused reduction kernel vs the einsum oracle on the same sweep;
    churn masking rides in the coefficients (the reduce_flat contract)."""
    _, buf, _, coeffs = _flat_case(n, dtype, masked, salt=1)
    got = ops.reduce_flat(
        coeffs, buf, backend="pallas_fused", block_d=256, interpret=True
    )
    want = ops.reduce_flat(coeffs, buf, backend="einsum")
    assert got.shape == want.shape == (buf.shape[1],)
    tol = 1e-4 if dtype == jnp.float32 else 0.25
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


def test_flat_dispatch_rejects_unknown_backend():
    buf = jnp.zeros((2, 8))
    with pytest.raises(ValueError, match="relay_backend"):
        ops.mix_flat(jnp.eye(2), buf, backend="triton")
    with pytest.raises(ValueError, match="relay_backend"):
        ops.reduce_flat(jnp.ones(2), buf, backend="cuda")


def test_custom_vjp_gradient_parity_vs_einsum():
    """The mix kernel's custom_vjp must reproduce the einsum reference's
    cotangents for BOTH operands — dΔ (the transposed kernel pass) and dA
    (the (n, n) reduction) — through a padded tail block."""
    n, D = 5, 700  # 700 = 2·256 + 188: the bwd kernel also crosses padding
    rng = np.random.default_rng(17)
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    d = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)

    def loss_kernel(A_, d_):
        return jnp.vdot(k.relay_mix_2d(A_, d_, block_d=256, interpret=True), cot)

    def loss_ref(A_, d_):
        return jnp.vdot(ref.relay_mix_2d(A_, d_), cot)

    np.testing.assert_allclose(
        float(loss_kernel(A, d)), float(loss_ref(A, d)), rtol=1e-5
    )
    gA_k, gd_k = jax.grad(loss_kernel, argnums=(0, 1))(A, d)
    gA_r, gd_r = jax.grad(loss_ref, argnums=(0, 1))(A, d)
    np.testing.assert_allclose(np.asarray(gA_k), np.asarray(gA_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gd_k), np.asarray(gd_r), atol=1e-4)


@pytest.mark.parametrize("n", [4, 8, 10, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("requested", [None, 4096, 1 << 20])
def test_block_fits_the_vmem_tile_budget(n, dtype, requested):
    """The tile width: a lane multiple (or the whole buffer), no wider than
    the buffer, and its double-buffered in and out tiles — rows padded to
    the dtype's sublane tile — within ops.VMEM_TILE_BUDGET."""
    width = 10_013_594
    buf = jax.ShapeDtypeStruct((n, width), dtype)
    block = ops._block(requested, buf)
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * (4 // itemsize)
    rows = -(-n // sublanes) * sublanes
    assert block % 128 == 0 and 128 <= block <= width
    assert 4 * rows * block * itemsize <= ops.VMEM_TILE_BUDGET
    assert block <= (requested or k.DEFAULT_BLOCK_D)
    # a narrow buffer is one tile, never padded up to the default
    assert ops._block(requested, jax.ShapeDtypeStruct((n, 200), dtype)) == 200
    assert ops._block(requested, jax.ShapeDtypeStruct((n, 64), dtype)) == 128
