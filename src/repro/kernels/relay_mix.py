"""Pallas TPU kernel for the ColRel hot spot: Δ̃ = A · Δ (and the fused
τ-weighted PS reduction).

Shape regime: A is tiny ((n, n), n ≤ 128 clients) and Δ is enormous
((n, D), D = total model parameters, 10⁶–10¹¹).  The kernel keeps A resident
in VMEM for the whole launch and streams Δ through in (n, block_d) tiles —
one HBM read + one HBM write per element, with the (n×n)·(n×block_d) MXU
matmul per tile.  block_d is a multiple of 128 (lane granule); callers size
it through ``repro.kernels.ops`` so the double-buffered Δ and output tiles
fit a fixed VMEM budget (``ops.VMEM_TILE_BUDGET``).

The fused variant computes  u = (w·τᵀA) · Δ  — the relay∘aggregate
composition (DESIGN.md §2) — reading Δ once and writing only (1, block_d)
per tile: an n× reduction in write traffic vs relay-then-reduce.

``interpret`` defaults to False: the kernels compile for the TPU, and a
caller on another backend must ask for the interpreter explicitly.  Validated
in interpret mode against ``ref.py`` across shape/dtype sweeps
(tests/test_kernels.py) and compiled for a described v5e chip
(tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_D = 4096


def _kernel(w_ref, d_ref, o_ref):
    """One (rows, block_d) output tile = weights @ Δ-tile.  f32 operands
    contract at full f32 precision, as the HIGHEST einsum oracle does:
    Mosaic's default for f32 is one bf16 pass (measured 2.7e-3 relative
    error on a TPU v5e).  bf16 operands take the MXU's native bf16 path,
    named explicitly so a caller's ``jax.default_matmul_precision`` cannot
    ask for HIGHEST, which Mosaic refuses for them."""
    d = d_ref[...]
    precision = (
        jax.lax.Precision.HIGHEST
        if d.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    o_ref[...] = jnp.dot(
        w_ref[...], d, preferred_element_type=jnp.float32, precision=precision
    ).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _relay_mix_core(A, delta, block_d: int, interpret: bool):
    n, D = delta.shape
    Dp = -(-D // block_d) * block_d
    if Dp != D:
        delta = jnp.pad(delta, ((0, 0), (0, Dp - D)))
    out = pl.pallas_call(
        _kernel,
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((n, n), lambda j: (0, 0)),  # A resident
            pl.BlockSpec((n, block_d), lambda j: (0, j)),  # Δ streamed
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n, Dp), delta.dtype),
        interpret=interpret,
    )(A.astype(delta.dtype), delta)
    return out[:, :D]


def _relay_mix_fwd(A, delta, block_d, interpret):
    return _relay_mix_core(A, delta, block_d, interpret), (A, delta)


def _relay_mix_bwd(block_d, interpret, res, g):
    # the mix is linear: ∂/∂Δ = Aᵀ g (run the same kernel with Aᵀ);
    # ∂/∂A = g Δᵀ is a small (n, n) reduction.
    A, delta = res
    ddelta = _relay_mix_core(A.T, g, block_d, interpret)
    dA = jnp.einsum(
        "rd,od->ro", g.astype(jnp.float32), delta.astype(jnp.float32)
    ).astype(A.dtype)
    return dA, ddelta


_relay_mix_core.defvjp(_relay_mix_fwd, _relay_mix_bwd)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def relay_mix_2d(A, delta, *, block_d: int = DEFAULT_BLOCK_D, interpret: bool = False):
    """Δ̃ = A @ Δ for Δ of shape (n, D); D padded to a block_d multiple."""
    return _relay_mix_core(A, delta, block_d, interpret)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_aggregate_2d(
    coeffs, delta, *, block_d: int = DEFAULT_BLOCK_D, interpret: bool = False
):
    """u = coeffs @ Δ  (coeffs = w·τᵀA, shape (n,)) → (D,)."""
    n, D = delta.shape
    Dp = -(-D // block_d) * block_d
    if Dp != D:
        delta = jnp.pad(delta, ((0, 0), (0, Dp - D)))
    out = pl.pallas_call(
        _kernel,
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((1, n), lambda j: (0, 0)),
            pl.BlockSpec((n, block_d), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), delta.dtype),
        interpret=interpret,
    )(coeffs.reshape(1, n).astype(delta.dtype), delta)
    return out[0, :D]
