"""Jitted public wrappers: relay mixing over parameter *pytrees* backed by the
Pallas kernels.  Leaves are flattened to (n, leaf_size) tiles, streamed
through the kernel, and restored — so the single-host simulator can run the
whole D2D consensus as one fused kernel pass per leaf.

``interpret=None`` (every wrapper's default) derives the mode from the
backend: compiled Mosaic kernels on a TPU, the Pallas interpreter elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import relay as relay_lib
from repro.kernels import ref as _ref
from repro.kernels import relay_mix as _k

# the relay_backend knob (make_aggregator / build_round_step / scenarios):
#   einsum        pure-XLA reference path (ref.py oracles on the flat buffer)
#   pallas        kernel mix Δ̃ = A·Δ; the PS reduction stays an einsum
#   pallas_fused  kernel u = (w·τᵀA)·Δ — relay∘aggregate in one pass, the
#                 n×-less-write-traffic hot path
#   segment       sparse edge-list path (core.relay.EdgeRelay +
#                 jax.ops.segment_sum): relay∘aggregate cost scales with the
#                 edge count E, not n² — the n ≫ 10³ cohort-sampling regime
RELAY_BACKENDS = ("einsum", "pallas", "pallas_fused", "segment")


def validate_backend(backend: str) -> str:
    if backend not in RELAY_BACKENDS:
        raise ValueError(f"unknown relay_backend {backend!r} (known: {RELAY_BACKENDS})")
    return backend


def validate_sharded_backend(backend: str, *, shard: str, exchange: str = "gather") -> str:
    """Backend dispatch under sharding (``build_sharded_scan_round_step``):

    * ``shard="d"``: the contraction is partitioned over D by GSPMD, which
      has no partitioning rules for the Pallas kernels — einsum only.
    * ``exchange="ring"``: the ring collective *replaces* the relay
      contraction (k−1 ppermutes + psum), so a kernel backend would be
      silently ignored — einsum only, by refusal rather than surprise.
    * ``exchange="gather"``: the gathered (n, D) buffer is replicated
      per-device, so any dense backend runs unchanged inside shard_map.
    * ``segment`` is refused under every sharding mode: the sharded step
      builders take a dense (n, n) operand (replicated or GSPMD-partitioned),
      and an EdgeRelay's data-dependent gather/scatter has no sharding rule
      worth writing before the hierarchical-relaying follow-on.
    """
    validate_backend(backend)
    if backend == "segment":
        raise ValueError(
            "relay_backend='segment' is single-host only — the sharded "
            "round-step builders need a dense relay operand; use "
            "relay_backend='einsum' (or a pallas backend with "
            "exchange='gather')"
        )
    if shard == "d" and backend != "einsum":
        raise ValueError(
            "D-axis sharding partitions the relay contraction via GSPMD; "
            "the Pallas kernels have no partitioning rules — use "
            "relay_backend='einsum'"
        )
    if shard == "clients" and exchange == "ring" and backend != "einsum":
        raise ValueError(
            "exchange='ring' replaces the relay contraction with ppermute "
            "rotations; relay_backend must be 'einsum' (the kernel would "
            "never run)"
        )
    return backend


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mask_A(A, active):
    """Restrict A to the active block of a padded client dim (client churn);
    the mask folds into the operand (dense matrix or EdgeRelay edge values),
    the kernel itself is unchanged."""
    if active is None:
        return A if isinstance(A, relay_lib.EdgeRelay) else jnp.asarray(A)
    return relay_lib.mask_relay_matrix(A, active)


def relay_mix(
    A, stacked, *, active=None, block_d: int = _k.DEFAULT_BLOCK_D, interpret=None
):
    """Δ̃ = A·Δ over a stacked pytree (leaves (n, ...)).  ``active`` is the
    optional churn mask: inactive rows/cols of A are zeroed, so a departed
    client's slot neither relays nor is relayed."""
    interpret = _default_interpret() if interpret is None else interpret
    A = _mask_A(A, active)
    n = A.shape[0]

    def mix(leaf):
        flat = leaf.reshape(n, -1)
        out = _k.relay_mix_2d(
            jnp.asarray(A),
            flat,
            block_d=_block(block_d, flat),
            interpret=interpret,
        )
        return out.reshape(leaf.shape)

    return jax.tree.map(mix, stacked)


def fused_aggregate(
    A,
    tau,
    stacked,
    *,
    w,
    active=None,
    block_d: int = _k.DEFAULT_BLOCK_D,
    interpret=None,
):
    """w · Σ_r τ_r (A·Δ)_r without materializing the relayed updates.
    ``w`` may be a python float (fixed membership) or a traced scalar
    (1/n_active under churn); ``active`` masks A and τ to the live block."""
    interpret = _default_interpret() if interpret is None else interpret
    A = _mask_A(A, active)
    n = A.shape[0]
    tau = jnp.asarray(tau, jnp.float32)
    if active is not None:
        tau = tau * jnp.asarray(active, jnp.float32)
    coeffs = w * (tau @ A.astype(jnp.float32))

    def reduce(leaf):
        flat = leaf.reshape(n, -1)
        out = _k.fused_aggregate_2d(
            coeffs,
            flat,
            block_d=_block(block_d, flat),
            interpret=interpret,
        )
        return out.reshape(leaf.shape[1:])

    return jax.tree.map(reduce, stacked)


# --------------------------------------------------------------------------
# Flat-buffer dispatch: the (n, D) raveled hot path (utils.stacked_ravel)
# --------------------------------------------------------------------------


# VMEM the kernels' streamed tiles may take: the (n, block_d) Δ tile and the
# output tile, each double-buffered by the Pallas pipeline.  Half of the
# 16 MiB scoped-VMEM default of a TPU v5e core, so the f32 dot result of a
# tile and the resident (n, n) operand fit beside them.  A tile above it is
# refused by the chip's compiler (RESOURCE_EXHAUSTED in memory space vmem).
VMEM_TILE_BUDGET = 8 * 2**20


def _block(block_d, buf) -> int:
    """The Δ tile width for an (n, D) buffer: the requested ``block_d``
    (None ⇒ kernel default), clamped to the buffer (tiny-D scenarios must
    not pad a 64-wide model to a 4096 tile) and to ``VMEM_TILE_BUDGET``.
    Rows count as the buffer dtype's sublane tile pads them (8 for 32-bit,
    16 for 16-bit).  Floor 128 = the TPU lane granule.  The kernels sum
    over n only, so the tile width never changes their numbers."""
    n, width = buf.shape
    itemsize = jnp.dtype(buf.dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    rows = -(-n // sublanes) * sublanes
    fit = VMEM_TILE_BUDGET // (4 * rows * itemsize) // 128 * 128
    want = _k.DEFAULT_BLOCK_D if block_d is None else block_d
    return max(128, min(want, fit, width))


def mix_flat(
    A,
    buf,
    *,
    active=None,
    backend: str = "einsum",
    block_d: int | None = None,
    interpret=None,
):
    """Δ̃ = A·Δ on the contiguous (n, D) buffer.  ``backend`` picks the
    einsum oracle, the Pallas kernel, or the sparse segment-sum path
    (``backend="segment"``, which needs an :class:`~repro.core.relay.EdgeRelay`
    operand); ``active`` is the churn mask (zeroes inactive rows/cols of A —
    or the touching edge values — before dispatch, on every backend)."""
    validate_backend(backend)
    A = _mask_A(A, active)
    if backend == "segment":
        if not isinstance(A, relay_lib.EdgeRelay):
            raise ValueError(
                "relay_backend='segment' needs an EdgeRelay operand "
                "(a sparse OPT-α policy); got a dense relay matrix — "
                "use relay_backend='einsum' or convert via "
                "relay.edge_relay_from_dense"
            )
        return relay_lib.segment_mix(A, buf)
    if isinstance(A, relay_lib.EdgeRelay):
        A = A.todense(buf.shape[0])
    if backend == "einsum":
        return _ref.relay_mix_2d(A, buf)
    interpret = _default_interpret() if interpret is None else interpret
    return _k.relay_mix_2d(
        jnp.asarray(A),
        buf,
        block_d=_block(block_d, buf),
        interpret=interpret,
    )


def reduce_flat(
    coeffs,
    buf,
    *,
    backend: str = "einsum",
    block_d: int | None = None,
    interpret=None,
):
    """u = coeffs·Δ on the (n, D) buffer → (D,).  ``coeffs`` already carries
    every weighting (w·τᵀA for the fused colrel path, w·τ for the blind
    sum, ...), so churn masking happens in the caller's coefficients.
    ``backend="segment"`` lands here with an already-dense (n,) coefficient
    vector — the sparsity was spent computing it — so it runs the einsum
    reduction."""
    validate_backend(backend)
    coeffs = jnp.asarray(coeffs, jnp.float32)
    if backend in ("einsum", "segment"):
        return _ref.fused_aggregate_2d(coeffs, buf)
    interpret = _default_interpret() if interpret is None else interpret
    return _k.fused_aggregate_2d(
        coeffs,
        buf,
        block_d=_block(block_d, buf),
        interpret=interpret,
    )
