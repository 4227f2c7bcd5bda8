"""Ring-schedule D2D relay: the paper's physical exchange as manual
collectives (`shard_map` + `lax.ppermute`).

The relaying round of the paper (§II-C, eq. 2) is literally a network event:
every client transmits its local update to its D2D neighbors, each relay
forms the weighted consensus Δx̃_r = Σ_o α_{r,o} Δx_o, and the PS blindly
sums what arrives.  On a device mesh the same dataflow is a **ring
collective**: updates rotate around the client axis with `ppermute`, and
each rotation step contributes one α-weighted term to the local accumulator
— after n−1 rotations every relay holds its consensus with **O(1) live
buffers** instead of the O(n·|Δ|) gather of the einsum formulation.  The
blind PS reduction is then a τ-weighted `psum` over the same axis.

Step-by-step (4 devices; at rotation s, device r holds Δ_{(r−s) mod n} and
adds α_{r,(r−s)}·Δ_{(r−s)}):

    s=0   d0:Δ0   d1:Δ1   d2:Δ2   d3:Δ3      acc += α_{r,r}  Δ_r
    s=1   d0:Δ3   d1:Δ0   d2:Δ1   d3:Δ2      acc += α_{r,r−1}Δ_{r−1}
    s=2   d0:Δ2   d1:Δ3   d2:Δ0   d3:Δ1      acc += α_{r,r−2}Δ_{r−2}
    s=3   d0:Δ1   d1:Δ2   d2:Δ3   d3:Δ0      acc += α_{r,r−3}Δ_{r−3}
    psum( w·τ_r · acc_r )  →  the PS increment, replicated

Two granularities:

* **one client per device** (:func:`ring_relay_local`,
  :func:`ring_colrel_increment`, :func:`make_ring_round_mixer`): pytree
  deltas, the reference formulation; `tests/test_ring_relay.py` proves it
  equal to the einsum relay on a real (multi-axis) mesh.
* **a block of clients per device** (:func:`ring_relay_flat`,
  :func:`ring_colrel_increment_flat`): the production shape used inside
  `build_sharded_scan_round_step` — each of k devices owns m = n/k client
  rows of the raveled (n, D) buffer, rotations move (m, D) blocks, and each
  step contributes the (m, m) block-matmul A[rows_r, rows_{r−s}] @ block.
  k−1 ppermutes replace the all-gather regardless of how many clients share
  a device.

Reduction-order note: the ring accumulates α-terms in rotation order
(diagonal first), whereas the einsum contracts in XLA's order — the results
agree to f32 accumulation accuracy, *not* bitwise.  The sharded engine's
``exchange="gather"`` mode keeps the einsum order (bitwise vs the
single-device reference); ``exchange="ring"`` trades that for O(1) buffers
at a documented tolerance (see docs/distributed.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils import tree_axpy, tree_scale


def _combined_index(axis_names):
    idx = jax.lax.axis_index(axis_names[0])
    for a in axis_names[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def ring_relay_local(A, delta_local, axis_names: tuple):
    """Inside shard_map: delta_local = this client's Δx (no client dim).
    Returns Δx̃_r for the local relay r.  A: (n, n) host constant."""
    A = jnp.asarray(A, jnp.float32)
    n = A.shape[0]
    r = _combined_index(axis_names)
    perm = [(i, (i + 1) % n) for i in range(n)]

    acc = tree_scale(A[r, r], delta_local)

    def step(s, carry):
        buf, acc = carry
        buf = jax.tree.map(lambda x: jax.lax.ppermute(x, axis_names, perm), buf)
        origin = (r - s) % n
        acc = tree_axpy(A[r, origin], buf, acc)
        return buf, acc

    _, acc = jax.lax.fori_loop(1, n, step, (delta_local, acc))
    return acc


def ring_colrel_increment(A, tau, delta_local, *, w: float, axis_names: tuple):
    """Full blind round reduction inside shard_map:
    w · Σ_r τ_r Δx̃_r, replicated over the client axes."""
    relayed = ring_relay_local(A, delta_local, axis_names)
    r = _combined_index(axis_names)
    tau_r = jnp.asarray(tau, jnp.float32)[r]
    weighted = tree_scale(w * tau_r, relayed)
    return jax.tree.map(lambda x: jax.lax.psum(x, axis_names), weighted)


def make_ring_round_mixer(A, *, w: float, mesh, client_axes: tuple):
    """shard_map wrapper: stacked deltas (n, ...) sharded over `client_axes`
    → PS increment pytree (replicated).  Other dims must be unsharded within
    the client shard (use the einsum/fused paths for model-sharded deltas)."""
    from jax.sharding import PartitionSpec as P

    def local(tau, deltas_stacked):
        delta_local = jax.tree.map(lambda x: x[0], deltas_stacked)
        return ring_colrel_increment(A, tau, delta_local, w=w, axis_names=client_axes)

    def in_specs(deltas):
        return (
            P(),
            jax.tree.map(lambda x: P(client_axes, *([None] * (x.ndim - 1))), deltas),
        )

    def mixer(tau, deltas_stacked):
        spec_tau, spec_d = in_specs(deltas_stacked)
        out_spec = jax.tree.map(lambda x: P(*([None] * (x.ndim - 1))), deltas_stacked)
        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(spec_tau, spec_d),
            out_specs=out_spec,
            check_vma=False,
        )(jnp.asarray(tau, jnp.float32), deltas_stacked)

    return mixer


# --------------------------------------------------------------------------
# Block-ring on the raveled (n, D) buffer: m = n/k clients per device
# --------------------------------------------------------------------------


def ring_relay_flat(A, buf_local, *, axis_name: str, n_shards: int):
    """Inside shard_map: ``buf_local`` is this device's (m, D) block of the
    raveled delta buffer (rows j·m … (j+1)·m−1 of the (n, D) stack for
    device j).  Returns the local relays' consensus block Δx̃ (m, D).

    ``A`` is the full (n, n) relay matrix, replicated: each rotation step s
    contributes the (m, m) block ``A[j·m:, origin·m:] @ block`` where
    ``origin = (j − s) mod k`` is the device whose rows are passing through.
    ``n_shards`` (= k) must be static — it sizes the permutation table.
    """
    A = jnp.asarray(A, jnp.float32)
    n = A.shape[0]
    if n % n_shards != 0:
        raise ValueError(f"n={n} not divisible by n_shards={n_shards}")
    m = n // n_shards
    j = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def block(r, c):
        return jax.lax.dynamic_slice(A, (r * m, c * m), (m, m))

    acc = block(j, j) @ buf_local

    def step(s, carry):
        buf, acc = carry
        buf = jax.lax.ppermute(buf, axis_name, perm)
        origin = (j - s) % n_shards
        acc = acc + block(j, origin) @ buf
        return buf, acc

    if n_shards > 1:
        _, acc = jax.lax.fori_loop(1, n_shards, step, (buf_local, acc))
    return acc


def ring_colrel_increment_flat(
    A, tau, buf_local, *, w, axis_name: str, n_shards: int
):
    """Full blind round reduction on the flat buffer inside shard_map:
    u = w · Σ_r τ_r Δx̃_r → (D,), replicated over ``axis_name``.

    ``tau`` is the full (n,) mask, replicated (the sharded engine draws it
    identically on every device from the same key chain); churn masking of
    A and τ is the *caller's* job, exactly as in
    ``aggregation.colrel_increment_flat`` — this function only phrases the
    contraction as k−1 ppermutes + a psum.
    """
    relayed = ring_relay_flat(
        A, buf_local, axis_name=axis_name, n_shards=n_shards
    )
    m = relayed.shape[0]
    j = jax.lax.axis_index(axis_name)
    tau = jnp.asarray(tau, jnp.float32)
    tau_local = jax.lax.dynamic_slice(tau, (j * m,), (m,))
    u_local = (w * tau_local) @ relayed
    return jax.lax.psum(u_local, axis_name)
