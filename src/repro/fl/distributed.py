"""Distributed ColRel round step for the production mesh (dry-run + launcher).

Clients map to the mesh's client axes (``data``, or ``pod × data`` multi-pod);
each client's model/compute shards over ``model`` (and ``data`` for FSDP
archs).  Batches arrive stacked (n_clients, T, local_batch, ...).

Two relay schedules compute the identical PS update (DESIGN.md §2):

  * ``faithful``: per-client Δx materialized, local consensus Δx̃ = A·Δx
    (GSPMD lowers the client-dim einsum to all-gathers — the D2D exchange),
    then the blind masked PS sum.  Mirrors the paper's physical protocol.
  * ``fused``: PS ∘ relay fused to one weighted reduce with c = τᵀA.  With
    T = 1 the weighted per-client gradient sum is formed directly, so no
    per-client full-parameter tensor ever exists.  Beyond-paper optimization.

τ is sampled on the host per round and passed in — the step itself is
deterministic and identity-blind (OAC-compatible).  The exception is
:func:`build_fused_scan_round_step`, the pipelined engine's mesh analogue:
it takes the RNG key instead and draws the epoch's τ stream inside the scan
body (key chain in the carry), so a whole epoch — τ draws included — is one
device dispatch.

:func:`build_sharded_scan_round_step` is the **multi-device** production
path (same τ-fused signature): under ``shard="clients"`` the step runs in
`shard_map` over the mesh's client axis — each device owns m = n/k client
slots, runs their local SGD, and the relay exchange is either an
``all_gather`` of the raveled delta blocks (bitwise-identical math to the
single-device step) or the block-ring collective from `repro.fl.ring`
(O(1) live buffers, f32-tolerance-identical).  Under ``shard="d"`` the step
stays GSPMD: a sharding constraint from `repro.sharding.rules` partitions
the (n, D) relay contraction over the model axis.  See docs/distributed.md.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import aggregation, relay as relay_lib
from repro.core.aggregation import ServerOpt, active_weight
from repro.optim.sgd import ClientOpt
from repro.utils import stacked_ravel, tree_scale, tree_sub, tree_unravel


def build_round_step(
    loss_fn: Callable[[Any, dict], jax.Array],
    *,
    n_clients: int,
    local_steps: int,
    A=None,
    relay_mode: str = "faithful",
    relay_backend: str = "einsum",
    block_d: int | None = None,
    interpret=None,
    client_opt: ClientOpt = ClientOpt(kind="sgd", weight_decay=1e-4),
    server_opt: ServerOpt = ServerOpt(),
    constrain_buffer: Callable | None = None,
):
    """Returns round(params, server_state, batch, tau, lr, A=None, active=None)
    -> (params', state', loss).

    batch leaves: (n_clients, local_steps, per_client_batch, ...).

    The relay matrix may be bound at build time (static channel: it folds into
    the compiled step as a constant) or passed per call (time-varying channel:
    it is a traced input, so swapping A values between rounds does not retrace
    a jitted ``round``).  The call-time A wins when both are given.

    ``active`` is the churn mask over the padded client dimension
    (``n_clients = n_max``): a traced (n,) 0/1 vector restricting the relay
    matrix, τ and the blind weight (1/n_active) to the live clients, so
    membership changes between calls never retrace.  ``None`` keeps the
    static-weight fixed-membership path.

    ``relay_backend`` dispatches the relay∘aggregate contraction over the
    raveled (n, D) delta buffer to the Pallas kernels (see
    ``repro.core.aggregation.colrel_increment_flat``).  It applies wherever
    per-client deltas are materialized — every path except T = 1 fused, whose
    weighted-loss trick never forms an (n, D) tensor to stream (there is
    nothing for a kernel to read, so that path stays pure XLA).

    ``constrain_buffer`` (D-axis sharding) is applied to the raveled (n, D)
    buffer right after the ravel — `build_sharded_scan_round_step(shard="d")`
    passes a `with_sharding_constraint` over the mesh's model axis here, so
    GSPMD partitions the relay contraction over parameters.
    """
    T = local_steps
    A_static = A
    aggregation_kw = dict(
        backend=relay_backend, block_d=block_d, interpret=interpret
    )

    def round(params, server_state, batch, tau, lr, A=None, active=None):
        A = A_static if A is None else A
        if A is None:
            raise ValueError("no relay matrix: bind A at build time or pass it")

        def _mean_loss(losses):
            if active is None:
                return jnp.mean(losses)
            a_ = jnp.asarray(active, jnp.float32)
            return jnp.sum(losses * a_) / jnp.maximum(a_.sum(), 1.0)

        def _flat_increment(deltas):
            # ravel → kernel-dispatched increment → structured f32 view;
            # churn masking (A, τ, 1/n_active) happens inside the flat fn
            buf, spec = stacked_ravel(deltas)
            if constrain_buffer is not None:
                buf = constrain_buffer(buf)
            flat = aggregation.colrel_increment_flat(
                A, tau, buf, n=n_clients, fused=(relay_mode == "fused"),
                active=active, **aggregation_kw,
            )
            return tree_unravel(spec, flat, cast=False)

        if T == 1 and relay_mode == "fused":
            # never materialize per-client deltas: weighted loss trick —
            # Σ_o c_o Δ_o = -lr · ∇ Σ_o c_o L_o(x)  (+ wd term)
            w = active_weight(active, n=n_clients)
            A_f, tau_f = A, tau
            if active is not None:
                a = jnp.asarray(active, jnp.float32)
                A_f = relay_lib.mask_relay_matrix(A, a)
                tau_f = jnp.asarray(tau, jnp.float32) * a
            c = relay_lib.fused_coefficients(A_f, tau_f)  # (n,)

            def weighted_loss(p):
                sq = jax.tree.map(lambda x: x[:, 0], batch)  # (n, b, ...)
                losses = jax.vmap(lambda b_: loss_fn(p, b_))(sq)
                return jnp.sum(c * losses), losses

            (_, losses), gsum = jax.value_and_grad(weighted_loss, has_aux=True)(
                params
            )
            csum = jnp.sum(c)

            def _fused_inc(gs, pe):
                wd = csum * client_opt.weight_decay * pe.astype(jnp.float32)
                return -lr * w * (gs.astype(jnp.float32) + wd)

            inc = jax.tree.map(_fused_inc, gsum, params)
            mean_loss = _mean_loss(losses)
        elif T == 1:
            # deltas_g: stacked decayed grads (n, ...); Δ_i = -lr · g_i
            def one(client_batch):
                sq = jax.tree.map(lambda x: x[0], client_batch)
                loss, g = jax.value_and_grad(loss_fn)(params, sq)

                def _decayed(ge, pe):
                    wd = client_opt.weight_decay
                    return ge.astype(jnp.float32) + wd * pe.astype(jnp.float32)

                return jax.tree.map(_decayed, g, params), loss

            deltas_g, losses = jax.vmap(one)(batch)
            inc = _flat_increment(tree_scale(-lr, deltas_g))
            mean_loss = _mean_loss(losses)
        else:

            def client_update(client_batch):
                opt_state = client_opt.init(params)

                def step(carry, minibatch):
                    p, s = carry
                    loss, g = jax.value_and_grad(loss_fn)(p, minibatch)
                    p, s = client_opt.step(p, g, s, lr)
                    return (p, s), loss

                (new_p, _), losses = jax.lax.scan(
                    step, (params, opt_state), client_batch
                )
                return tree_sub(new_p, params), losses[0]

            deltas, losses = jax.vmap(client_update)(batch)
            mean_loss = _mean_loss(losses)
            inc = _flat_increment(deltas)

        new_params, new_state = server_opt.apply(params, server_state, inc)
        return new_params, new_state, mean_loss

    return round


def build_scan_round_step(
    loss_fn: Callable[[Any, dict], jax.Array],
    *,
    n_clients: int,
    local_steps: int,
    A=None,
    relay_mode: str = "faithful",
    relay_backend: str = "einsum",
    block_d: int | None = None,
    interpret=None,
    client_opt: ClientOpt = ClientOpt(kind="sgd", weight_decay=1e-4),
    server_opt: ServerOpt = ServerOpt(),
):
    """Epoch-fused variant of :func:`build_round_step`: returns
    ``scan_rounds(params, server_state, batches, taus, lr, A=None,
    active=None) -> (params', state', losses)`` running R rounds in one
    ``lax.scan`` — one dispatch per channel epoch instead of per round.

    ``batches`` leaves are stacked (R, n_clients, local_steps, b, ...) and
    ``taus`` is (R, n_clients); A and the churn mask are loop-invariant
    traced inputs (constant within an epoch, by definition of an epoch).
    The scan body *is* the single-round step, so R sequential calls of the
    per-round function produce bit-identical results.
    """
    round = build_round_step(
        loss_fn,
        n_clients=n_clients,
        local_steps=local_steps,
        A=A,
        relay_mode=relay_mode,
        relay_backend=relay_backend,
        block_d=block_d,
        interpret=interpret,
        client_opt=client_opt,
        server_opt=server_opt,
    )

    def scan_rounds(params, server_state, batches, taus, lr, A=None, active=None):
        def body(carry, xs):
            p, s = carry
            batch, tau = xs
            p, s, loss = round(p, s, batch, tau, lr, A=A, active=active)
            return (p, s), loss

        (params, server_state), losses = jax.lax.scan(
            body, (params, server_state), (batches, taus)
        )
        return params, server_state, losses

    return scan_rounds


def build_fused_scan_round_step(
    loss_fn: Callable[[Any, dict], jax.Array],
    *,
    n_clients: int,
    local_steps: int,
    A=None,
    relay_mode: str = "faithful",
    relay_backend: str = "einsum",
    block_d: int | None = None,
    interpret=None,
    client_opt: ClientOpt = ClientOpt(kind="sgd", weight_decay=1e-4),
    server_opt: ServerOpt = ServerOpt(),
    constrain_buffer: Callable | None = None,
):
    """τ-in-body variant of :func:`build_scan_round_step` (the pipelined
    engine's mesh analogue): returns ``scan_rounds(key, params,
    server_state, batches, p, lr, A=None, active=None) -> (key', params',
    state', losses)``.

    Instead of a host-sampled ``taus`` block, the step takes the RNG key and
    the uplink marginals ``p`` and draws each round's τ inside the scan body
    — per round: split the chain, ``Bernoulli(p)`` on the subkey — exactly
    the per-round driver's op order, so the realized τ stream (and the
    returned advanced key) are bit-identical to R sequential host draws.
    One device dispatch covers the whole epoch, τ included, and the key
    chain never leaves the device between epochs.
    """
    round = build_round_step(
        loss_fn,
        n_clients=n_clients,
        local_steps=local_steps,
        A=A,
        relay_mode=relay_mode,
        relay_backend=relay_backend,
        block_d=block_d,
        interpret=interpret,
        client_opt=client_opt,
        server_opt=server_opt,
        constrain_buffer=constrain_buffer,
    )

    def scan_rounds(key, params, server_state, batches, p, lr, A=None, active=None):
        def body(carry, batch):
            k, pr, s = carry
            k, sub = jax.random.split(k)
            tau = jax.random.bernoulli(sub, p).astype(jnp.float32)
            pr, s, loss = round(pr, s, batch, tau, lr, A=A, active=active)
            return (k, pr, s), loss

        (key, params, server_state), losses = jax.lax.scan(
            body, (key, params, server_state), batches
        )
        return key, params, server_state, losses

    return scan_rounds


def build_sharded_scan_round_step(
    loss_fn: Callable[[Any, dict], jax.Array],
    *,
    n_clients: int,
    local_steps: int,
    mesh,
    shard: str = "clients",
    exchange: str = "gather",
    relay_mode: str = "fused",
    relay_backend: str = "einsum",
    block_d: int | None = None,
    interpret=None,
    client_opt: ClientOpt = ClientOpt(kind="sgd", weight_decay=1e-4),
    server_opt: ServerOpt = ServerOpt(),
):
    """Multi-device variant of :func:`build_fused_scan_round_step`: same
    signature ``scan_rounds(key, params, server_state, batches, p, lr,
    A=None, active=None) -> (key', params', state', losses)``, executed
    across ``mesh``.

    ``shard="clients"`` runs the scan body in `shard_map` over the mesh's
    client axis: each of the k devices owns ``m = n_clients / k`` client
    slots (``batches`` leaves (R, n_clients, T, b, ...) sharded on dim 1),
    runs their local SGD steps, and exchanges raveled delta blocks —

    * ``exchange="gather"``: ``all_gather`` the (m, D) blocks to the full
      (n, D) buffer and reuse ``aggregation.colrel_increment_flat``
      verbatim.  Same contraction, same order ⇒ the trajectory is
      *bitwise-identical* to the single-device step.
    * ``exchange="ring"``: the block-ring collective
      (`repro.fl.ring.ring_colrel_increment_flat`): k−1 ``ppermute``
      rotations, each contributing an (m, m) block-matmul, then a τ-weighted
      ``psum``.  O(1) live buffers, but ring accumulation order ≠ einsum
      contraction order ⇒ identical only to f32 accumulation accuracy
      (documented tolerance; see docs/distributed.md).

    Model parameters, the RNG key, A, p and the churn mask stay replicated;
    every device draws the *same* τ from the same key chain, so the realized
    randomness — and the returned advanced key — match the single-device
    fused step exactly.  Churn masking composes unchanged: A and τ are
    masked before the exchange, so a departed client's block contributes
    exactly zero on either exchange.

    ``shard="d"`` keeps the single-program GSPMD formulation and shards the
    *parameter* axis instead: a `sharding.rules.flat_buffer_specs`
    constraint on the raveled (n, D) buffer partitions the relay
    contraction over the mesh's "model" axis (for models too large to
    replicate).  einsum backend only (`kernels.ops.validate_sharded_backend`).
    """
    from repro.fl import ring as ring_lib
    from repro.kernels import ops as kernel_ops
    from repro.sharding import rules as sharding_rules

    kernel_ops.validate_sharded_backend(
        relay_backend, shard=shard, exchange=exchange
    )
    if shard == "d":
        from jax.sharding import NamedSharding

        def constrain(buf):
            spec = sharding_rules.flat_buffer_specs(
                mesh, n=buf.shape[0], d=buf.shape[1]
            )
            return jax.lax.with_sharding_constraint(
                buf, NamedSharding(mesh, spec)
            )

        return build_fused_scan_round_step(
            loss_fn,
            n_clients=n_clients,
            local_steps=local_steps,
            relay_mode=relay_mode,
            relay_backend=relay_backend,
            block_d=block_d,
            interpret=interpret,
            client_opt=client_opt,
            server_opt=server_opt,
            constrain_buffer=constrain,
        )
    if shard != "clients":
        raise ValueError(f"unknown shard mode: {shard!r} (clients | d)")
    if exchange not in ("gather", "ring"):
        raise ValueError(f"unknown exchange: {exchange!r} (gather | ring)")

    from jax.sharding import PartitionSpec as P

    axis = sharding_rules.shard_axis(mesh)
    k_shards = mesh.shape[axis]
    if n_clients % k_shards != 0:
        raise ValueError(
            f"n_clients={n_clients} not divisible by the {k_shards}-device "
            f"client axis {axis!r}"
        )
    T = local_steps

    def _mean_loss(losses, active):
        if active is None:
            return jnp.mean(losses)
        a_ = jnp.asarray(active, jnp.float32)
        return jnp.sum(losses * a_) / jnp.maximum(a_.sum(), 1.0)

    def _local_scan(key, params, server_state, batches, p, lr, A, active):
        # inside shard_map: batches leaves are this device's (R, m, T, b, ...)
        # client shard; everything else is replicated.
        def body(carry, batch):
            kcur, pr, s = carry
            kcur, sub = jax.random.split(kcur)
            tau = jax.random.bernoulli(sub, p).astype(jnp.float32)

            if T == 1:
                def one(client_batch):
                    sq = jax.tree.map(lambda x: x[0], client_batch)
                    loss, g = jax.value_and_grad(loss_fn)(pr, sq)

                    def _decayed(ge, pe):
                        wd = client_opt.weight_decay
                        return ge.astype(jnp.float32) + wd * pe.astype(
                            jnp.float32
                        )

                    return jax.tree.map(_decayed, g, pr), loss

                deltas_g, losses = jax.vmap(one)(batch)
                deltas = tree_scale(-lr, deltas_g)
            else:
                def client_update(client_batch):
                    opt_state = client_opt.init(pr)

                    def step(c, minibatch):
                        p_, s_ = c
                        loss, g = jax.value_and_grad(loss_fn)(p_, minibatch)
                        p_, s_ = client_opt.step(p_, g, s_, lr)
                        return (p_, s_), loss

                    (new_p, _), losses = jax.lax.scan(
                        step, (pr, opt_state), client_batch
                    )
                    return tree_sub(new_p, pr), losses[0]

                deltas, losses = jax.vmap(client_update)(batch)

            buf_local, spec = stacked_ravel(deltas)  # (m, D)
            if exchange == "gather":
                buf = jax.lax.all_gather(buf_local, axis, axis=0, tiled=True)
                flat = aggregation.colrel_increment_flat(
                    A, tau, buf, n=n_clients, fused=(relay_mode == "fused"),
                    active=active, backend=relay_backend, block_d=block_d,
                    interpret=interpret,
                )
            else:
                w = active_weight(active, n=n_clients)
                A_eff, tau_eff = A, tau
                if active is not None:
                    a = jnp.asarray(active, jnp.float32)
                    A_eff = relay_lib.mask_relay_matrix(A, a)
                    tau_eff = tau * a
                flat = ring_lib.ring_colrel_increment_flat(
                    A_eff, tau_eff, buf_local, w=w, axis_name=axis,
                    n_shards=k_shards,
                )
            inc = tree_unravel(spec, flat, cast=False)
            losses_all = jax.lax.all_gather(losses, axis, axis=0, tiled=True)
            mean_loss = _mean_loss(losses_all, active)
            pr, s = server_opt.apply(pr, s, inc)
            return (kcur, pr, s), mean_loss

        (key, params, server_state), losses = jax.lax.scan(
            body, (key, params, server_state), batches
        )
        return key, params, server_state, losses

    def scan_rounds(key, params, server_state, batches, p, lr, A=None, active=None):
        if A is None:
            raise ValueError("no relay matrix: pass A per call")
        batch_specs = jax.tree.map(
            lambda x: P(None, axis, *([None] * (x.ndim - 2))), batches
        )
        rep = lambda tree: jax.tree.map(lambda x: P(), tree)  # noqa: E731
        in_specs = (
            P(),            # key chain (replicated: every device draws the same τ)
            rep(params),
            rep(server_state),
            batch_specs,
            P(),            # p
            P(),            # lr
            P(),            # A
            P() if active is not None else rep(active),
        )
        out_specs = (P(), rep(params), rep(server_state), P())
        return jax.shard_map(
            _local_scan,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )(key, params, server_state, batches, p, lr, A, active)

    return scan_rounds
