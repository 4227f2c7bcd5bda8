"""Single-host FL simulator (paper-scale: n≈10 clients, small models).

Implements Algs. 1 + 2 literally: per round —
  broadcast x^(r) → T local SGD steps per client (map over clients) →
  D2D relay Δx̃ = A·Δx → Bernoulli τ mask → blind PS aggregation → server opt.

Used by the paper-figure benchmarks (Figs. 2-4), the convergence tests and
the examples.  The whole round is one jitted function.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import aggregation
from repro.core import relay as relay_lib
from repro.core.aggregation import ServerOpt
from repro.optim.sgd import ClientOpt
from repro.utils import stacked_ravel, tree_sub, tree_unravel


def _abstract(x, skip=0):
    """``x``'s shape (less its ``skip`` leading axes) and dtype, no value."""
    return jax.ShapeDtypeStruct(jnp.shape(x)[skip:], jnp.result_type(x))


def _has_conv(jaxpr) -> bool:
    """Whether ``jaxpr`` (a ``Jaxpr`` or ``ClosedJaxpr``) runs a convolution,
    looking into every sub-jaxpr an equation carries (``pjit``,
    ``custom_jvp_call``, ``scan``, ``cond``'s branches, ...)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            return True
        for value in eqn.params.values():
            subs = value if isinstance(value, (tuple, list)) else (value,)
            if any(
                isinstance(sub, (Jaxpr, ClosedJaxpr)) and _has_conv(sub)
                for sub in subs
            ):
                return True
    return False


def _metrics(loss, tau, delta_norm):
    """Round metrics as a plain dict (jit-friendly)."""
    return {"loss": loss, "tau": tau, "delta_norm": delta_norm}


class FLSimulator:
    """strategy ∈ {colrel, colrel_fused, fedavg_blind, fedavg_nonblind,
    no_dropout}; A is required for the colrel strategies.

    The relay matrix A and the connectivity vector p are *round inputs*: a
    time-varying channel (``repro.channels``) may pass fresh values to
    ``run_round`` every round without retracing the jitted step — A enters the
    compiled function as a traced argument, never a closure constant.  The
    values given at construction are only defaults.  ``trace_count`` counts
    actual retraces (it should stay at 1 across channel epochs of fixed n).

    Client churn: ``n_clients`` is the *padded* client dimension ``n_max``.
    Passing ``run_round(..., active=mask)`` with a (n_max,) 0/1 mask runs the
    round over only the live clients — inactive slots still compute a local
    update (fixed shapes), but contribute exactly zero to the PS increment
    and are excluded from the metrics; the blind weight renormalizes to
    1/n_active.  The mask is traced, so clients may join/leave every round
    while ``trace_count`` stays at 1.  ``active=None`` (default) is the
    full-membership path, bit-identical to the fixed-n formulation.

    ``relay_backend`` ∈ {einsum, pallas, pallas_fused} picks the engine for
    the relay∘aggregate contraction over the raveled ``(n, D)`` delta buffer
    (``repro.kernels``); einsum is the pure-XLA reference.  ``block_d`` /
    ``interpret`` tune the Pallas kernel (None ⇒ defaults).

    ``run_round`` is the per-round reference path (one dispatch per round).
    For long horizons, :class:`repro.fl.engine.EpochScanEngine` fuses whole
    channel epochs into ``lax.scan`` calls over the same ``_round_math``
    (and :class:`repro.fl.engine.PipelinedScanEngine` additionally draws τ
    inside the chunk and prefetches the host work), bit-identical to calling
    ``run_round`` round by round.
    """

    def __init__(
        self,
        loss_fn: Callable[[Any, dict], jax.Array],
        *,
        n_clients: int,
        strategy: str = "colrel",
        A: np.ndarray | None = None,
        p: np.ndarray | None = None,
        local_steps: int = 8,
        client_opt: ClientOpt = ClientOpt(kind="sgd", weight_decay=1e-4),
        server_opt: ServerOpt = ServerOpt(),
        relay_backend: str = "einsum",
        block_d: int | None = None,
        interpret=None,
    ):
        self.loss_fn = loss_fn
        self.n = n_clients
        self.T = local_steps
        self.client_opt = client_opt
        self.server_opt = server_opt
        self.strategy = strategy
        self.relay_backend = relay_backend
        self.p = (
            jnp.asarray(p, jnp.float32) if p is not None else jnp.ones((n_clients,))
        )
        self.A = relay_lib.as_relay_operand(A, n=n_clients, backend=relay_backend)
        self.aggregator = aggregation.make_aggregator(
            strategy,
            n=n_clients,
            relay_backend=relay_backend,
            block_d=block_d,
            interpret=interpret,
        )
        self.trace_count = 0
        # how map_clients maps _client_update over the clients ("sequential"
        # or "vmap"); decided from the loss at its first trace
        self.client_map: str | None = None
        self._round = jax.jit(self._round_impl)

    # -- one client: T local SGD steps from the broadcast global model -----
    def _client_update(self, params, client_batch, lr):
        opt_state = self.client_opt.init(params)

        def step(carry, minibatch):
            p, s = carry
            loss, g = jax.value_and_grad(self.loss_fn)(p, minibatch)
            p, s = self.client_opt.step(p, g, s, lr)
            return (p, s), loss

        (new_params, _), losses = jax.lax.scan(step, (params, opt_state), client_batch)
        return tree_sub(new_params, params), losses[0]

    def map_clients(self, params, batch, lr):
        """``_client_update`` over every client from the broadcast
        ``params``: ``(deltas, losses)``, leaves stacked ``(n, ...)``.

        A loss that runs a convolution maps the clients one after another
        (``lax.map``): under ``vmap`` each client's own weights turn every
        convolution into an n-way grouped one, with layout transposes
        around it and its weight gradient off the MXU.  Every other loss
        keeps ``vmap``, where per-client weights become a batched
        ``dot_general``.  The choice is made once, from the loss traced on
        one client's minibatch, and kept in ``client_map``."""
        if self.client_map is None:
            # abstract shapes only: leaves of ``batch`` are (n, T, b, ...)
            jaxpr = jax.make_jaxpr(self.loss_fn)(
                jax.tree.map(_abstract, params),
                jax.tree.map(lambda x: _abstract(x, skip=2), batch),
            )
            self.client_map = "sequential" if _has_conv(jaxpr) else "vmap"
        if self.client_map == "sequential":
            return jax.lax.map(lambda b: self._client_update(params, b, lr), batch)
        return jax.vmap(self._client_update, in_axes=(None, 0, None))(params, batch, lr)

    def local_updates(self, params, batch, lr):
        """Every client's T local steps from the broadcast ``params``
        (:meth:`map_clients`): ``(buf, spec, losses)`` — the raveled (n, D)
        delta buffer, its :class:`~repro.utils.trees.TreeSpec` and the
        per-client losses.  The deltas are raveled once, so the aggregation
        hot spot (and the kernel backends behind it) see one contiguous
        buffer while the clients ran on the structured view."""
        with jax.named_scope("local_train"):
            deltas, losses = self.map_clients(params, batch, lr)
        with jax.named_scope("ravel"):
            buf, spec = stacked_ravel(deltas)
        return buf, spec, losses

    def _round_impl(self, params, server_state, batch, tau, A, lr, active):
        self.trace_count += 1  # python-side: runs only when jit retraces
        return self._round_math(params, server_state, batch, tau, A, lr, active)

    def _round_math(self, params, server_state, batch, tau, A, lr, active):
        """The round as a pure function — traced both by the per-round jit
        (``run_round``) and by the epoch-segmented scan engines
        (``repro.fl.engine``), so all paths share one definition and
        stay bit-identical by construction.

        Its device work is named by layer (``jax.named_scope``, metadata
        only): ``local_train`` (the clients' local steps), ``ravel`` (the
        delta buffer's ravel and the increment's unravel), ``aggregate``
        (relay and PS aggregation) and ``server_update``; a device trace
        finds each op's layer in its name-scope path."""
        buf, spec, losses = self.local_updates(params, batch, lr)
        with jax.named_scope("aggregate"):
            flat_inc = self.aggregator.flat_fn(tau, buf, A, active)
        with jax.named_scope("ravel"):
            increment = tree_unravel(spec, flat_inc, cast=False)
        with jax.named_scope("server_update"):
            new_params, new_state = self.server_opt.apply(
                params, server_state, increment
            )

        # per-client ‖Δ‖² falls out of the buffer for free (one row-sum)
        per_client_dn = jnp.sum(buf * buf, axis=1)
        if active is None:
            mean_loss, dn = jnp.mean(losses), jnp.mean(per_client_dn)
        else:
            # churn: metrics average over the live clients only (a padded
            # slot's local run is dead compute and must not skew them)
            a = jnp.asarray(active, jnp.float32)
            denom = jnp.maximum(a.sum(), 1.0)
            mean_loss = jnp.sum(losses * a) / denom
            dn = jnp.sum(per_client_dn * a) / denom
            tau = tau * a
        return new_params, new_state, _metrics(mean_loss, tau, jnp.sqrt(dn))

    def run_round(
        self, key, params, server_state, batch, lr, *, A=None, p=None, active=None
    ):
        """batch: pytree with leaves (n, T, b, ...).

        ``A`` / ``p`` override the construction-time channel for this round
        (time-varying channels); both enter the jitted step by value only.
        ``active`` is the churn mask over the padded client dimension (see
        class docstring) — also by value, so membership changes don't retrace.
        """
        tau, A_round, active_round = self.round_inputs(key, A=A, p=p, active=active)
        return self._round(params, server_state, batch, tau, A_round, lr, active_round)

    def round_inputs(self, key, *, A=None, p=None, active=None):
        """``(tau, A, active)`` as ``run_round`` feeds them to the round math:
        the uplink mask drawn from ``key``, the relay matrix as this
        simulator's backend takes it, the churn mask as f32."""
        tau = self.sample_tau(key, p)
        A_round = (
            self.A
            if A is None
            else relay_lib.as_relay_operand(A, n=self.n, backend=self.relay_backend)
        )
        active_round = None if active is None else jnp.asarray(active, jnp.float32)
        return tau, A_round, active_round

    def sample_tau(self, key, p=None):
        """One round's uplink mask, exactly as ``run_round`` draws it.  The
        epoch-segmented scan engine calls this per round to materialize a
        segment's τ stream, so loop and scan consume identical randomness."""
        p_round = self.p if p is None else jnp.asarray(p, jnp.float32)
        tau = jax.random.bernoulli(key, p_round).astype(jnp.float32)
        if self.strategy == "no_dropout":
            tau = jnp.ones_like(tau)
        return tau

    def init_server_state(self, params):
        return self.server_opt.init(params)
