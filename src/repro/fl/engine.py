"""Epoch-segmented ``lax.scan`` round engine.

``FLSimulator.run_round`` dispatches one compiled step per round, so a
1000-round sweep pays ~1000 host→device round-trips even though the step
itself never retraces (A, p, τ, active are traced inputs).  Within a channel
epoch the tuple ``(A, p, active)`` is *constant* — only τ and the data
change — so whole epochs can be fused into a single ``jax.lax.scan`` over
stacked per-round streams:

    carry = (server params, server opt state)
    xs    = (batch_r, τ_r, valid_r)          # stacked over rounds
    A, lr, active                            # loop-invariant traced inputs

The scan body is the simulator's own ``_round_math``, so the fused path is
bit-identical to the per-round reference by construction (and by test:
``tests/test_scan_engine.py``).

Compile discipline
------------------
Epoch lengths vary, and a scan's length is static — scanning each epoch at
its exact length would recompile per distinct length.  The engine therefore
runs fixed-size chunks: an epoch of L rounds becomes ``L // chunk`` scans of
``chunk`` rounds plus a final padded scan whose dead rounds are masked out of
the carry (``jnp.where`` on a per-round valid flag selects the old carry
bit-exactly, so padding never perturbs real rounds).  One compile for the
chunk scan — ``trace_count`` stays at 1 across epochs of a fixed client dim
(2 when both the ``active=None`` and the masked variant are used).

Epoch orchestration lives on the host: ``run_schedule`` walks
``ChannelSchedule.segments()``, re-solves OPT-α once per segment boundary
(the adaptive policy), materializes the segment's τ/batch streams with
exactly the loop driver's RNG order, and issues one ``run_segment`` per
epoch.

Pipelined path
--------------
:class:`PipelinedScanEngine` is the next rung: the chunk body *also* draws
the τ stream (the key chain becomes part of the scan carry, so the separate
per-chunk τ dispatch disappears — exactly one device dispatch per chunk),
and all host work for the next segment (adaptive OPT-α re-solve, batch
stacking, segment sampling) runs on a background worker
(:class:`repro.channels.scheduler.SegmentPrefetcher`) while the device
executes the current chunk — JAX's async dispatch returns control to the
host immediately, so the consumer thread keeps feeding the device without
ever blocking on results.  Still bit-identical to the loop driver (same
gated key chain, same batch order, same policy call order — tested).

Sharded path
------------
:class:`ShardedScanEngine` is the top rung: the same schedule walk drives a
**multi-device** round step
(:func:`repro.fl.distributed.build_sharded_scan_round_step`) — each device
of a mesh owns a block of clients (or, in D mode, a slice of the parameter
axis), the relay exchange runs as a collective (all-gather or block-ring
``ppermute``), and staged batches are ``device_put`` straight into their
sharded layout (`repro.sharding.rules.round_batch_specs`) so no device ever
receives another device's client bytes.  One dispatch per channel epoch,
prefetched staging optional.  See docs/distributed.md for the dataflow.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import relay as relay_lib
from repro.fl.compile_watch import watching_compiles
from repro.fl.simulator import FLSimulator
from repro.obs import NULL_TRACER


def _stack_rounds(batches: list) -> Any:
    """Stack a list of per-round batch pytrees into one (R, ...) pytree:
    host-side ``np.stack`` per leaf, then a single device transfer each —
    one H2D per segment instead of one per round."""
    return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *batches)


def _pad_leading(tree: Any, pad: int) -> Any:
    """Append ``pad`` zero rounds along the leading axis of every leaf."""
    if pad == 0:
        return tree
    return jax.tree.map(
        lambda x: jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]),
        tree,
    )


def _concat_metrics(parts: list) -> Any:
    """Concatenate per-chunk metric pytrees along the round axis."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *ms: jnp.concatenate(ms), *parts)


def _trim_concat(parts: list, chunk: int) -> Any:
    """Concatenate (metrics, real_rounds) chunk pairs, trimming the padding
    off remainder chunks.  The pipelined engine defers this to segment/run
    boundaries: the slice and concatenate are *eager* device ops, and on the
    CPU backend an eager op queues behind the in-flight chunk computation —
    running them per chunk would stall the feeding thread for a full chunk's
    compute time and serialize the pipeline."""
    trimmed = []
    for metrics, real in parts:
        if real < chunk:
            metrics = jax.tree.map(lambda m, n=real: m[:n], metrics)
        trimmed.append(metrics)
    return _concat_metrics(trimmed)


class EpochScanEngine:
    """Fused multi-round execution for an :class:`FLSimulator`.

    The engine never re-implements round math: its scan body calls
    ``sim._round_math``, and a segment's remainder rounds run as one
    zero-padded, valid-masked chunk — same compiled function, no per-length
    retrace.

    ``trace_count`` counts the engine's compiles (chunk-scan traces plus any
    per-round traces of the wrapped simulator) — the scan-path analogue of
    ``FLSimulator.trace_count``.
    """

    def __init__(self, sim: FLSimulator, *, chunk: int = 32, tracer=None):
        """``chunk`` is the scan length per compiled call and should track
        the channel's coherence time: a padded chunk computes ``chunk``
        rounds regardless of how many are real, so ``chunk`` far above the
        typical epoch length trades dead compute for nothing (e.g. 2-round
        epochs under ``chunk=32`` cost 16× the math of the loop path).

        ``tracer`` (a :class:`repro.obs.Tracer`) records per-chunk dispatch
        spans plus explicit blocked-on-device fences; the fences change the
        async-dispatch overlap (observer effect), so they — like every other
        traced extra — run only when ``tracer.enabled``.  Also settable
        after construction via the ``tracer`` attribute.
        """
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        self.sim = sim
        self.chunk = int(chunk)
        self.tracer = NULL_TRACER if tracer is None else tracer
        self._scan_traces = 0
        self._chunk_fn = jax.jit(self._chunk_impl)
        self._taus_fn = jax.jit(self._taus_impl)

    @property
    def trace_count(self) -> int:
        return self._scan_traces + self.sim.trace_count

    # -- one compiled call: scan `chunk` rounds under a fixed channel -------
    def _chunk_impl(self, params, server_state, batches, taus, valid, A, lr, active):
        self._scan_traces += 1  # python-side: runs only when jit retraces

        def body(carry, xs):
            p0, s0 = carry
            batch, tau, v = xs
            p1, s1, metrics = self.sim._round_math(p0, s0, batch, tau, A, lr, active)
            # padded rounds: keep the old carry bit-exactly (v is a scalar
            # bool; where(True, new, old) passes `new` through unchanged)
            p1 = jax.tree.map(lambda a, b: jnp.where(v, a, b), p1, p0)
            s1 = jax.tree.map(lambda a, b: jnp.where(v, a, b), s1, s0)
            return (p1, s1), metrics

        (params, server_state), metrics = jax.lax.scan(
            body, (params, server_state), (batches, taus, valid)
        )
        return params, server_state, metrics

    # -- one compiled call: a chunk's τ stream from the sequential key chain
    def _taus_impl(self, key, p, valid):
        def body(k, v):
            k2, sub = jax.random.split(k)
            tau = jax.random.bernoulli(sub, p).astype(jnp.float32)
            if self.sim.strategy == "no_dropout":
                tau = jnp.ones_like(tau)
            # padded rounds must not advance the key chain — the final key
            # has to equal the loop driver's after exactly R splits
            k = jax.tree.map(lambda a, b: jnp.where(v, a, b), k2, k)
            return k, tau

        return jax.lax.scan(body, key, valid)

    def sample_taus(self, key, p, n_rounds: int):
        """A segment's τ stream, drawn in chunk-sized compiled calls but
        bit-identical to ``n_rounds`` sequential ``split`` + ``sample_tau``
        rounds (tested).  Returns ``(advanced_key, (n_rounds, n) taus)``."""
        p = jnp.asarray(p, jnp.float32)
        C = self.chunk
        parts = []
        for start in range(0, n_rounds, C):
            real = min(C, n_rounds - start)
            valid = jnp.arange(C) < real
            if self.tracer.enabled:
                with self.tracer.span("scan.taus", cat="dispatch", rounds=real):
                    key, taus = self._taus_fn(key, p, valid)
            else:
                key, taus = self._taus_fn(key, p, valid)
            parts.append(taus[:real] if real < C else taus)
        return key, (parts[0] if len(parts) == 1 else jnp.concatenate(parts))

    def run_segment(
        self, params, server_state, batches, taus, lr, *, A=None, active=None
    ):
        """Run one channel epoch: ``R`` rounds under a fixed (A, active).

        ``batches``: pytree with leaves (R, n, T, b, ...) — the epoch's data
        stream; ``taus``: (R, n) float32 — the epoch's uplink masks (drawn
        host-side, e.g. via ``sim.sample_tau``).  Dispatches
        ``ceil(R / chunk)`` compiled calls, the last one zero-padded and
        masked.  Returns ``(params, server_state, metrics)`` with every
        metric stacked over the R real rounds (padding trimmed).
        """
        A_seg = (
            self.sim.A
            if A is None
            else relay_lib.as_relay_operand(
                A, n=self.sim.n, backend=self.sim.relay_backend
            )
        )
        if A_seg is None and self.sim.strategy in ("colrel", "colrel_fused"):
            raise ValueError("colrel strategies need a relay matrix A")
        active_seg = None if active is None else jnp.asarray(active, jnp.float32)
        taus = jnp.asarray(taus, jnp.float32)
        R, C = int(taus.shape[0]), self.chunk
        if R == 0:
            raise ValueError("empty segment")
        parts = []
        for start in range(0, R, C):
            stop = min(start + C, R)
            pad = C - (stop - start)
            bs = _pad_leading(jax.tree.map(lambda x: x[start:stop], batches), pad)
            ts = _pad_leading(taus[start:stop], pad)
            valid = jnp.arange(C) < (stop - start)
            if self.tracer.enabled:
                with self.tracer.span(
                    "scan.chunk", cat="dispatch", rounds=stop - start
                ):
                    params, server_state, metrics = self._chunk_fn(
                        params, server_state, bs, ts, valid, A_seg, lr, active_seg
                    )
                # explicit fence: bills the in-flight chunk to the device
                # phase (untraced runs never block here — async dispatch)
                with self.tracer.span("scan.device", cat="device", track="device"):
                    jax.block_until_ready(metrics)
            else:
                params, server_state, metrics = self._chunk_fn(
                    params, server_state, bs, ts, valid, A_seg, lr, active_seg
                )
            if pad:
                metrics = jax.tree.map(lambda m: m[: stop - start], metrics)
            parts.append(metrics)
        return params, server_state, _concat_metrics(parts)

    @watching_compiles
    def run_schedule(
        self,
        key,
        params,
        server_state,
        *,
        schedule,
        rounds,
        next_batch: Callable[[], Any],
        lr,
        policy=None,
        on_segment: Callable | None = None,
    ):
        """Drive a :class:`ChannelSchedule` for ``rounds`` rounds, one
        ``run_segment`` per channel epoch.

        Mirrors the per-round loop driver exactly: the key chain advances
        once per round in round order (``sample_taus``), ``next_batch()`` is
        called once per round in round order, and ``policy.relay_matrix``
        is evaluated once per segment — the same value the loop's per-round
        calls get from the policy's cache.  The trajectory is therefore
        bit-identical to calling ``run_round`` round by round.

        ``next_batch`` returns one round's stacked batch pytree
        (n, T, b, ...).  ``on_segment(segment, params, metrics)`` is an
        optional host callback per epoch (evaluation hooks).  Returns
        ``(params, server_state, metrics, key)`` with metrics stacked over
        all rounds.
        """
        all_metrics = []
        for seg in schedule.segments(rounds):
            A = policy.relay_matrix(seg.state) if policy is not None else None
            # materialize the segment chunk-by-chunk: the scan consumes at
            # most `chunk` rounds per compiled call, so never hold more than
            # one chunk of batches in memory (a single-epoch 500-round
            # schedule must not stack 500 rounds of data at once)
            seg_metrics = []
            for start in range(0, seg.n_rounds, self.chunk):
                window = min(self.chunk, seg.n_rounds - start)
                key, taus = self.sample_taus(key, seg.p, window)
                if self.tracer.enabled:
                    with self.tracer.span(
                        "scan.stage",
                        cat="stage",
                        epoch=seg.epoch_id,
                        rounds=window,
                    ):
                        stacked = _stack_rounds(
                            [next_batch() for _ in range(window)]
                        )
                else:
                    stacked = _stack_rounds([next_batch() for _ in range(window)])
                params, server_state, metrics = self.run_segment(
                    params,
                    server_state,
                    stacked,
                    taus,
                    lr,
                    A=A,
                    active=seg.active,
                )
                seg_metrics.append(metrics)
            metrics = _concat_metrics(seg_metrics)
            all_metrics.append(metrics)
            if on_segment is not None:
                on_segment(seg, params, metrics)
        return params, server_state, _concat_metrics(all_metrics), key


class PipelinedScanEngine:
    """Pipelined epoch execution: fused chunk body + async host/device
    overlap.

    Two changes over :class:`EpochScanEngine`, one on each side of the
    dispatch boundary:

    * **Device** — the τ stream is drawn *inside* the chunk scan: the RNG
      key chain joins the carry, each round splits it, samples
      ``Bernoulli(p)`` and gates the advance on the round's valid flag
      (padded rounds leave the chain untouched, exactly like the loop
      driver's ``split``-per-round order).  The separate per-chunk
      ``_taus_fn`` dispatch is gone — **one compiled dispatch per chunk**,
      counted by ``dispatches``.
    * **Host** — the schedule walk, the adaptive OPT-α re-solves and the
      per-chunk batch staging (stack + zero-pad + H2D, all numpy-side) run
      through a :class:`~repro.channels.scheduler.SegmentPrefetcher`.
      Because a chunk dispatch returns before the device finishes (async
      dispatch), staging epoch k+1 overlaps the device's in-flight chunk of
      epoch k — double-buffered inline by default, or ``prefetch_depth``
      chunks ahead on a worker thread (``prefetch="thread"``).  Epoch k+1's
      host work hides behind epoch k's device work; measured as
      ``prefetch_stats.overlap_fraction``.  The consumer loop itself runs
      no eager jnp ops — on the CPU backend those queue behind the
      in-flight computation and would re-serialize the pipeline (padding
      and valid masks are built host-side; metric trims/concats are
      deferred to segment/run boundaries).

    Everything that makes the scan engine trustworthy carries over
    unchanged: the body calls ``sim._round_math`` (bit-identity with the
    loop by construction and by test), fixed-size chunks with valid-masked
    zero padding keep ``trace_count ≤ 2``, and the key chain, batch order
    and policy call order are the serial driver's exactly.
    """

    def __init__(
        self,
        sim: FLSimulator,
        *,
        chunk: int = 32,
        prefetch: str = "inline",
        prefetch_depth: int = 2,
        tracer=None,
    ):
        """``prefetch`` picks the staging mode (see
        :class:`~repro.channels.scheduler.SegmentPrefetcher`): ``"inline"``
        (default) software-pipelines staging behind async dispatch on one
        thread — the right choice on CPU hosts, where a staging thread
        mostly fights the dispatch thread for the GIL; ``"thread"`` stages
        on a worker thread ``prefetch_depth`` chunks ahead — worth trying
        on real accelerators.

        ``tracer`` flows to the prefetcher (stage/h2d spans on the
        ``prefetcher`` track) and adds a dispatch span per chunk on the
        consumer side; the three spans of one chunk share its ``chunk``
        attr, the chunk's sequence number in the run, and the dispatch span
        says how the round maps its clients (``client_map``, see
        :meth:`FLSimulator.map_clients`).  No fence: the
        device's own time comes from a device trace, and the pipeline runs
        as it does untraced.  Compiles inside the run become ``compile``
        spans (:mod:`repro.fl.compile_watch`).  Also settable after
        construction via the ``tracer`` attribute."""
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        if prefetch not in ("inline", "thread"):
            raise ValueError(f"unknown prefetch mode: {prefetch!r}")
        self.sim = sim
        self.chunk = int(chunk)
        self.prefetch = prefetch
        self.prefetch_depth = int(prefetch_depth)
        self.tracer = NULL_TRACER if tracer is None else tracer
        self._scan_traces = 0
        # per-run counters (reset by run_schedule, like prefetch_stats):
        # compiled chunk calls — exactly one per chunk
        self.dispatches = 0
        self.prefetch_stats = None  # PrefetchStats of the latest run
        self._chunk_fn = jax.jit(self._chunk_impl)

    @property
    def trace_count(self) -> int:
        return self._scan_traces + self.sim.trace_count

    # -- the fully-fused chunk: τ draw + A apply + round math, one dispatch --
    def _chunk_impl(self, key, params, server_state, batches, valid, A, p, lr, active):
        self._scan_traces += 1  # python-side: runs only when jit retraces

        # the loop driver's per-round draw is split-then-Bernoulli(p) on the
        # subkey.  Only the *split chain* is inherently sequential, so run it
        # as a (cheap, key-only) scan and draw all rounds' τ in one batched
        # Bernoulli over the stacked subkeys — vmap of a PRNG draw over
        # distinct keys produces bit-identical samples to sequential calls,
        # and keeping the draws out of the round scan keeps them off its
        # serial critical path.  Still a single compiled dispatch.
        def key_step(k, v):
            k2, sub = jax.random.split(k)
            # padded rounds must not advance the chain — the final key has
            # to equal the loop driver's after exactly R splits
            k = jax.tree.map(lambda a, b: jnp.where(v, a, b), k2, k)
            return k, sub

        key, subs = jax.lax.scan(key_step, key, valid)
        taus = jax.vmap(lambda s: jax.random.bernoulli(s, p))(subs)
        taus = taus.astype(jnp.float32)
        if self.sim.strategy == "no_dropout":
            taus = jnp.ones_like(taus)

        def body(carry, xs):
            p0, s0 = carry
            batch, tau, v = xs
            p1, s1, metrics = self.sim._round_math(p0, s0, batch, tau, A, lr, active)
            # padded rounds: keep the old carry bit-exactly
            p1 = jax.tree.map(lambda a, b: jnp.where(v, a, b), p1, p0)
            s1 = jax.tree.map(lambda a, b: jnp.where(v, a, b), s1, s0)
            return (p1, s1), metrics

        (params, server_state), metrics = jax.lax.scan(
            body, (params, server_state), (batches, taus, valid)
        )
        return key, params, server_state, metrics

    @watching_compiles
    def run_schedule(
        self,
        key,
        params,
        server_state,
        *,
        schedule,
        rounds,
        next_batch: Callable[[], Any],
        lr,
        policy=None,
        on_segment: Callable | None = None,
    ):
        """Drive a ``ChannelSchedule`` for ``rounds`` rounds — same contract
        and bit-identical trajectory as :meth:`EpochScanEngine.run_schedule`
        and the per-round loop, but with host staging prefetched and τ fused
        into the chunk dispatch.  ``on_segment(segment, params, metrics)``
        forces a device sync per epoch (it hands over concrete params), so
        leave it unset on pure-throughput runs.  Returns
        ``(params, server_state, metrics, key)``.
        """
        from repro.channels.scheduler import SegmentPrefetcher

        C = self.chunk
        self.dispatches = 0
        prefetcher = SegmentPrefetcher(
            schedule,
            rounds,
            chunk=C,
            next_batch=next_batch,
            policy=policy,
            depth=self.prefetch_depth,
            pad_to_chunk=True,  # remainder chunks arrive zero-padded (numpy)
            threaded=self.prefetch == "thread",
            tracer=self.tracer,
        )
        # The consumer loop must never run an *eager* jnp op: on the CPU
        # backend those queue behind the in-flight chunk and would stall the
        # pipeline for a full chunk's compute.  Everything here is either
        # jnp.asarray of host data (non-blocking) or the compiled dispatch
        # itself; metric trimming/concatenation is deferred (_trim_concat).
        all_parts: list = []  # (metrics, real_rounds) per chunk, in order
        seg_parts: list = []
        seg_id = A_seg = p_seg = active_seg = None
        valid_cache: dict = {}
        try:
            for item in prefetcher:
                seg = item.segment
                if seg.epoch_id != seg_id:
                    # channel values are loop-invariant within a segment:
                    # one device conversion per epoch, not per chunk
                    seg_id = seg.epoch_id
                    A_seg = (
                        self.sim.A
                        if item.A is None
                        else relay_lib.as_relay_operand(
                            item.A, n=self.sim.n, backend=self.sim.relay_backend
                        )
                    )
                    if A_seg is None and self.sim.strategy in (
                        "colrel",
                        "colrel_fused",
                    ):
                        raise ValueError("colrel strategies need a relay matrix A")
                    active_seg = (
                        None
                        if seg.active is None
                        else jnp.asarray(seg.active, jnp.float32)
                    )
                    p_seg = jnp.asarray(seg.p, jnp.float32)
                real = item.n_rounds
                valid = valid_cache.get(real)
                if valid is None:
                    valid = valid_cache[real] = jnp.asarray(np.arange(C) < real)
                if self.tracer.enabled:
                    with self.tracer.span(
                        "pipelined.chunk",
                        cat="dispatch",
                        epoch=seg.epoch_id,
                        rounds=real,
                        chunk=self.dispatches,
                    ) as span:
                        key, params, server_state, metrics = self._chunk_fn(
                            key,
                            params,
                            server_state,
                            item.batches,
                            valid,
                            A_seg,
                            p_seg,
                            lr,
                            active_seg,
                        )
                        # known once the first dispatch has traced the round
                        span.set(client_map=self.sim.client_map)
                else:
                    key, params, server_state, metrics = self._chunk_fn(
                        key,
                        params,
                        server_state,
                        item.batches,
                        valid,
                        A_seg,
                        p_seg,
                        lr,
                        active_seg,
                    )
                self.dispatches += 1
                prefetcher.note_inflight(metrics["loss"])
                seg_parts.append((metrics, real))
                if item.last_in_segment:
                    if on_segment is not None:
                        seg_metrics = _trim_concat(seg_parts, C)
                        on_segment(seg, params, seg_metrics)
                        # already trimmed: the final _trim_concat must not
                        # re-slice it (its round count may exceed C)
                        all_parts.append((seg_metrics, C))
                    else:
                        all_parts.extend(seg_parts)
                    seg_parts = []
        finally:
            prefetcher.close()
            self.prefetch_stats = prefetcher.stats
        if self.tracer.enabled:
            self.tracer.count("pipelined.dispatches", self.dispatches)
        return params, server_state, _trim_concat(all_parts, C), key


class ShardedScanEngine:
    """Schedule driver for the multi-device sharded round step.

    Wraps a ``scan_rounds`` built by
    :func:`repro.fl.distributed.build_sharded_scan_round_step` and drives a
    ``ChannelSchedule`` one **whole epoch per compiled dispatch** — the
    channel tuple (A, p, active) is constant within an epoch, so the epoch
    is the natural scan unit and no valid-mask padding is needed (a scan's
    length is static, so schedules should keep epoch lengths uniform —
    coherence dividing the horizon — to hold ``trace_count`` at 1, or 2
    when both churned and churn-free epochs occur).

    The host side differs from the single-device engines in one way:
    staged batches are *placed*, not copied — each chunk is ``device_put``
    under the `NamedSharding` that
    :func:`repro.sharding.rules.round_batch_specs` resolves for the mesh,
    so the transfer scatters every device exactly its clients' bytes and
    the dispatch never reshards its input.  (In ``shard="d"`` mode batches
    stay replicated — GSPMD shards the delta buffer instead — so placement
    falls back to the plain transfer.)

    ``prefetch`` picks the staging mode: ``"serial"`` stages each epoch
    inline before its dispatch (the scan-engine analogue); ``"inline"`` /
    ``"thread"`` stage through a
    :class:`~repro.channels.scheduler.SegmentPrefetcher` (its ``place``
    hook carries the sharded placement), overlapping epoch k+1's OPT-α
    re-solve + stacking + scatter with epoch k's device execution —
    measured in ``prefetch_stats``.

    The trajectory matches the single-device fused engines to the exchange
    mode's guarantee: bitwise for ``exchange="gather"`` on the same local
    math, f32-accumulation tolerance for ``exchange="ring"`` (see
    `repro.fl.ring`).  Key chain, batch order and policy call order are the
    serial driver's exactly.
    """

    def __init__(
        self,
        step_fn: Callable,
        *,
        mesh,
        shard: str = "clients",
        prefetch: str = "inline",
        prefetch_depth: int = 2,
        tracer=None,
    ):
        """``step_fn`` is the ``scan_rounds(key, params, server_state,
        batches, p, lr, A=..., active=...)`` callable from
        ``build_sharded_scan_round_step`` (built on the same ``mesh`` and
        ``shard`` mode).  ``tracer`` adds per-epoch dispatch + device-fence
        spans and the prefetcher's stage/h2d spans."""
        if prefetch not in ("serial", "inline", "thread"):
            raise ValueError(f"unknown prefetch mode: {prefetch!r}")
        if shard not in ("clients", "d"):
            raise ValueError(f"unknown shard mode: {shard!r} (clients | d)")
        self.mesh = mesh
        self.shard = shard
        self.prefetch = prefetch
        self.prefetch_depth = int(prefetch_depth)
        self.tracer = NULL_TRACER if tracer is None else tracer
        self._step_fn = step_fn
        self._scan_traces = 0
        self.dispatches = 0
        self.prefetch_stats = None
        self._fn = jax.jit(self._epoch_impl)

    @property
    def trace_count(self) -> int:
        return self._scan_traces

    def _epoch_impl(self, key, params, server_state, batches, p, lr, A, active):
        self._scan_traces += 1  # python-side: runs only when jit retraces
        return self._step_fn(
            key, params, server_state, batches, p, lr, A=A, active=active
        )

    def place(self, host):
        """Staging-side placement: host-stacked chunk → mesh layout.  In
        clients mode, ``device_put`` under ``round_batch_specs`` scatters
        dim 1 over the client axis; in D mode batches are replicated and
        the plain per-leaf transfer suffices."""
        from repro.sharding import rules

        if self.shard != "clients":
            return jax.tree.map(jnp.asarray, host)
        specs = rules.round_batch_specs(host, self.mesh)
        return jax.device_put(host, rules.to_shardings(specs, self.mesh))

    def _dispatch(self, key, params, server_state, batches, seg, lr, A):
        active = None if seg.active is None else jnp.asarray(seg.active, jnp.float32)
        p = jnp.asarray(seg.p, jnp.float32)
        if self.tracer.enabled:
            with self.tracer.span(
                "shard.epoch",
                cat="dispatch",
                epoch=seg.epoch_id,
                rounds=seg.n_rounds,
            ):
                out = self._fn(key, params, server_state, batches, p, lr, A, active)
        else:
            out = self._fn(key, params, server_state, batches, p, lr, A, active)
        self.dispatches += 1
        return out

    @watching_compiles
    def run_schedule(
        self,
        key,
        params,
        server_state,
        *,
        schedule,
        rounds,
        next_batch: Callable[[], Any],
        lr,
        policy=None,
        on_segment: Callable | None = None,
    ):
        """Drive a ``ChannelSchedule`` for ``rounds`` rounds across the
        mesh — same contract as :meth:`EpochScanEngine.run_schedule`.  A
        relay policy is required (the sharded step is colrel-only).
        Returns ``(params, server_state, metrics, key)``; ``metrics`` is
        ``{"loss": (rounds,)}`` — the active-masked mean client loss per
        round, identical across devices by construction."""
        if policy is None:
            raise ValueError("the sharded engine needs a relay policy")
        self.dispatches = 0
        self.prefetch_stats = None
        losses: list = []
        # the carry goes onto the mesh, replicated, before the first
        # dispatch: an array's mesh is part of its abstract value, so a
        # carry that reached the mesh only as the first epoch's output would
        # retrace (and recompile) the second epoch
        key, params, server_state = jax.device_put(
            (key, params, server_state),
            jax.sharding.NamedSharding(self.mesh, jax.sharding.PartitionSpec()),
        )
        if self.prefetch == "serial":
            for seg in schedule.segments(rounds):
                A = jnp.asarray(policy.relay_matrix(seg.state), jnp.float32)
                if self.tracer.enabled:
                    with self.tracer.span(
                        "shard.stage", cat="stage", epoch=seg.epoch_id
                    ):
                        host = [next_batch() for _ in range(seg.n_rounds)]
                        stacked = self.place(
                            jax.tree.map(lambda *xs: np.stack(xs), *host)
                        )
                else:
                    host = [next_batch() for _ in range(seg.n_rounds)]
                    stacked = self.place(
                        jax.tree.map(lambda *xs: np.stack(xs), *host)
                    )
                key, params, server_state, seg_losses = self._dispatch(
                    key, params, server_state, stacked, seg, lr, A
                )
                if self.tracer.enabled:
                    with self.tracer.span(
                        "shard.device", cat="device", track="device",
                        epoch=seg.epoch_id,
                    ):
                        jax.block_until_ready(seg_losses)
                losses.append(seg_losses)
                if on_segment is not None:
                    on_segment(seg, params, {"loss": seg_losses})
        else:
            from repro.channels.scheduler import SegmentPrefetcher

            # chunk = the full horizon ⇒ exactly one staged item per
            # segment (a segment never exceeds the horizon): the sharded
            # step scans whole epochs, so staging must hand it whole epochs
            prefetcher = SegmentPrefetcher(
                schedule,
                rounds,
                chunk=rounds,
                next_batch=next_batch,
                policy=policy,
                depth=self.prefetch_depth,
                threaded=self.prefetch == "thread",
                tracer=self.tracer,
                place=self.place,
            )
            try:
                for item in prefetcher:
                    seg = item.segment
                    A = jnp.asarray(item.A, jnp.float32)
                    key, params, server_state, seg_losses = self._dispatch(
                        key, params, server_state, item.batches, seg, lr, A
                    )
                    prefetcher.note_inflight(seg_losses)
                    if self.tracer.enabled:
                        with self.tracer.span(
                            "shard.device", cat="device", track="device",
                            epoch=seg.epoch_id,
                        ):
                            jax.block_until_ready(seg_losses)
                    losses.append(seg_losses)
                    if on_segment is not None:
                        on_segment(seg, params, {"loss": seg_losses})
            finally:
                prefetcher.close()
            self.prefetch_stats = prefetcher.stats
        if self.tracer.enabled:
            self.tracer.count("shard.dispatches", self.dispatches)
        metrics = {
            "loss": losses[0] if len(losses) == 1 else jnp.concatenate(losses)
        }
        return params, server_state, metrics, key


def run_rounds_loop(
    sim: FLSimulator,
    key,
    params,
    server_state,
    *,
    schedule,
    rounds,
    next_batch: Callable[[], Any],
    lr,
    policy=None,
    on_round: Callable | None = None,
    before_round: Callable | None = None,
    tracer=None,
):
    """The per-round reference driver: the exact loop the figure benchmarks
    run — one dispatch per round and, like every existing driver, a host
    read of the round's loss (``float(...)``, a device sync per round: the
    dispatch-bound regime the scan engine exists to remove).  Factored out
    so loop-vs-scan comparisons share one definition.  ``tracer`` records
    per-round stage/dispatch/sync spans (the loop already syncs per round,
    so tracing adds no extra fence here).  ``before_round(state, sub,
    params, batch, A)`` sees each round's inputs before it runs, and
    ``on_round(round, params)`` its result.
    Returns ``(params, server_state, per_round_metrics, key)``."""
    tracer = NULL_TRACER if tracer is None else tracer
    all_metrics = []
    for state in schedule.rounds(rounds):
        A = policy.relay_matrix(state) if policy is not None else None
        key, sub = jax.random.split(key)
        if tracer.enabled:
            with tracer.span("loop.stage", cat="stage", round=state.round):
                batch = jax.tree.map(jnp.asarray, next_batch())
            if before_round is not None:
                before_round(state, sub, params, batch, A)
            with tracer.span("loop.round", cat="dispatch", round=state.round):
                params, server_state, m = sim.run_round(
                    sub,
                    params,
                    server_state,
                    batch,
                    lr,
                    A=A,
                    p=state.p,
                    active=state.active,
                )
            with tracer.span(
                "loop.sync", cat="device", track="device", round=state.round
            ):
                float(m["loss"])  # the loop driver's per-round host sync
        else:
            batch = jax.tree.map(jnp.asarray, next_batch())
            if before_round is not None:
                before_round(state, sub, params, batch, A)
            params, server_state, m = sim.run_round(
                sub,
                params,
                server_state,
                batch,
                lr,
                A=A,
                p=state.p,
                active=state.active,
            )
            float(m["loss"])  # the per-round host sync the loop driver models
        all_metrics.append(m)
        if on_round is not None:
            on_round(state.round, params)
    metrics = jax.tree.map(lambda *ms: jnp.stack(ms), *all_metrics)
    return params, server_state, metrics, key
