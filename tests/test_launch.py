"""End-to-end coverage of the continuous-training service: the
:class:`~repro.launch.train.ContinuousTrainer` burst driver, checkpoint
publication, resume, and the :class:`~repro.launch.serve.SnapshotEvalLoop`
live-eval side — all at reduced config on CPU, fast-suite sized.

The key contracts:

* bursting through the trainer is the *same trajectory* as one uninterrupted
  engine call (the stream objects are shared and advance only when rounds
  run) — checked bitwise against ``run_rounds_loop``;
* ``restore_latest`` + ``advance_stream`` resumes a crashed run bitwise
  (sync engines);
* the serve loop sees exactly the snapshots the trainer publishes, in
  order, and scores them with the caller's eval function.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import channels, checkpoint
from repro.channels.delay import GeometricDelays
from repro.core import topology
from repro.core.aggregation import ServerOpt
from repro.fl.engine import run_rounds_loop
from repro.fl.simulator import FLSimulator
from repro.launch.serve import SnapshotEvalLoop
from repro.launch.train import ContinuousTrainer, build_connectivity, build_topology

N = 6
DIM = 4


def _loss_fn(params, batch):
    diff = params["x"][None, :] - batch["c"]
    return 0.5 * jnp.mean(jnp.sum(diff ** 2, axis=-1))


def _stream(seed=42):
    rng = np.random.default_rng(seed)

    def next_batch():
        return {"c": rng.standard_normal((N, 2, 4, DIM)).astype(np.float32)}

    return next_batch


def _schedule():
    return channels.StaticChannel(topology.ring(N, 2), np.full(N, 0.8))


def _sim(momentum=0.9):
    return FLSimulator(
        _loss_fn, n_clients=N, strategy="fedavg_blind",
        server_opt=ServerOpt(momentum=momentum))


def _trainer(sim, **kw):
    kw.setdefault("schedule", _schedule())
    kw.setdefault("next_batch", _stream())
    kw.setdefault("lr", 0.1)
    return ContinuousTrainer(sim, **kw)


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _params0():
    return {"x": jnp.ones((DIM,))}


def test_trainer_bursts_match_one_uninterrupted_run(tmp_path):
    """15 rounds in publish-sized bursts of 5 ≡ one 15-round loop call,
    bitwise — and each burst published a snapshot."""
    sim = _sim()
    ref_p, ref_ss, ref_metrics, ref_key = run_rounds_loop(
        sim, jax.random.key(1), _params0(), sim.init_server_state(_params0()),
        schedule=_schedule(), rounds=15, next_batch=_stream(), lr=0.1)

    d = str(tmp_path / "ckpts")
    published = []
    trainer = _trainer(_sim(), ckpt_dir=d, publish_every=5, keep=0)
    trainer.init(_params0(), jax.random.key(1))
    metrics = trainer.run(15, on_publish=lambda p, r: published.append((p, r)))

    assert trainer.round == 15
    assert _tree_equal(ref_p, trainer.params)
    assert _tree_equal(ref_ss, trainer.server_state)
    assert _tree_equal(ref_metrics, metrics)
    assert np.array_equal(
        jax.random.key_data(ref_key), jax.random.key_data(trainer.key))
    assert [r for _, r in published] == [5, 10, 15]
    assert checkpoint.latest_checkpoint(d).endswith("ckpt_00000015.npz")
    meta = checkpoint.load_metadata(checkpoint.latest_checkpoint(d))
    assert meta["round"] == 15 and meta["engine"] == "loop"


def test_trainer_restore_latest_resumes_bitwise(tmp_path):
    ref = _trainer(_sim())
    ref.init(_params0(), jax.random.key(1))
    ref.run(18)

    d = str(tmp_path / "ckpts")
    first = _trainer(_sim(), ckpt_dir=d, publish_every=6)
    first.init(_params0(), jax.random.key(1))
    first.run(12)  # "crash" after the round-12 snapshot

    resumed = _trainer(_sim(), ckpt_dir=d, publish_every=6)
    resumed.init(_params0(), jax.random.key(1))
    assert resumed.restore_latest()
    assert resumed.round == 12
    resumed.advance_stream()  # fast-forward the fresh schedule/batch stream
    resumed.run(6)

    assert resumed.round == 18
    assert _tree_equal(ref.params, resumed.params)
    assert _tree_equal(ref.server_state, resumed.server_state)
    assert np.array_equal(
        jax.random.key_data(ref.key), jax.random.key_data(resumed.key))


def test_trainer_restore_latest_edge_cases(tmp_path):
    t = _trainer(_sim())
    with pytest.raises(RuntimeError, match="init"):
        t.restore_latest()
    with pytest.raises(RuntimeError, match="init"):
        t.run(1)
    t.init(_params0(), jax.random.key(0))
    assert not t.restore_latest()  # no ckpt_dir configured
    t2 = _trainer(_sim(), ckpt_dir=str(tmp_path / "empty"))
    t2.init(_params0(), jax.random.key(0))
    assert not t2.restore_latest()  # dir has no snapshot
    with pytest.raises(ValueError, match="unknown engine"):
        _trainer(_sim(), engine="warp")


def test_trainer_async_engine_streams_across_bursts(tmp_path):
    """The async engine keeps its arrival buffer across bursts (reset only
    on the first) — bursting equals one uninterrupted run_schedule call."""
    delays = GeometricDelays(N, mean=1.0, max_delay=4, seed=5)
    one = _trainer(_sim(momentum=0.0), engine="async", delays=delays,
                   staleness_decay=0.7)
    one.init(_params0(), jax.random.key(1))
    m_one = one.run(12)

    delays2 = GeometricDelays(N, mean=1.0, max_delay=4, seed=5)
    burst = _trainer(_sim(momentum=0.0), engine="async", delays=delays2,
                     staleness_decay=0.7, ckpt_dir=str(tmp_path / "c"),
                     publish_every=4)
    burst.init(_params0(), jax.random.key(1))
    m_burst = burst.run(12)

    assert m_one["loss"].shape == (12,)
    assert _tree_equal(one.params, burst.params)
    assert _tree_equal(m_one, m_burst)
    assert checkpoint.latest_checkpoint(str(tmp_path / "c")) is not None


def test_trainer_stop_callback_halts_between_bursts():
    t = _trainer(_sim(), publish_every=3)
    t.init(_params0(), jax.random.key(0))
    calls = []

    def stop():
        calls.append(len(calls))
        return len(calls) >= 2  # allow two bursts, then halt

    metrics = t.run(30, stop=stop)
    assert t.round == 6
    assert metrics["loss"].shape == (6,)


@pytest.mark.parametrize("engine", ["scan", "pipelined"])
def test_trainer_scan_engines_run_and_publish(engine, tmp_path):
    d = str(tmp_path / "ckpts")
    t = _trainer(_sim(), engine=engine, chunk=4, ckpt_dir=d)
    t.init(_params0(), jax.random.key(1))
    metrics = t.run(8)  # publish_every=0 → one final snapshot
    assert metrics["loss"].shape == (8,)
    latest = checkpoint.latest_checkpoint(d)
    assert latest is not None and latest.endswith("ckpt_00000008.npz")
    assert checkpoint.load_metadata(latest)["engine"] == engine


def test_snapshot_eval_loop_follows_published_snapshots(tmp_path):
    """The live-eval side: a trainer publishing into a directory, a
    SnapshotEvalLoop polling it — every new snapshot is reloaded and scored,
    an unchanged pointer is a no-op, and the watch() history tracks the
    published rounds in order."""
    d = str(tmp_path / "ckpts")
    trainer = _trainer(_sim(), ckpt_dir=d, publish_every=4)
    trainer.init(_params0(), jax.random.key(1))

    eval_batch = {"c": np.zeros((N, 2, 4, DIM), np.float32)}
    eval_fn = jax.jit(_loss_fn)
    loop = SnapshotEvalLoop(d, params_like=_params0(), eval_fn=eval_fn)

    with pytest.raises(RuntimeError, match="poll"):
        loop.eval_batch(eval_batch)
    assert not loop.poll()  # nothing published yet

    trainer.run(4)
    assert loop.poll() and loop.round == 4
    assert not loop.poll()  # pointer unchanged → no reload
    # the same compiled eval_fn on the trainer's live params: the reloaded
    # snapshot must score exactly as the state it was published from
    direct = float(eval_fn(trainer.params, eval_batch))
    assert loop.eval_batch(eval_batch) == direct

    # watch(): train between polls via the injectable sleep
    def sleep(_interval):
        trainer.run(4)

    history = loop.watch(eval_batch, max_polls=3, interval=0.0, sleep=sleep)
    assert [rnd for rnd, _ in history] == [8, 12]
    assert all(np.isfinite(loss) for _, loss in history)
    # training reduces the quadratic eval loss round over round
    assert history[-1][1] < direct


def test_snapshot_eval_loop_requires_eval_fn(tmp_path):
    d = str(tmp_path / "ckpts")
    checkpoint.publish(d, params=_params0(), server_state=None,
                       key=jax.random.key(0), round=1)
    loop = SnapshotEvalLoop(d, params_like=_params0())
    assert loop.poll()
    with pytest.raises(RuntimeError, match="eval_fn"):
        loop.eval_batch({"c": np.zeros((N, 2, 4, DIM), np.float32)})


def test_build_topology_and_connectivity_helpers():
    assert build_topology("ring", 8, 2).sum() == 8 * 4
    assert build_topology("fct", 5, 1).sum() == 5 * 4
    assert build_topology("disconnected", 4, 1).sum() == 0
    assert build_topology("clusters", 8, 1).shape == (8, 8)
    with pytest.raises(ValueError):
        build_topology("moebius", 4, 1)
    assert np.allclose(build_connectivity("homogeneous", 6, 0.3).p, 0.3)
    assert build_connectivity("paper", 10, 0.2).p.shape == (10,)
    assert build_connectivity("heterogeneous", 7, 0.2).p.shape == (7,)


def test_trainer_run_zero_rounds_returns_empty():
    t = _trainer(_sim())
    t.init(_params0(), jax.random.key(0))
    assert t.run(0) == {}
