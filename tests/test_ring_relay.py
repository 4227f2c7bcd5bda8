"""Ring ppermute relay == einsum relay, on real meshes (subprocess: device
count must be forced before jax init)."""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code, devices=8):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_ring_equals_einsum_single_axis():
    out = _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import topology, opt_alpha, connectivity, relay as relay_lib
from repro.fl.ring import make_ring_round_mixer
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(8, 1)
n = 8
p = connectivity.heterogeneous_profile(n).p
A = opt_alpha.optimize(p, topology.ring(n, 2), sweeps=10).A
rng = np.random.default_rng(0)
deltas = {"w": jnp.asarray(rng.standard_normal((n, 12, 5)), jnp.float32),
          "b": jnp.asarray(rng.standard_normal((n, 7)), jnp.float32)}
tau = jnp.asarray(rng.random(n) < p, jnp.float32)
w = 1.0 / n
want = relay_lib.masked_aggregate(tau, relay_lib.relay(A, deltas), w=w)
with mesh:
    mixer = make_ring_round_mixer(A, w=w, mesh=mesh, client_axes=("data",))
    got = jax.jit(mixer)(tau, deltas)
for k in deltas:
    err = float(jnp.abs(got[k] - want[k]).max())
    assert err < 1e-5, (k, err)
print("OK")
""")
    assert "OK" in out


def test_block_ring_flat_equals_einsum():
    """Block-ring on the raveled (n, D) buffer — m = n/k clients per device
    — matches `aggregation.colrel_increment_flat` for k ∈ {4, 8} devices,
    with and without a churn mask (masking is the caller's job, mirroring
    the sharded round step's ring branch)."""
    out = _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import topology, opt_alpha, connectivity, aggregation
from repro.core import relay as relay_lib
from repro.fl.ring import ring_colrel_increment_flat
from repro.launch.mesh import make_client_mesh

n, D = 8, 48
p = connectivity.heterogeneous_profile(n).p
A = opt_alpha.optimize(p, topology.ring(n, 2), sweeps=10).A
rng = np.random.default_rng(3)
buf = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
tau = jnp.asarray(rng.random(n) < p, jnp.float32)
churn = jnp.asarray(rng.random(n) < 0.7, jnp.float32)
for k in (4, 8):
    mesh = make_client_mesh(k)
    for label, active in (("full", None), ("churn", churn)):
        want = aggregation.colrel_increment_flat(A, tau, buf, n=n, active=active)
        w = aggregation.active_weight(active, n=n)
        A_eff, tau_eff = (A, tau) if active is None else (
            relay_lib.mask_relay_matrix(A, active), tau * active)

        def local(A_, t_, w_, b_):
            return ring_colrel_increment_flat(
                A_, t_, b_, w=w_, axis_name="clients", n_shards=k)

        got = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(None, None), P(None), P(), P("clients", None)),
            out_specs=P(None), check_vma=False,
        ))(jnp.asarray(A_eff, jnp.float32), tau_eff, jnp.asarray(w, jnp.float32), buf)
        err = float(jnp.abs(got - want).max())
        assert err < 1e-5, (k, label, err)
print("OK")
""")
    assert "OK" in out


def test_ring_equals_einsum_multi_axis():
    """Client axis spans ("pod","data") — the multi-pod layout."""
    out = _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import topology, opt_alpha, connectivity, relay as relay_lib
from repro.fl.ring import make_ring_round_mixer
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(4, 1, pod=2)
n = 8
p = connectivity.heterogeneous_profile(n).p
A = opt_alpha.optimize(p, topology.ring(n, 1), sweeps=10).A
rng = np.random.default_rng(1)
deltas = {"w": jnp.asarray(rng.standard_normal((n, 6)), jnp.float32)}
tau = jnp.ones((n,), jnp.float32)
w = 1.0 / n
want = relay_lib.masked_aggregate(tau, relay_lib.relay(A, deltas), w=w)
with mesh:
    mixer = make_ring_round_mixer(A, w=w, mesh=mesh, client_axes=("pod", "data"))
    got = jax.jit(mixer)(tau, deltas)
err = float(jnp.abs(got["w"] - want["w"]).max())
assert err < 1e-5, err
print("OK")
""")
    assert "OK" in out
