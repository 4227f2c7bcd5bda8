"""How ``FLSimulator.map_clients`` maps the local updates over the clients.

A loss with a convolution (ResNet-20) trains its clients one after another
(``lax.map``), so every convolution stays an ordinary one; any other loss
keeps ``vmap``.  These tests hold the sequential map to the vmapped one at
f32 tolerance, the lowered rounds to the convolutions the rule promises,
the MLP round to the vmap path bit for bit, and the detector to
convolutions nested in sub-jaxprs.  Lowering only where the name says so:
nothing here compiles a ResNet-20 round.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench.scenarios import _make_mlp
from repro.configs.resnet20_cifar import CONFIG
from repro.core import topology
from repro.fl.async_engine import AsyncRoundEngine
from repro.fl.simulator import FLSimulator, _has_conv
from repro.models.resnet import init_resnet20, resnet20_loss

N, T, B = 3, 2, 4


def _resnet_loss(params, batch):
    return resnet20_loss(params, CONFIG, batch)


def _resnet_setting(hw=16, seed=0):
    params = init_resnet20(jax.random.key(seed), CONFIG, num_classes=10)
    rng = np.random.default_rng(seed)
    batch = {
        "images": jnp.asarray(
            rng.standard_normal((N, T, B, hw, hw, 3)).astype(np.float32)
        ),
        "labels": jnp.asarray(rng.integers(0, 10, (N, T, B)).astype(np.int32)),
    }
    return params, batch


def _mlp_setting(dim=12, width=16, n_classes=5, seed=0):
    init, loss = _make_mlp(dim, width, n_classes)
    rng = np.random.default_rng(seed)
    batch = {
        "inputs": jnp.asarray(rng.standard_normal((N, T, B, dim)).astype(np.float32)),
        "labels": jnp.asarray(rng.integers(0, n_classes, (N, T, B)).astype(np.int32)),
    }
    return loss, init(jax.random.key(seed)), batch


def _round_args(sim, params, batch):
    A = jnp.asarray(topology.ring(N, 1), jnp.float32) / 2.0
    tau = jnp.asarray([1.0, 0.0, 1.0])
    active = jnp.asarray([1.0, 1.0, 0.0])
    return (params, sim.init_server_state(params), batch, tau, A, 0.05, active)


def _grouped_convs(hlo: str) -> list[str]:
    counts = re.findall(r"(?:feature|batch)_group_count = (\d+)", hlo)
    return [c for c in counts if int(c) > 1]


def test_sequential_clients_match_vmapped_clients():
    """The sequential map is the same per-client SGD: deltas and losses
    match ``vmap(_client_update)`` to f32 tolerance."""
    params, batch = _resnet_setting()
    sim = FLSimulator(_resnet_loss, n_clients=N, local_steps=T)
    deltas, losses = jax.jit(lambda p, b: sim.map_clients(p, b, 0.05))(params, batch)
    assert sim.client_map == "sequential"
    ref_deltas, ref_losses = jax.jit(
        jax.vmap(sim._client_update, in_axes=(None, 0, None))
    )(params, batch, 0.05)
    assert losses.shape == (N,)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    for got, want in zip(jax.tree.leaves(deltas), jax.tree.leaves(ref_deltas)):
        assert got.shape == want.shape and got.shape[0] == N
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("program", ["round", "async_compute"])
def test_resnet20_round_lowers_with_no_grouped_convolution(program):
    """Lowering only: the round (and the async engine's copy of its client
    compute) runs every convolution ungrouped."""
    params, batch = _resnet_setting()
    sim = FLSimulator(
        _resnet_loss, n_clients=N, local_steps=T, strategy="colrel_fused"
    )
    args = _round_args(sim, params, batch)
    if program == "round":
        lowered = sim._round.lower(*args)
    else:
        eng = AsyncRoundEngine(sim)
        p, _, b, tau, A, lr, active = args
        lowered = eng._compute.lower(p, b, tau, A, lr, active)
    hlo = lowered.as_text()
    assert sim.client_map == "sequential"
    assert hlo.count("stablehlo.convolution") > 0
    assert _grouped_convs(hlo) == []


def test_mlp_round_keeps_vmap_bit_for_bit():
    """A loss without a convolution keeps ``vmap``: the round lowers to the
    same program as a round over ``vmap(_client_update)`` and gives the
    same bits."""

    class VmapSimulator(FLSimulator):
        def map_clients(self, params, batch, lr):
            return jax.vmap(self._client_update, in_axes=(None, 0, None))(
                params, batch, lr
            )

    loss, params, batch = _mlp_setting()
    sims = [
        cls(loss, n_clients=N, local_steps=T) for cls in (FLSimulator, VmapSimulator)
    ]
    args = [_round_args(sim, params, batch) for sim in sims]
    texts = [sim._round.lower(*a).as_text() for sim, a in zip(sims, args)]
    assert sims[0].client_map == "vmap"
    assert texts[0] == texts[1]
    outs = [sim._round(*a) for sim, a in zip(sims, args)]
    for got, want in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def _conv(x, w):
    return jax.lax.conv_general_dilated(x, w, (1, 1), "SAME")


@jax.custom_jvp
def _custom_conv(x, w):
    return _conv(x, w)


@_custom_conv.defjvp
def _custom_conv_jvp(primals, tangents):
    x, w = primals
    dx, dw = tangents
    return _conv(x, w), _conv(dx, w) + _conv(x, dw)


@pytest.mark.parametrize(
    "where, expected",
    [
        ("top", True),
        ("pjit", True),
        ("custom_jvp", True),
        ("scan", True),
        ("cond", True),
        ("dot_only", False),
    ],
)
def test_conv_detector_looks_into_sub_jaxprs(where, expected):
    def loss(w, x):
        if where == "top":
            y = _conv(x, w)
        elif where == "pjit":
            y = jax.jit(_conv)(x, w)
        elif where == "custom_jvp":
            y = _custom_conv(x, w)
        elif where == "scan":
            y, _ = jax.lax.scan(lambda c, _: (_conv(c, w), None), x, None, length=2)
        elif where == "cond":
            y = jax.lax.cond(jnp.sum(x) > 0, _conv, lambda x, w: x, x, w)
        else:
            y = x @ jnp.ones((x.shape[-1], x.shape[-1]))
        return jnp.sum(y)

    w = jax.ShapeDtypeStruct((2, 2, 3, 3), jnp.float32)
    x = jax.ShapeDtypeStruct((1, 2, 4, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(loss)(w, x)
    top_level = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    if where not in ("top", "dot_only"):
        assert "conv_general_dilated" not in top_level  # only in a sub-jaxpr
    assert _has_conv(jaxpr) is expected
