"""Compile rehearsals of the relay kernels for a described TPU v5e chip.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached.  These tests lower the Pallas kernels with
``interpret=False`` at the widths the benchmark asks for and check that
Mosaic accepted them (``tpu_custom_call`` in the compiled program) —
catching what interpret mode cannot, such as a tile that does not fit VMEM.
Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.bench.scenarios import build, get_scenario
from repro.kernels import ops
from repro.kernels import relay_mix as k
from repro.utils import tree_size


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def resnet20_width():
    """D of the paper's §V model as the benchmark builds it (272,282)."""
    bundle = build(get_scenario("resnet20_cifar"))
    return tree_size(jax.eval_shape(bundle.init_fn, jax.random.key(0)))


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("kernel", ["relay_mix_2d", "fused_aggregate_2d"])
@pytest.mark.parametrize("n", [4, 10])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_compiles_at_resnet20_width(one_chip, resnet20_width, kernel, n, dtype):
    delta = jax.ShapeDtypeStruct((n, resnet20_width), dtype, sharding=one_chip)
    if kernel == "relay_mix_2d":
        lhs = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    else:
        lhs = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    fn = getattr(k, kernel)
    text = _compiled_text(lambda a, d: fn(a, d, interpret=False), lhs, delta)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["mix_flat", "reduce_flat"])
def test_relay_sweep_1e7_tile_is_clamped_to_compile(one_chip, op):
    """relay_sweep_1e7 requests block_d = 1,048,576 at n = 8 in f32, which
    the chip's compiler refuses for VMEM; ``ops._block`` clamps it."""
    spec = get_scenario("relay_sweep_1e7")
    n = spec.n_clients
    width = spec.dim * spec.width + spec.width + spec.width * 10 + 10
    buf = jax.ShapeDtypeStruct((n, width), jnp.float32, sharding=one_chip)
    assert ops._block(spec.block_d, buf) < spec.block_d
    if op == "mix_flat":
        lhs = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
        backend = "pallas"
    else:
        lhs = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
        backend = "pallas_fused"
    fn = getattr(ops, op)
    text = _compiled_text(
        lambda a, b: fn(
            a, b, backend=backend, block_d=spec.block_d, interpret=False
        ),
        lhs,
        buf,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["relay_mix_2d", "fused_aggregate_2d"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_compiles_at_the_gate_precision(one_chip, resnet20_width, kernel, dtype):
    """The harness's gates run under ``default_matmul_precision("highest")``;
    a bf16 kernel must not inherit it (Mosaic refuses HIGHEST for bf16)."""
    n = 10
    delta = jax.ShapeDtypeStruct((n, resnet20_width), dtype, sharding=one_chip)
    shape = (n, n) if kernel == "relay_mix_2d" else (n,)
    lhs = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fn = getattr(k, kernel)
    with jax.default_matmul_precision("highest"):
        text = _compiled_text(lambda a, d: fn(a, d, interpret=False), lhs, delta)
    assert "tpu_custom_call" in text
