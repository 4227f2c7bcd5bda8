"""Relay-mix Pallas kernel vs jnp einsum oracle: us/call across model sizes.

On a TPU the kernels run compiled; on any other backend they run in the
Pallas interpreter, and the rows say so (``pallas_interp``): an interpreter
timing is a correctness harness, not a kernel speed.  The first line names
the device the numbers came from.  The derived column is the HBM traffic
each path needs: the faithful relay reads and writes n·D elements, the
fused path reads n·D and writes D."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels import relay_mix as k


def _time(f, *args, reps=3):
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    return 1e6 * (time.perf_counter() - t0) / reps


def run(full: bool = False):
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    print(f"# device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"pallas {'interpreter' if interpret else 'compiled'}")
    tag = "pallas_interp" if interpret else "pallas"
    rows = []
    n = 16
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    tau = jnp.asarray(rng.random(n) < 0.5, jnp.float32)
    # interpret mode executes the kernel body in Python per grid step — keep
    # the default sweep CPU-friendly; --full adds the 2M-element block
    sizes = (1 << 14, 1 << 17) + ((1 << 21,) if full else ())
    for D in sizes:
        d = jnp.asarray(rng.standard_normal((n, D)), jnp.bfloat16)
        us_ref = _time(lambda d: ref.relay_mix_2d(A, d), d)
        us_ker = _time(lambda d: k.relay_mix_2d(A, d, interpret=interpret), d)
        c = (1.0 / n) * tau @ A
        us_fused = _time(
            lambda d: k.fused_aggregate_2d(c, d, interpret=interpret), d
        )
        bytes_faithful = 2 * n * D * 2  # read + write, bf16
        bytes_fused = (n + 1) * D * 2
        name = f"relay_kernel/D{D}"
        rows.append((f"{name}/einsum_ref", us_ref, f"bytes={bytes_faithful}"))
        rows.append((f"{name}/{tag}", us_ker, f"bytes={bytes_faithful}"))
        rows.append((f"{name}/{tag}_fused", us_fused, f"bytes={bytes_fused}"))
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")
    return rows


if __name__ == "__main__":
    run()
