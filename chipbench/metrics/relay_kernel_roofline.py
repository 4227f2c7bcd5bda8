"""Roofline share of the fused relay/aggregate Pallas kernel
(``kernels/relay_mix.fused_aggregate_2d``): the least time the chip could
take for the kernel's calls in the traced window, the larger of bytes over
peak HBM bandwidth and operations over peak FLOP/s, divided by the summed
device time of the kernel's events, in percent.

Per call, u = c @ Delta over the (n, D) buffer: it has to read Delta
(n * D * 4 bytes, float32) and the n coefficients and write u (D * 4);
2 * n * D operations.  The padding of D to a tile multiple is not counted.
At every size here bytes bound it: 4 bytes per 2 operations is far below
the v5e's ratio of about 240 operations per byte."""
import re

# the kernel's op in the device trace, named after its jitted wrapper
KERNEL = re.compile(r"^fused_aggregate_2d(\.\d+)?$")


def call_bytes(n: int, d: int) -> int:
    return 4 * (n * d + n + d)


def call_flops(n: int, d: int) -> int:
    return 2 * n * d


def read(art):
    trace, peak = art["trace"], art["peak"]
    if trace is None or peak is None:
        return None
    calls, seconds = 0, 0.0
    for name, (count, secs, _) in trace["ops"].items():
        if KERNEL.search(name):
            calls += count
            seconds += secs
    if calls == 0 or seconds <= 0:
        return None
    n = art["n_clients"]
    d = art["cell"].config["model"]["n_params"]
    least = calls * max(
        call_bytes(n, d) / peak["hbm_bytes_per_s"],
        call_flops(n, d) / peak["bf16_flops_per_s"],
    )
    return 100.0 * least / seconds
