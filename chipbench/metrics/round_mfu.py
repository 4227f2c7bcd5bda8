"""Model FLOP/s utilisation of the round step: the operations that the
active clients' forward and backward passes require (T steps of the local
batch each; no padded rounds, no inactive slots), times the rounds per
second of the traced window, over the chip's bf16 peak, in percent.
Against the bf16 peak because an f32 convolution at the default matmul
precision runs as one bf16 pass on the MXU."""


def read(art):
    trace, peak = art["trace"], art["peak"]
    if trace is None or peak is None or art["traced_rounds"] <= 0:
        return None
    per_round = (
        art["flops_per_example"] * art["active_clients"] * art["local_steps"]
        * art["local_batch"]
    )
    rate = art["traced_rounds"] / trace["window_s"]
    return 100.0 * per_round * rate / peak["bf16_flops_per_s"]
