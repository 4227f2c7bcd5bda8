"""The FLOP counts against a count by hand, and the kernel's bytes."""
import json
import pathlib

from chipbench import cells

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_resnet20_conv_macs_by_hand():
    model = cells.load_module(BENCH / "models" / "resnet20.py")
    cfg = _config("resnet20_n10")
    stem = 32 * 32 * 9 * 3 * 16  # 442,368
    stage0 = 6 * 32 * 32 * 9 * 16 * 16  # six 16->16 convs at 32x32
    # first block of a stage: stride-2 conv, a conv at the new width and a
    # 1x1 stride-2 projection; then four more convs at the new width
    stage1 = 16 * 16 * 9 * 16 * 32 + 5 * 16 * 16 * 9 * 32 * 32 + 16 * 16 * 16 * 32
    stage2 = 8 * 8 * 9 * 32 * 64 + 5 * 8 * 8 * 9 * 64 * 64 + 8 * 8 * 32 * 64
    head = 64 * 10
    total = stem + stage0 + stage1 + stage2 + head
    assert total == 40_813_184
    assert sum(m for _, m in model.conv_macs(cfg)) == total
    assert model.flops_per_example(cfg) == 6 * total - 2 * stem


def test_reference_param_count_matches_config():
    import jax

    cfg = _config("resnet20_n10")
    model = cells.load_module(BENCH / "models" / f"{cfg['reference']}.py")
    shapes = jax.eval_shape(lambda k: model.init(k, cfg), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == cfg["model"]["n_params"]


def test_relay_kernel_bytes_and_flops():
    reader = cells.load_module(BENCH / "metrics" / "relay_kernel_roofline.py")
    n, d = 10, 272_282
    assert reader.call_bytes(n, d) == 4 * (n * d + n + d)
    assert reader.call_flops(n, d) == 2 * n * d
    # memory-bound on a v5e: the byte time exceeds the operation time
    assert reader.call_bytes(n, d) / 819e9 > reader.call_flops(n, d) / 197e12
