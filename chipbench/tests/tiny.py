"""A copy of the benchmark with a small cell, for runs on the CPU."""
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]


def tiny_root(tmp_path) -> pathlib.Path:
    """``tmp_path`` set up as a checkout's benchmark, with a cell
    ``tiny_resnet.fig5``: ResNet-20 at its widths with its 10 clients, of batch
    2, on the fig5 channel in chunks of 4 rounds, held to the limits of
    ``resnet20_n10.fig5``."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    bench_dir = tmp_path / "chipbench"
    cfg = json.loads((bench_dir / "configs" / "resnet20_n10.json").read_text())
    cfg["spec"].update(n_train=64, local_batch=2)
    (bench_dir / "configs" / "tiny_resnet.json").write_text(json.dumps(cfg))
    mix = json.loads((bench_dir / "traffic" / "fig5.json").read_text())
    mix["spec"].update(adj_every=4, p_every=4, chunk=4)
    mix["burst_rounds"] = 8
    (bench_dir / "traffic" / "tiny_fig5.json").write_text(json.dumps(mix))
    shutil.copy(bench_dir / "limits" / "resnet20_n10.fig5.json",
                bench_dir / "limits" / "tiny_resnet.tiny_fig5.json")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_resnet", "source": "x", "reduced": [],
                             "file": "chipbench/configs/tiny_resnet.json", "why": "x"})
    bench["workloads"].append({"name": "tiny_resnet.tiny_fig5", "config": "tiny_resnet",
                               "traffic": "tiny_fig5", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path
