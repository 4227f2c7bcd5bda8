"""A copy of the benchmark with small cells, for runs on the CPU."""
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = pathlib.Path(__file__).resolve().parent


def tiny_root(tmp_path) -> pathlib.Path:
    """``tmp_path`` set up as a checkout's benchmark, with two cells on the
    fig5 channel in chunks of 4 rounds, held to the limits of
    ``resnet20_n10.fig5``: ``tiny_resnet.tiny_fig5``, ResNet-20 at its
    widths with its 10 clients, of batch 2, and ``tiny_mlp.tiny_fig5``,
    the system's MLP over 48 features with 32 hidden units."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    bench_dir = tmp_path / "chipbench"
    cfg = json.loads((bench_dir / "configs" / "resnet20_n10.json").read_text())
    cfg["spec"].update(n_train=64, local_batch=2)
    mix = json.loads((bench_dir / "traffic" / "fig5.json").read_text())
    mix["spec"].update(adj_every=4, p_every=4, chunk=4)
    mix["burst_rounds"] = 8
    add_cell(tmp_path, "tiny_resnet", cfg, "tiny_fig5", mix)
    add_cell(tmp_path, "tiny_mlp", mlp_config(dim=48, width=32, n_train=64, local_batch=2),
             "tiny_fig5", mix)
    return tmp_path


def mlp_config(**spec) -> dict:
    """A configuration of the system's spec-sized MLP (``model="mlp"``:
    one ReLU layer of ``width`` over ``dim`` flat features), checked against
    the plain reference ``mlp.py`` of this directory; fig5's configuration
    otherwise, with ``spec`` over its ScenarioSpec fields."""
    cfg = json.loads((ROOT / "chipbench" / "configs" / "resnet20_n10.json").read_text())
    cfg["spec"].update(model="mlp", **spec)
    s = cfg["spec"]
    cfg["reference"] = "mlp"
    cfg["model"] = {"dim": s["dim"], "width": s["width"], "n_classes": s["n_classes"]}
    return cfg


def add_cell(root, config: str, cfg: dict, traffic: str, mix: dict,
             limits: str = "resnet20_n10.fig5") -> str:
    """Add the configuration ``config`` and the mix ``traffic`` (if new) to
    the benchmark at ``root`` and one cell of the two, held to the limits of
    the cell ``limits``; copies the plain reference ``mlp.py`` beside the
    others.  Returns the cell's name."""
    root = pathlib.Path(root)
    bench_dir = root / "chipbench"
    name = f"{config}.{traffic}"
    shutil.copy(HERE / "mlp.py", bench_dir / "models" / "mlp.py")
    (bench_dir / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / f"{traffic}.json").write_text(json.dumps(mix))
    shutil.copy(bench_dir / "limits" / f"{limits}.json", bench_dir / "limits" / f"{name}.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "x", "reduced": [],
                             "file": f"chipbench/configs/{config}.json", "why": "x"})
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name
