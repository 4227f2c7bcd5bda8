"""BENCHMARK.json resolves to its files, and new pieces are found by name."""
import json
import pathlib
import re
import shutil

import pytest

from chipbench import cells

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = cells.resolve(ROOT, cell)
    model = c.model()
    assert model.flops_per_example(c.config) > 0
    numbers = {f"{n}{v}" for n in ("loss", "dnorm") for v in ("", "1")}
    numbers |= {f"{n}{v}" for n in ("step1", "change3") for v in ("", "_med", "_all")}
    numbers |= {"p0_gap", "tau_mismatch", "nonfinite_rounds"}
    numbers |= {"alpha_unbiased", "alpha_off_support", "alpha_excess"}
    assert {"tau_mismatch", "alpha_unbiased", "alpha_excess"} <= set(c.limits["limits"])
    assert set(c.limits["limits"]) <= numbers
    assert c.traffic["burst_rounds"] % c.traffic["spec"]["chunk"] == 0
    for m in c.per_layer:
        assert callable(c.reader(m["name"]).read)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "rounds_per_s"}


def test_benchmark_keys_and_names():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()


def _copy_root(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    return tmp_path


def test_new_config_mix_and_metric_are_found_without_edits(tmp_path):
    root = _copy_root(tmp_path)
    bench_dir = root / "chipbench"
    cfg = json.loads((bench_dir / "configs" / "resnet20_n10.json").read_text())
    cfg["spec"]["n_clients"] = 4
    (bench_dir / "configs" / "resnet20_n4.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "bursty.json").write_text(json.dumps(
        {"spec": {"fading": "static", "drift": "static", "chunk": 8}, "burst_rounds": 16}
    ))
    (bench_dir / "metrics" / "rounds_in_window.py").write_text(
        "def read(art):\n    return float(art['window_rounds'])\n"
    )
    (bench_dir / "limits" / "resnet20_n4.bursty.json").write_text(json.dumps(
        {"limits": {"loss": 1.0, "step1": 1.0, "change3": 1.0, "tau_mismatch": 0}}
    ))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "resnet20_n4", "source": "x", "reduced": [],
                             "file": "chipbench/configs/resnet20_n4.json", "why": "x"})
    bench["workloads"].append({"name": "resnet20_n4.bursty", "config": "resnet20_n4",
                               "traffic": "bursty", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "rounds_in_window", "unit": "rounds",
                               "better": "higher", "source": "program_counter",
                               "layer": "x", "moves": "rounds_per_s",
                               "workloads": ["resnet20_n4.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = cells.resolve(root, "resnet20_n4.bursty")
    assert c.config["spec"]["n_clients"] == 4
    assert c.traffic["burst_rounds"] == 16
    names = [m["name"] for m in c.per_layer]
    assert "rounds_in_window" in names
    assert c.reader("rounds_in_window").read({"window_rounds": 48}) == 48.0
    spec = __import__("chipbench.harness", fromlist=["x"]).build_spec(c, 7)
    assert spec.n_clients == 4 and spec.chunk == 8 and spec.fading == "static"
    # a metric limited to the new cell is not asked of the others
    other = cells.resolve(root, "resnet20_n10.fig5")
    assert "rounds_in_window" not in [m["name"] for m in other.per_layer]


def test_unknown_cell_and_missing_file_are_errors(tmp_path):
    with pytest.raises(KeyError):
        cells.resolve(ROOT, "no_such.cell")
    root = _copy_root(tmp_path)
    (root / "chipbench" / "traffic" / "fig5.json").unlink()
    with pytest.raises(FileNotFoundError):
        cells.resolve(root, "resnet20_n10.fig5")
