"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 chipbench/calibrate.py --workload resnet20_n10.fig5 \
        --seeds 1-15 --control-seeds 1-3 --out chiprun_out/cal.jsonl

For every seed, one process sets the cell up exactly as a run does (the
trainer at the cell's sizes, its first three rounds through the window's
own call) and compares those rounds with the float32 reference: the
program's readings.  For the control seeds it also puts the reference in
the program's place in bfloat16 on the chip (the control), and sets the
program up again with each fault of ``faults.FAULTS`` planted beneath
its timed path (a state left unchanged reads 1 and needs no run; pick
others with ``--faults``).  One JSON line per seed and variant.  Not run
by the benchmark's own runs.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def faults_default() -> tuple:
    sys.path.insert(0, str(ROOT))
    from chipbench import faults

    return faults.FAULTS[1:]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-15 or 3,9,27")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--faults", default=",".join(faults_default()),
                    help="comma-separated faults to plant on the control seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import cells, faults, harness

    harness.use_compile_cache()
    cell = cells.resolve(ROOT, args.workload)
    harness.check_devices(cell, harness.load_peaks(ROOT), require_tpu=True)
    control = set(_seeds(args.control_seeds)) if args.control_seeds else set()
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as f:

        def emit(seed, t0, results):
            for name, numbers in results.items():
                line = {"cell": cell.name, "seed": seed, "variant": name, **numbers,
                        "seconds": time.perf_counter() - t0}
                f.write(json.dumps(line) + "\n")
                f.flush()
                print(json.dumps(line), flush=True)

        for seed in _seeds(args.seeds):
            t0 = time.perf_counter()
            prep = harness.prepare(cell, seed)
            prep.trainer = None
            gc.collect()
            emit(seed, t0, harness.check(cell, prep, control=seed in control))
        for fault in [f for f in args.faults.split(",") if f]:
            # a state left unchanged reads 1 and needs no run
            for seed in sorted(control):
                t0 = time.perf_counter()
                with faults.planted(fault):
                    prep = harness.prepare(cell, seed)
                prep.trainer = None
                gc.collect()
                emit(seed, t0, {f"fault_{fault}": harness.check(cell, prep)["program"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
