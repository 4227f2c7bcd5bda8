"""Chip benchmark of the ColRel round, one cell per run.

    python3 chipbench/run.py --workload resnet20_n10.fig5 --seed 7 \
        --seconds 20 --trace 0

Runs from the root of a checkout on the machine that holds the chips.
The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` rounds, the metrics (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
the device, and ``checks``, each number compared beside its limit (also
the last lines of standard error).  Exits non-zero with no result when
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chipbench: the system under test (src/repro) is not here", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import harness

    harness.use_compile_cache()
    try:
        result = harness.run(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace), T_START
        )
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
