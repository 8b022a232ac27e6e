"""Chip benchmark: one run of one cell, one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs the cell of ``BENCHMARK.json`` named by ``--workload`` once, in this
process, on the chips JAX finds: set-up (server, weights from the seed,
every program the traffic can use), a lead-in, ``--seconds`` of measured
window, then the check of the served tokens against the plain reference.
The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last the
numbers compared beside their limits, which also end standard error.

Exits non-zero and prints no result where JAX finds no TPU, or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import cell_run, setup, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        out, lines = cell_run.run(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except setup.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
