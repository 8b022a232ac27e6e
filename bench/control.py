"""Readings that set the limit of the correctness check.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed, one run of the cell as ``bench/run.py`` makes it (set-up,
lead-in, a window of ``--seconds`` at the cell's own load, tail), then on
the same sample of finished requests the check twice: on the served tokens
(the program's ``correct``), and on the tokens the control puts first, the
plain reference computed with int8 weights and inputs in every matrix
product (``control_correct``, which has to come out false).  Beside each,
readings of its gaps: the widest, the 99th percentile, the mean, and the
share of positions off the reference's first token.  All seeds run in this
one process.  Writes one JSON line per seed to standard output.  The
benchmark's own runs do not run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import cell_run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    t0 = T_START
    for seed in args.seeds:
        out, _ = cell_run.run(cell, seed, args.seconds, False, t0,
                              control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "control_correct": out["control"]["correct"],
                          "program": out["program_gaps"],
                          "control": out["control"]["gaps"],
                          "compared": out["compared"],
                          "control_compared": out["control"]["compared"],
                          "metrics": out["metrics"],
                          "device": out["device"]}), flush=True)
        gc.collect()
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
