"""The knee of a cell's traffic mix: the completion rate under a backlog.

    python bench/knee.py --workload <cell> --seed <n> --backlog <n> \
        --lead-in <s> --seconds <s>

Set-up as ``bench/run.py`` makes it, then every request of the mix (its
lengths, a backlog of ``--backlog`` requests) is due at once.  After
``--lead-in`` seconds of draining, the window of ``--seconds`` counts the
requests finished and the tokens returned.  Requests finished per second in
the window is the highest rate the system sustains on this mix; the cell's
traffic file offers a fixed share of it.  Prints one JSON line.
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

from harness import cell_run, serve_loop, setup, spec, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--backlog", type=int, required=True)
    ap.add_argument("--lead-in", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devs = setup.devices(cell.chips)
    setup.compile_cache()
    cell_run.verify_config(cell.config)
    server = setup.build_server(cell.config, cell.chips, args.seed)
    mix = dict(cell.traffic, arrivals="backlog", backlog=args.backlog,
               lead_in_s=args.lead_in, tail_s=0.0)
    sched = traffic.generate(mix, cell.config["tenants"],
                             cell.config["vocab_size"], args.seed,
                             args.seconds)
    buckets, bounds = setup.shapes(cell.config, cell.traffic)
    setup.warm(server, buckets, bounds)
    setup_s = time.perf_counter() - T_START
    run = serve_loop.serve(server, sched, args.seconds)
    w0, w1 = run.window
    done = [r for r in run.records if r.finished and w0 <= r.times[-1] < w1]
    toks = sum(1 for r in run.records for t in r.times if w0 <= t < w1)
    left = sum(1 for r in run.records if not r.finished)
    print(json.dumps({
        "workload": args.workload, "backlog": args.backlog,
        "finished_in_window": len(done), "left_unfinished": left,
        "knee_rps": len(done) / args.seconds, "tok_s": toks / args.seconds,
        "mean_output": sum(r.max_new for r in done) / max(len(done), 1),
        "setup_s": setup_s, "device": devs[0].device_kind,
        "peak_bytes": cell_run.peak_bytes(devs)}), flush=True)
    return 0 if left else 1


if __name__ == "__main__":
    raise SystemExit(main())
