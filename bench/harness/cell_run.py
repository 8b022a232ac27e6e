"""One run of one cell: set-up, lead-in, window, tail, then the check.

With ``trace=False`` the result carries the cell's end-to-end metrics, with
``trace=True`` its per-layer metrics, read by ``bench/metrics/<name>.py``
from the run's records, the program's counters and the profiler's trace of
the window.  Either way the served tokens are checked against the plain
reference once the server is gone, and ``correct`` says whether they held.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
from typing import Dict, List, Tuple

import jax
import numpy as np

from harness import check, serve_loop, setup, spec, traffic
from harness.context import Context

# the traced run profiles the last seconds of its window: a whole window's
# trace of a decode-bound model holds millions of device ops
TRACE_SECONDS = 6.0

def verify_config(config: dict) -> None:
    """The program's registered configuration has the file's sizes: the file
    holds the configuration as it is run."""
    from repro.configs import get_config, get_reduced

    cfg = (get_reduced if config["program_reduced"] else get_config)(
        config["arch"])
    pairs = {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
             "num_attention_heads": cfg.num_heads,
             "num_key_value_heads": cfg.num_kv_heads,
             "head_dim": cfg.resolved_head_dim,
             "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
             "hidden_act": cfg.act, "rope_theta": cfg.rope_theta,
             "norm_eps": cfg.norm_eps, "norm": cfg.norm,
             "tie_word_embeddings": cfg.tie_embeddings,
             "torch_dtype": cfg.param_dtype}
    bad = {k: (config[k], v) for k, v in pairs.items() if config[k] != v}
    if bad:
        raise ValueError(f"configuration file and program disagree "
                         f"(file, program): {bad}")


def program_counters(server) -> Dict[str, object]:
    """The engines' histograms (count, sum) and the programs built so far."""
    reg = server.metrics()
    out: Dict[str, object] = {}
    for name in ("queue_wait_s", "prefill_s", "decode_step_s"):
        n = s = 0.0
        for wclass in set(server.classes.values()):
            h = reg.merged_histogram(name, wclass=wclass)
            n, s = n + h.count, s + h.sum
        out[name] = (n, s)
    out["cache_builds"] = server.exec_cache.snapshot()["builds"]
    out["engine_builds"] = sum(g.compile_builds
                               for g in server.engines.values())
    return out


def counter_delta(a: dict, b: dict) -> Dict[str, object]:
    out = {k: (b[k][0] - a[k][0], b[k][1] - a[k][1])
           for k in ("queue_wait_s", "prefill_s", "decode_step_s")}
    out["builds"] = max(b["cache_builds"] - a["cache_builds"],
                        b["engine_builds"] - a["engine_builds"])
    return out


def peak_bytes(devs) -> int:
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs))


def p95(values: List[float]) -> float:
    return float(np.percentile(values, 95)) if values else math.nan


def end_to_end(run: serve_loop.Run, seconds: float, setup_s: float,
               stop: float) -> Dict[str, float]:
    """out_tok_s, ttft_p95_ms, itl_p95_ms, setup_s.  A request due in the
    window that never got its first token counts with the time it waited
    until the run stopped."""
    w0, w1 = run.window
    toks = sum(1 for r in run.records for t in r.times if w0 <= t < w1)
    ttft = [(r.first if r.times else stop) - r.due
            for r in run.records if w0 <= r.due < w1]
    itl = [b - a for r in run.records for a, b in zip(r.times, r.times[1:])
           if w0 <= b < w1]
    return {"out_tok_s": toks / seconds, "ttft_p95_ms": p95(ttft) * 1e3,
            "itl_p95_ms": p95(itl) * 1e3, "setup_s": setup_s}


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True, control: bool = False,
        log=lambda m: print(m, file=sys.stderr, flush=True)
        ) -> Tuple[dict, List[str]]:
    """Run ``cell`` once.  Returns (the result's JSON object, the lines
    stating each number compared beside its limit).  ``control`` also puts
    the int8 control through the same check on the same sample
    (``control``)."""
    devs = setup.devices(cell.chips, require_tpu)
    if require_tpu:
        setup.compile_cache()
    config, mix = cell.config, cell.traffic
    verify_config(config)
    tenants = config["tenants"]
    server = setup.build_server(config, cell.chips, seed)
    sched = traffic.generate(mix, tenants, config["vocab_size"], seed,
                             seconds)
    buckets, bounds = setup.shapes(config, mix)
    t_warm = serve_loop.clock()
    built = setup.warm(server, buckets, bounds)
    log(f"set-up: server and weights {t_warm - t_start:.1f} s, {built} "
        f"programs built in {serve_loop.clock() - t_warm:.1f} s for prompt "
        f"buckets {buckets[0]}..{buckets[-1]} and KV bounds {bounds[0]}.."
        f"{bounds[-1]}; {len(sched.arrivals)} requests scheduled")

    marks: Dict[str, object] = {}
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    def on_open():
        marks["open"] = program_counters(server)

    def trace_start():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        marks["trace"] = serve_loop.clock()

    def on_close():
        if trace:
            marks["trace"] = (marks["trace"], serve_loop.clock())
            jax.profiler.stop_trace()
        marks["close"] = program_counters(server)

    hooks = [(0.0, on_open), (seconds, on_close)]
    if trace:
        hooks.append((max(seconds - TRACE_SECONDS, 0.0), trace_start))
    result = serve_loop.serve(server, sched, seconds, hooks)
    stop = serve_loop.clock()
    setup_s = result.window[0] - t_start
    peak = peak_bytes(devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    due = [r for r in result.records
           if result.window[0] <= r.due < result.window[1]]
    failed = sum(1 for r in due if not r.times)
    ttft = sorted(((r.first if r.times else stop) - r.due) * 1e3 for r in due)
    if ttft:
        log(f"window: {len(due)} requests due, ttft ms p50 "
            f"{np.percentile(ttft, 50):.1f} p90 {np.percentile(ttft, 90):.1f} "
            f"p95 {np.percentile(ttft, 95):.1f} max {ttft[-1]:.1f}; "
            f"peak {peak} bytes")
    out = {"correct": False, "attempted": len(due), "failed": failed}

    peaks = spec.peaks(devs[0].device_kind) if require_tpu else {}
    if trace:
        reduce = spec.load_module(spec.BENCH / "trace" / "reduce.py")
        tr = reduce.reduce_dir(trace_dir, marks["trace"], len(devs))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = tr.busy_s, tr.window_s
        ctx = Context(config, result.records, result.window,
                      counter_delta(marks["open"], marks["close"]), peaks, tr)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = tr.breakdown()
    else:
        e2e = end_to_end(result, seconds, setup_s, stop)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out["metrics"] = metrics
    out["device"] = device

    # the check: finished requests, then the server is freed before the
    # reference runs, so that the reference never sets the peak
    results = server.results()
    finished = [check.Served(r.tenant, r.prompt,
                             np.asarray(results[r.tenant][r.rid], np.int32))
                for r in result.records if r.finished]
    seeds = {t: seed + i for i, t in enumerate(tenants)}
    del server, results
    gc.collect()
    picked = check.sample(finished, seed, config["check"]["sample_requests"])
    t_check = serve_loop.clock()
    gaps = check.served_gaps(config, seeds, picked)
    log(f"check: {gaps.size} served tokens of {len(picked)} requests "
        f"through the reference in {serve_loop.clock() - t_check:.1f} s")
    out["correct"], out["compared"], lines = check.decide(config, gaps)
    lines[-1] += f" (over {len(picked)} requests)"
    log(f"gaps: {check.summary(gaps)}")
    if control:
        ctl = check.served_gaps(config, seeds, picked, control=True)
        ok, compared, _ = check.decide(config, ctl)
        out["control"] = {"correct": ok, "compared": compared,
                          "gaps": check.summary(ctl)}
        out["program_gaps"] = check.summary(gaps)
        out["compared"] = out.pop("compared")
    return out, lines

