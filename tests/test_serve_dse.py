"""Serving-side two-stage DSE: Stage-1 design-point search (TP-degree /
slot-count / bucket-ladder trades on the analytical model), Stage-2 split
search over Stage-1-optimal points (AnalyticalPolicy.decide returning
per-tenant DesignPoints, retune decisions), and design-aware warm compiles.

Pure analytical tests (no devices) plus engine-level cache checks; the
live-application path is covered by tests/test_workloads.py
(test_live_reconfigure_stream_invariance, mixed-fleet e2e) and the CI
``dse-smoke`` job (repro.launch.serve --dse-smoke)."""
import dataclasses

import numpy as np
import pytest

import jax

from repro.configs import get_reduced
from repro.core.dse import DesignPoint, dp_candidates, tp_candidates
from repro.core.analytical import tp_collective_latency
from repro.common.platform import TPU_V5E
from repro.distribution import strip
from repro.models import build_model
from repro.serve.dse import Stage1Optimizer, TenantDesignSpace, padded_factor
from repro.serve.fabric import AnalyticalPolicy, TenantObservation
from repro.workloads import (DECODE, ENCDEC, ENCODER, SSM, DecodeEngine,
                             ServeConfig)


def _load(pending, active=1, util=0.0, queue=0, space=None, lengths=()):
    return TenantObservation(pending_tokens=pending, queue_depth=queue,
                             active=active, arena_utilization=util,
                             space=space, recent_lengths=tuple(lengths))


def _space(**kw):
    base = dict(wclass=DECODE, max_len=64, base_slots=2,
                per_slot_elems=64 * 128, tp_allowed=True)
    base.update(kw)
    return TenantDesignSpace(**base)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def test_tp_candidates_and_design_point_knobs():
    assert tp_candidates(1) == (1,)
    assert tp_candidates(4) == (1, 2, 4)
    assert tp_candidates(6) == (1, 2, 4, 6)
    assert tp_candidates(0) == ()
    p = DesignPoint(cus=4, tp=2, slots=8, buckets=(8, 64))
    assert p.knobs() == {"tp": 2, "slots": 8, "buckets": [8, 64]}
    assert DesignPoint(cus=4).knobs() == {}      # split-only: no knobs
    p2 = DesignPoint(cus=4, tp=1, slots=4, dp=4)
    assert p2.knobs() == {"tp": 1, "slots": 4, "dp": 4}


def test_dp_candidates():
    assert dp_candidates(4, 1) == (1, 2, 4)
    assert dp_candidates(6, 1) == (1, 2, 4, 6)   # max packing always in
    assert dp_candidates(8, 2) == (1, 2, 4)      # bounded by tp * dp <= cus
    assert dp_candidates(3, 2) == (1,)
    assert dp_candidates(0, 1) == ()
    assert dp_candidates(2, 4) == ()             # replica wider than grant


def test_tp_collective_latency_shape():
    assert tp_collective_latency(TPU_V5E, 1, 1e6) == 0.0
    one = tp_collective_latency(TPU_V5E, 2, 4096)
    two = tp_collective_latency(TPU_V5E, 4, 4096)
    assert 0.0 < one < two          # more phases at higher degree


def test_padded_factor():
    assert padded_factor((64,), ()) == 1.0
    assert padded_factor((64,), (8, 8)) == 8.0          # capacity-only pads 8x
    assert padded_factor((8, 64), (8, 8)) == 1.0        # fitted ladder: none
    assert padded_factor((8, 64), (8, 60)) == (8 + 64) / 68
    assert padded_factor((8,), (100,)) == 1.0           # oversized: ignored


# ---------------------------------------------------------------------------
# Stage 1: the three trades
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stage1():
    pol = AnalyticalPolicy()
    return pol, pol.stage1


def test_stage1_slots_cover_queue(stage1):
    """A deep queue pulls the slot count up: batching amortizes the step's
    weight traffic over min(slots, queue) streams."""
    pol, s1 = stage1
    cfg = get_reduced("minitron-4b")
    sp = _space()
    deep = s1.best(cfg, sp, 12, 2)
    shallow = s1.best(cfg, sp, 1, 2)
    assert deep.slots * (deep.dp or 1) >= 8 and shallow.slots <= 2
    assert deep.cost < s1.cost_of(cfg, sp, 12,
                                  DesignPoint(cus=2, tp=2, slots=2))


def test_stage1_tp_below_grant_for_tiny_models(stage1):
    """The all-reduce phases dominate a reduced model's µs-scale step, so
    Stage 1 caps the TP degree below a large grant instead of sharding the
    step into collective overhead."""
    pol, s1 = stage1
    cfg = get_reduced("minitron-4b")
    best = s1.best(cfg, _space(), 4, 8)
    assert best.tp < 8
    full = s1.cost_of(cfg, _space(), 4,
                      DesignPoint(cus=8, tp=8, slots=best.slots))
    assert best.cost < full


def test_stage1_cost_monotone_in_grant(stage1):
    """More CUs never hurt: the design space at grant c contains every
    design at c' < c (Stage 2's split search relies on this)."""
    pol, s1 = stage1
    for arch, wc in (("minitron-4b", DECODE), ("falcon-mamba-7b", SSM)):
        cfg = get_reduced(arch)
        sp = _space(wclass=wc)
        costs = [s1.best(cfg, sp, 6, c).cost for c in (1, 2, 4, 8)]
        assert all(a >= b - 1e-18 for a, b in zip(costs, costs[1:])), costs


def test_stage1_ladder_fits_observed_lengths(stage1):
    """Observed short jobs pull a quantile bucket into the ladder, cutting
    the encode phase's padded FLOPs vs the capacity-only program."""
    pol, s1 = stage1
    cfg = get_reduced("qwen2.5-32b")
    sp = _space(wclass=ENCODER, max_len=64, base_buckets=())
    lengths = (5, 7, 6, 8, 30)
    best = s1.best(cfg, sp, 4, 2, lengths)
    assert best.buckets is not None and len(best.buckets) >= 2
    assert best.buckets[-1] == 64                      # capacity always last
    assert padded_factor(best.buckets, lengths) \
        < padded_factor((64,), lengths)
    cap_only = s1.cost_of(cfg, sp, 4,
                          DesignPoint(cus=2, tp=best.tp,
                                      slots=best.slots, buckets=()),
                          lengths)
    assert best.cost < cap_only


def test_stage1_encdec_prices_src_by_expected_bucket(stage1):
    """An enc-dec tenant's cross-attention read prices at the ladder's
    expected bucket of the observed sources, not blindly at capacity."""
    pol, s1 = stage1
    cfg = dataclasses.replace(get_reduced("seamless-m4t-medium"),
                              dtype="float32")
    sp = _space(wclass=ENCDEC, max_len=16, max_src=16, base_buckets=(8,))
    short = s1.cost_of(cfg, sp, 4,
                       DesignPoint(cus=2, tp=2, slots=2, buckets=(8, 16)),
                       lengths=(5, 6), src_cap=16)
    cap = s1.cost_of(cfg, sp, 4,
                     DesignPoint(cus=2, tp=2, slots=2, buckets=(8, 16)),
                     lengths=(), src_cap=16)
    assert short < cap


def test_stage1_replicated_fabric_pays_no_collectives(stage1):
    """tp_allowed=False (replicated engines, no sharding rules) must price
    zero collective cost — otherwise larger grants look like regressions
    and the policy freezes (regression test for the mixed-fleet fabric)."""
    pol, s1 = stage1
    cfg = get_reduced("minitron-4b")
    sp = _space(tp_allowed=False)
    assert s1.collective_s(cfg, 2, 8, sp) == 0.0
    costs = [s1.best(cfg, sp, 4, c).cost for c in (1, 2, 4, 8)]
    assert all(a >= b - 1e-18 for a, b in zip(costs, costs[1:])), costs


def test_stage1_slot_memory_feasibility(stage1):
    """Slot counts are bounded by the pool the compute CUs' HBM can pin."""
    pol, s1 = stage1
    cfg = get_reduced("minitron-4b")
    tight = Stage1Optimizer(pol.step_cost, mem_budget_bytes=4 * 64 * 128 * 3)
    sp = _space()                                    # per_slot_elems 64*128
    best = tight.best(cfg, sp, 12, 1)
    assert best.slots <= 3, best


def test_stage1_dp_fills_grant_past_the_slot_cap(stage1):
    """When one engine's step program can't batch past ``slot_cap``, a deep
    queue on a wide grant is served by tiling the grant into data-parallel
    replicas (the Herald trade): total concurrency multiplies by dp while
    each replica stays at a cheap low TP degree."""
    pol, s1 = stage1
    cfg = get_reduced("minitron-4b")
    sp = _space(slot_cap=4)
    best = s1.best(cfg, sp, 16, 4)
    assert best.dp and best.dp >= 2, best
    assert best.slots * best.dp >= 8, best
    forced = s1.cost_of(cfg, sp, 16,
                        DesignPoint(cus=4, tp=4, slots=4, dp=1))
    assert best.cost < forced


def test_stage1_respects_dp_cap(stage1):
    """dp_cap=1 pins the tenant to a single engine regardless of grant."""
    pol, s1 = stage1
    cfg = get_reduced("minitron-4b")
    best = s1.best(cfg, _space(slot_cap=4, dp_cap=1), 16, 4)
    assert best.dp == 1, best


# ---------------------------------------------------------------------------
# Stage 2: decide over design points
# ---------------------------------------------------------------------------

def test_decide_returns_design_points_with_knobs():
    cfgs = {"a": get_reduced("minitron-4b"), "b": get_reduced("minitron-4b")}
    pol = AnalyticalPolicy()
    points, reason = pol.decide(
        {"a": _load(100, queue=10, space=_space()),
         "b": _load(100, queue=10, space=_space())}, cfgs,
        {"a": 4, "b": 4}, 8)
    assert all(isinstance(p, DesignPoint) for p in points.values())
    if reason != "hysteresis":
        assert any(p.slots not in (None, 2) or (p.tp or p.cus) < p.cus
                   for p in points.values()), points
    assert pol.predicted is not None and pol.predicted["best_s"] > 0


def test_decide_retunes_same_split_on_knob_gain():
    """When the best composition keeps the CU split but better per-tenant
    knobs clear the gain bar, decide returns reason='retune' — a pure
    Stage-1 delta the fabric applies with no CU move."""
    cfg = get_reduced("minitron-4b")
    pol = AnalyticalPolicy()
    sp = _space()
    current = {"a": DesignPoint(cus=8, tp=8, slots=1)}
    points, reason = pol.decide(
        {"a": _load(200, active=1, queue=15, space=sp)}, {"a": cfg},
        current, 8)
    assert reason == "retune"
    assert points["a"].cus == 8 and points["a"].slots > 1


def test_decide_split_only_matches_pre_dse_shape():
    """two_stage=False: design points carry no knobs (the CU count is the
    whole design point) and the split dynamics are the pre-DSE ones."""
    cfgs = {"a": get_reduced("minitron-4b"), "b": get_reduced("minitron-4b")}
    pol = AnalyticalPolicy(two_stage=False)
    assert pol.stage1 is None
    points, reason = pol.decide(
        {"a": _load(100, space=_space()), "b": _load(0, space=_space())},
        cfgs, {"a": 4, "b": 4}, 8)
    live = {t: p for t, p in points.items() if p.cus > 0}
    assert live == {"a": DesignPoint(cus=8, cost=live["a"].cost)}
    assert reason == "unify"
    assert all(p.tp is None and p.slots is None for p in points.values())


# ---------------------------------------------------------------------------
# design-aware warm compile: prewarmed programs are reused after the
# matching reconfigure (the stall-free retune path)
# ---------------------------------------------------------------------------

def test_warm_compile_covers_candidate_design_point():
    cfg = dataclasses.replace(get_reduced("minitron-4b"), dtype="float32")
    model = build_model(cfg)
    params = strip(model.init(jax.random.key(0)))
    eng = DecodeEngine(model, params, ServeConfig(max_slots=2, max_len=32,
                                                  eos_id=-1))
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(1, cfg.vocab_size, size=8), max_new_tokens=3)
    eng.run_to_completion(50)                        # seed prefill lengths
    built = eng.warm_compile(None, DesignPoint(cus=0, slots=4))
    assert built >= 1
    before = eng.compile_builds
    eng.apply(None, DesignPoint(cus=0, slots=4))
    eng.submit(rng.integers(1, cfg.vocab_size, size=8), max_new_tokens=3)
    eng.run_to_completion(50)
    assert eng.compile_builds == before, \
        "reconfigured engine re-compiled a program warm_compile had built"


# ---------------------------------------------------------------------------
# predicted-vs-measured accounting: a dse-driven retune must leave a ledger
# entry pairing Stage 1's predicted unit cost with the measured step p50
# (8 fake host devices, subprocess — device count is fixed at first init)
# ---------------------------------------------------------------------------

def test_design_key_is_compact_and_total():
    from repro.serve.dse import design_key
    assert design_key(4, {"tp": 2, "dp": 1, "slots": 8,
                          "buckets": None}) == "c4-tp2-dp1-s8"
    assert design_key(2, {"tp": None, "dp": None, "slots": 4,
                          "buckets": (128, 512)}) == "c2-tp0-dp1-s4-b128.512"


def test_predicted_vs_measured_after_dse_retune():
    import json
    import subprocess
    import sys
    import textwrap
    prelude = (
        "import os\n"
        'os.environ["XLA_FLAGS"] = '
        '"--xla_force_host_platform_device_count=8"\n'
        "import sys\n"
        'sys.path.insert(0, "src")\n'
        "import json\n"
        "import jax\n"
        "import numpy as np\n"
        "from repro.launch.mesh import make_host_mesh\n")
    body = textwrap.dedent("""
    import dataclasses
    from repro.serve.fabric import (AnalyticalPolicy, ComposedServer,
                                    TenantSpec)
    from repro.serve import ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    sc = ServeConfig(max_slots=2, max_len=48, eos_id=-1)
    tenants = [TenantSpec("a", "minitron-4b",
                          serve=dataclasses.replace(sc, slot_cap=4)),
               TenantSpec("b", "qwen2.5-32b", seed=1, serve=sc)]
    srv = ComposedServer(mesh, tenants, policy=AnalyticalPolicy(),
                         decide_every=3)
    rng = np.random.default_rng(0)
    for t, n in (("a", 16), ("b", 6)):      # queue depth >> default slots
        vocab = srv.cfgs[t].vocab_size
        for _ in range(n):
            srv.submit(t, rng.integers(1, vocab, size=8), max_new_tokens=10)
    srv.drain(max_steps=500)
    pvm = srv.stats()["predicted_vs_measured"]
    committed = {k: e for k, e in pvm["entries"].items()
                 if e["commits"] > 0 and e["ratio"] is not None}
    print(json.dumps({
        "recompositions": srv.stats()["recompositions"],
        "n_entries": len(pvm["entries"]),
        "n_committed_with_ratio": len(committed),
        "classes": sorted({e["class"] for e in committed.values()}),
        "ratios_finite": all(e["ratio"] > 0 for e in committed.values()),
        "agg": pvm["aggregate"],
    }))
    """)
    out = subprocess.run([sys.executable, "-c", prelude + body],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["recompositions"] >= 1
    # at least one policy-committed design point accumulated measured
    # steps under the same key -> a predicted/measured ratio exists
    assert res["n_committed_with_ratio"] >= 1
    assert res["ratios_finite"]
    assert res["agg"]["entries_with_both"] >= 1
    assert res["agg"]["mean_abs_log2_error"] >= 0
