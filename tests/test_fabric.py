"""Real-time recomposition: delta planning is movement-minimal and pure;
resharding a live engine preserves decode numerics bit-exactly; unaffected
tenants keep their device assignments.  Device-touching scenarios run in an
8-host-device subprocess (device count is fixed at first jax init)."""
import json
import subprocess
import sys
import textwrap

import pytest

from repro.core.composer import (RecompositionDelta, plan_recomposition,
                                 recomposition_delta)
from repro.serve.fabric import (AnalyticalPolicy, TenantObservation,
                                _candidate_splits, _compositions)

# ---------------------------------------------------------------------------
# pure delta-planning tests (no devices)
# ---------------------------------------------------------------------------


def test_plan_unchanged_tenants_keep_exact_cus():
    cur = {"a": (0, 1, 2, 3), "b": (4, 5, 6, 7)}
    new = plan_recomposition(cur, {"a": 4, "b": 4}, 8)
    assert new == cur
    d = recomposition_delta(cur, new)
    assert d == RecompositionDelta(("a", "b"), (), (), ())


def test_plan_grow_steals_only_from_shrunk_tenant():
    cur = {"a": (0, 1, 2, 3), "b": (4, 5, 6, 7)}
    new = plan_recomposition(cur, {"a": 6, "b": 2}, 8)
    # a keeps its 4 and gains 2; b keeps a subset of its own
    assert set(cur["a"]) <= set(new["a"]) and len(new["a"]) == 6
    assert set(new["b"]) <= set(cur["b"]) and len(new["b"]) == 2
    assert not set(new["a"]) & set(new["b"])
    d = recomposition_delta(cur, new)
    assert set(d.moved) == {"a", "b"} and not d.unchanged


def test_plan_third_tenant_unaffected_by_neighbors():
    cur = {"a": (0, 1), "b": (2, 3, 4), "c": (5, 6, 7)}
    new = plan_recomposition(cur, {"a": 3, "b": 2, "c": 3}, 8)
    assert new["c"] == cur["c"]                  # untouched
    d = recomposition_delta(cur, new)
    assert "c" in d.unchanged and set(d.moved) == {"a", "b"}


def test_plan_park_and_admit():
    cur = {"a": (0, 1, 2, 3), "b": (4, 5, 6, 7)}
    new = plan_recomposition(cur, {"a": 8, "b": 0}, 8)
    assert new == {"a": (0, 1, 2, 3, 4, 5, 6, 7)}
    d = recomposition_delta(cur, new)
    assert d.evicted == ("b",) and d.moved == ("a",)
    back = plan_recomposition(new, {"a": 4, "b": 4}, 8)
    assert len(back["a"]) == len(back["b"]) == 4
    assert recomposition_delta(new, back).admitted == ("b",)


def test_plan_rejects_oversubscription():
    with pytest.raises(ValueError):
        plan_recomposition({}, {"a": 5, "b": 4}, 8)


def test_compositions_enumerates_all_positive_splits():
    splits = list(_compositions(5, 2))
    assert splits == [(1, 4), (2, 3), (3, 2), (4, 1)]
    assert all(sum(s) == 8 for s in _compositions(8, 3))


def test_candidate_splits_proportional_fallback_at_pod_scale():
    # C(63, 7) >> budget: one demand-proportional split instead of a hang
    busy = [f"t{i}" for i in range(8)]
    demand = {t: float(i + 1) for i, t in enumerate(busy)}
    splits = list(_candidate_splits(64, busy, demand))
    assert len(splits) == 1
    (s,) = splits
    assert sum(s) == 64 and all(x >= 1 for x in s)
    assert list(s) == sorted(s)      # heavier demand never gets less


# ---------------------------------------------------------------------------
# policy (pure: analytical model only)
# ---------------------------------------------------------------------------

def _load(pending, active=1, util=0.0):
    return TenantObservation(pending_tokens=pending, queue_depth=0,
                             active=active, arena_utilization=util)


def _cus(points):
    """Design-point dict -> {tenant: CU count} (composed tenants only)."""
    return {t: p.cus for t, p in points.items() if p.cus > 0}


def test_policy_gives_lone_busy_tenant_the_fabric():
    from repro.configs import get_reduced
    cfgs = {"a": get_reduced("minitron-4b"), "b": get_reduced("minitron-4b")}
    pol = AnalyticalPolicy()
    points, reason = pol.decide({"a": _load(100), "b": _load(0)},
                                cfgs, {"a": 4, "b": 4}, 8)
    assert _cus(points) == {"a": 8} and reason == "unify"


def test_policy_hysteresis_keeps_balanced_split():
    from repro.configs import get_reduced
    cfgs = {"a": get_reduced("minitron-4b"), "b": get_reduced("minitron-4b")}
    pol = AnalyticalPolicy()
    points, reason = pol.decide({"a": _load(50), "b": _load(50)},
                                cfgs, {"a": 4, "b": 4}, 8)
    assert _cus(points) == {"a": 4, "b": 4} and reason == "hysteresis"


def test_policy_admits_parked_tenant_with_new_work():
    from repro.configs import get_reduced
    cfgs = {"a": get_reduced("minitron-4b"), "b": get_reduced("minitron-4b")}
    points, reason = AnalyticalPolicy().decide(
        {"a": _load(10), "b": _load(10)}, cfgs, {"a": 8, "b": 0}, 8)
    assert reason == "admit" and _cus(points).get("b", 0) >= 1


def test_decide_legacy_keyword_form_is_gone():
    """The PR-5 calling convention (TenantLoad values + classes=/lengths=
    side channels) rode one release behind a DeprecationWarning and was
    deleted when the grace window closed (the fabriclint deprecation rule
    is the enforcement; see docs/static-analysis.md)."""
    from repro.configs import get_reduced
    cfgs = {"a": get_reduced("minitron-4b"), "b": get_reduced("minitron-4b")}
    obs = {"a": _load(100), "b": _load(0)}
    with pytest.raises(TypeError):
        AnalyticalPolicy().decide(obs, cfgs, {"a": 4, "b": 4}, 8,
                                  classes={"a": "decode"})


# ---------------------------------------------------------------------------
# device scenarios (8 fake host devices, subprocess)
# ---------------------------------------------------------------------------

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import numpy as np
from repro.launch.mesh import make_host_mesh
"""


def _run(body: str, timeout=900):
    out = subprocess.run([sys.executable, "-c",
                          _PRELUDE + textwrap.dedent(body)],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_recomposition_preserves_decode_numerics():
    """Tokens across a mid-stream grow -> shrink -> unify sequence match a
    never-recomposed run bit-exactly (acceptance criterion)."""
    res = _run("""
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.distribution import strip
    from repro.models import build_model
    from repro.serve import ServeConfig, ServeEngine

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh)
    cfg = get_reduced("minitron-4b")
    sc = ServeConfig(max_slots=2, max_len=64, eos_id=-1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(4, 12))) for _ in range(3)]

    def run(script):
        model = build_model(cfg)
        params = strip(model.init(jax.random.key(0)))
        eng = ServeEngine(model, params, sc, mesh=comp.submesh(range(4), "t"))
        for p in prompts:
            eng.submit(p, max_new_tokens=10)
        step = 0
        while eng._queue or eng._active:
            if step in script:
                ids, name = script[step]
                eng.reshard_to(comp.submesh(ids, name))
            eng.step()
            step += 1
            assert step < 200
        return {str(r): t for r, t in eng.results().items()}

    ref = run({})
    dyn = run({3: (range(6), "grown"), 7: (range(2), "shrunk"),
               11: (range(8), "unified")})
    print(json.dumps({"match": ref == dyn, "n": len(ref)}))
    """)
    assert res["n"] == 3 and res["match"], "recomposition changed numerics"


def test_composed_server_delta_leaves_unmoved_tenant_devices():
    """ComposedServer.recompose: the unchanged tenant keeps the SAME mesh
    devices; moved tenants' params land on their new sub-mesh."""
    res = _run("""
    from repro.serve.fabric import ComposedServer, TenantSpec
    from repro.serve import ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    sc = ServeConfig(max_slots=2, max_len=32, eos_id=-1)
    srv = ComposedServer(mesh, [
        TenantSpec("a", "minitron-4b", serve=sc),
        TenantSpec("b", "minitron-4b", seed=1, serve=sc),
        TenantSpec("c", "minitron-4b", seed=2, serve=sc),
    ], policy=None)                      # sizes: a=3, b=3, c=2

    def devs(t):
        leaf = jax.tree.leaves(srv.engines[t].params)[0]
        return sorted(d.id for d in leaf.sharding.device_set)

    c_before_sub = srv.subs["c"]
    c_before_devs = devs("c")
    ev = srv.recompose({"a": 4, "b": 2, "c": 2})
    print(json.dumps({
        "c_same_sub": srv.subs["c"] is c_before_sub,
        "c_devs_same": devs("c") == c_before_devs,
        "unchanged": list(ev.unchanged), "moved": sorted(ev.moved),
        "a_ndev": len(devs("a")), "b_ndev": len(devs("b")),
    }))
    """)
    assert res["c_same_sub"] and res["c_devs_same"]
    assert res["unchanged"] == ["c"] and res["moved"] == ["a", "b"]
    assert res["a_ndev"] == 4 and res["b_ndev"] == 2


def test_tp_decode_equivalence_across_degrees():
    """Same prompts through 1-way (replicated), 2-way and 4-way TP
    sub-meshes must emit identical token streams, including across a
    mid-stream reshard_to() that changes the TP degree (satellite +
    tentpole acceptance: sharded decode is an implementation detail, never
    a numerics change a user can observe)."""
    res = _run("""
    import dataclasses
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.models import build_model
    from repro.serve import ServeConfig, ServeEngine, serve_engine_rules

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh)
    # fp32: greedy argmax must be reduction-order-proof across TP degrees
    cfg = dataclasses.replace(get_reduced("minitron-4b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sc = ServeConfig(max_slots=2, max_len=64, eos_id=-1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(4, 12))) for _ in range(3)]

    def run(tp, rules, script=None):
        eng = ServeEngine(model, params, sc,
                          mesh=comp.submesh(range(tp), f"tp{tp}"),
                          rules=rules)
        for p in prompts:
            eng.submit(p, max_new_tokens=10)
        step = 0
        while eng.has_work:
            if script and step in script:
                eng.reshard_to(comp.submesh(range(script[step]), "re"))
            eng.step()
            step += 1
            assert step < 200
        return {str(r): t for r, t in eng.results().items()}

    rules = serve_engine_rules()
    ref = run(1, None)                           # replicated baseline
    tp2 = run(2, rules)
    tp4 = run(4, rules)
    dyn = run(4, rules, {3: 2, 7: 8, 11: 4})     # shrink -> unify -> back
    print(json.dumps({"n": len(ref), "tp2": tp2 == ref, "tp4": tp4 == ref,
                      "dyn": dyn == ref}))
    """)
    assert res["n"] == 3
    assert res["tp2"] and res["tp4"], "TP decode diverged from replicated"
    assert res["dyn"], "mid-stream TP-degree change altered the stream"


def test_warm_recompose_skips_post_move_compile():
    """With warming on, the target composition's executables are built
    before the switch commits: the first post-move step performs zero cold
    compiles, and the engine is actually sharded over its new sub-mesh."""
    res = _run("""
    from repro.serve.fabric import ComposedServer, TenantSpec
    from repro.serve import ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    sc = ServeConfig(max_slots=2, max_len=32, eos_id=-1)
    srv = ComposedServer(mesh, [
        TenantSpec("a", "minitron-4b", serve=sc),
        TenantSpec("b", "minitron-4b", seed=1, serve=sc),
    ], policy=None, tp=True, warm=True)          # sizes: a=4, b=4
    rng = np.random.default_rng(0)
    vocab = srv.cfgs["a"].vocab_size
    for t in ("a", "b"):
        srv.submit(t, rng.integers(1, vocab, size=8), max_new_tokens=16)
    for _ in range(3):
        srv.step()                               # executables for 4+4 built

    ev = srv.recompose({"a": 6, "b": 2})
    builds_after_warm = {t: srv.engines[t].compile_builds for t in "ab"}
    srv.step()                                   # first post-move step
    builds_after_step = {t: srv.engines[t].compile_builds for t in "ab"}

    def tp_degree(t):
        leaf = jax.tree.leaves(srv.engines[t].params)[0]
        return len(leaf.sharding.device_set)

    print(json.dumps({
        "warm_builds": ev.warm_builds,
        "warm_seconds_pos": ev.warm_compile_seconds > 0,
        "cold_after_move": {t: builds_after_step[t] - builds_after_warm[t]
                            for t in "ab"},
        "a_ndev": tp_degree("a"), "b_ndev": tp_degree("b"),
        "post_step_recorded": sorted(ev.post_step_seconds),
    }))
    """)
    assert res["warm_builds"] >= 2 and res["warm_seconds_pos"]
    assert res["cold_after_move"] == {"a": 0, "b": 0}, \
        "post-recomposition step recompiled despite warming"
    assert res["a_ndev"] == 6 and res["b_ndev"] == 2
    assert res["post_step_recorded"] == ["a", "b"]


def test_prewarm_async_commits_after_background_compile():
    """prewarm_async: the policy's chosen composition compiles in a
    background thread while the old composition keeps serving; the switch
    commits on a later autoscale tick, marked `overlapped`, and every
    request still completes with its full budget."""
    res = _run("""
    import time
    from repro.serve.fabric import (AnalyticalPolicy, ComposedServer,
                                    TenantSpec)
    from repro.serve import ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    sc = ServeConfig(max_slots=2, max_len=64, eos_id=-1)
    srv = ComposedServer(mesh, [
        TenantSpec("a", "minitron-4b", serve=sc),
        TenantSpec("b", "minitron-4b", seed=1, serve=sc),
    ], policy=AnalyticalPolicy(), decide_every=2, prewarm_async=True)
    rng = np.random.default_rng(0)
    vocab = srv.cfgs["a"].vocab_size
    for _ in range(4):
        srv.submit("a", rng.integers(1, vocab, size=8), max_new_tokens=24)
    steps = 0
    while (not srv.events) and steps < 300:
        srv.step()
        if srv._pending_prewarm is not None:
            time.sleep(0.05)      # let the compile thread make progress
        steps += 1
    out = srv.drain(max_steps=400)
    lens = sorted(len(v) for v in out["a"].values())
    print(json.dumps({
        "events": len(srv.events),
        "overlapped": [e.overlapped for e in srv.events],
        "lens": lens,
    }))
    """)
    assert res["events"] >= 1
    assert res["overlapped"][0] is True, \
        "first recomposition should commit from the background prewarm"
    assert res["lens"] == [24, 24, 24, 24]


def test_replica_group_routing_and_merged_stats():
    """ReplicaGroup under skewed request lengths: least-loaded routing
    keeps owed work balanced across replicas (no replica ends up with all
    the long streams), the group-merged load signals equal the sums over
    ``per_replica`` stats, and every request completes with its full
    budget under its stable group rid."""
    res = _run("""
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.core.dse import DesignPoint
    from repro.models import build_model
    from repro.serve import ReplicaGroup, ServeConfig, serve_engine_rules
    from repro.workloads import DECODE

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh)
    cfg = get_reduced("minitron-4b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sc = ServeConfig(max_slots=2, max_len=64, eos_id=-1)
    grp = ReplicaGroup(DECODE, model, params, sc,
                       sub=comp.submesh(range(4), "t"),
                       rules=serve_engine_rules())
    grp.apply(None, DesignPoint(cus=4, tp=1, dp=4))
    rng = np.random.default_rng(0)
    budgets = [32, 2, 32, 2, 32, 2, 32, 2]        # skewed lengths
    rids = [grp.submit(rng.integers(1, cfg.vocab_size, size=6),
                       max_new_tokens=b) for b in budgets]
    owed = [r.pending_tokens() for r in grp.replicas]
    queued = [r.queue_depth + r.active_count for r in grp.replicas]
    st = grp.stats()
    merged_ok = (
        st["dp"] == 4 and len(st["per_replica"]) == 4
        and st["pending_tokens"] == sum(owed) == grp.pending_tokens()
        and st["queue_depth"] == sum(r.queue_depth for r in grp.replicas)
        and st["active"] == sum(r.active_count for r in grp.replicas)
        and abs(st["arena_utilization"]
                - sum(r.arena_utilization() for r in grp.replicas) / 4)
            < 1e-6)
    out = grp.run_to_completion(400)
    print(json.dumps({
        "owed": owed, "queued": queued, "merged_ok": merged_ok,
        "rids": rids,
        "lens": {str(r): len(out[r]) for r in rids},
    }))
    """)
    assert res["merged_ok"], "group stats disagree with per-replica sums"
    assert res["rids"] == list(range(8))            # stable group rids
    # every replica took work, and the owed spread stays below one long
    # request (least-loaded routing: nobody hoards the 32-token streams)
    assert min(res["queued"]) >= 1, res
    assert max(res["owed"]) - min(res["owed"]) < 32, res
    assert res["lens"] == {str(i): b for i, b in
                           enumerate([32, 2, 32, 2, 32, 2, 32, 2])}


def test_dp_replica_streams_bit_identical():
    """Acceptance: which replica serves a request never changes its tokens.
    dp=2 streams match the dp=1 baseline bit-exactly, and so does a run
    whose replica count is retuned mid-stream (1 -> 2 -> 4 -> 1) while
    requests are live — adoption copies cache rows exactly, never
    re-prefills."""
    res = _run("""
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.core.dse import DesignPoint
    from repro.models import build_model
    from repro.serve import ReplicaGroup, ServeConfig, serve_engine_rules
    from repro.workloads import DECODE

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh)
    cfg = get_reduced("minitron-4b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sc = ServeConfig(max_slots=4, max_len=64, eos_id=-1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(4, 12))) for _ in range(4)]

    def run(dp0, script):
        grp = ReplicaGroup(DECODE, model, params, sc,
                           sub=comp.submesh(range(4), "t"),
                           rules=serve_engine_rules())
        # tp pinned at 1: the dp axis must be the ONLY thing that varies
        grp.apply(None, DesignPoint(cus=4, tp=1, dp=dp0))
        for p in prompts:
            grp.submit(p, max_new_tokens=10)
        step = 0
        while grp.has_work:
            if step in script:
                grp.apply(None, DesignPoint(cus=4, dp=script[step]))
            grp.step()
            step += 1
            assert step < 200
        return {str(r): t for r, t in grp.results().items()}

    ref = run(1, {})
    dp2 = run(2, {})
    dyn = run(1, {3: 2, 6: 4, 9: 1})
    print(json.dumps({"n": len(ref), "dp2": dp2 == ref, "dyn": dyn == ref}))
    """)
    assert res["n"] == 4
    assert res["dp2"], "dp=2 streams diverged from the dp=1 baseline"
    assert res["dyn"], "mid-stream dp retune altered a live stream"


@pytest.mark.slow
def test_traffic_driven_autoscale_end_to_end():
    """Policy-driven fabric: a burst triggers at least one recomposition and
    every request still completes with its full token budget."""
    res = _run("""
    from repro.serve.fabric import (AnalyticalPolicy, ComposedServer,
                                    TenantSpec)
    from repro.serve import ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    sc = ServeConfig(max_slots=2, max_len=64, eos_id=-1)
    srv = ComposedServer(mesh, [
        TenantSpec("a", "minitron-4b", serve=sc),
        TenantSpec("b", "minitron-4b", seed=1, serve=sc),
    ], policy=AnalyticalPolicy(), decide_every=4)
    rng = np.random.default_rng(0)
    vocab = srv.cfgs["a"].vocab_size
    for _ in range(3):
        srv.submit("a", rng.integers(1, vocab, size=8), max_new_tokens=12)
    srv.submit("b", rng.integers(1, vocab, size=8), max_new_tokens=6)
    out = srv.drain(max_steps=400)
    lens = {t: sorted(len(v) for v in d.values()) for t, d in out.items()}
    print(json.dumps({"recomps": len(srv.events), "lens": lens}))
    """)
    assert res["recomps"] >= 1
    assert res["lens"]["a"] == [12, 12, 12]
    assert res["lens"]["b"] == [6]
