"""Serving-engine invariants that don't need devices: the pooled-cache slot
write is positional (a 1-slot pool behaves like an N-slot one), admission
backpressure is distinguished from real allocator bugs, and pipelined decode
dispatch is an observably pure reordering of host synchronization."""
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.arena import AllocationError
from repro.distribution import strip
from repro.models import build_model
from repro.obs import Telemetry
from repro.serve import ExecutableCache, ServeConfig, ServeEngine

import jax


@pytest.fixture(scope="module")
def qwen():
    cfg = get_reduced("qwen2.5-32b")
    model = build_model(cfg)
    params = strip(model.init(jax.random.key(0)))
    return cfg, model, params


def _serve(model, params, **kw):
    defaults = dict(max_slots=3, max_len=48, eos_id=-1, prefill_bucket=8)
    defaults.update(kw)
    return ServeEngine(model, params, ServeConfig(**defaults))


def _submit_all(eng, cfg, n=4, seed=0, new=6):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        plen = int(rng.integers(4, 14))
        eng.submit(rng.integers(1, cfg.vocab_size, size=plen),
                   max_new_tokens=new)


# ---------------------------------------------------------------------------
# satellite: _write_slot must not drop the prefill when max_slots == 1
# ---------------------------------------------------------------------------

def test_single_slot_pool_receives_prefill(qwen):
    """With a (1, ...) pool and a (1, ...) single cache, shape-mismatch
    inference can't tell them apart; the explicit slot-axis write must
    still land — streams match a multi-slot engine's exactly."""
    cfg, model, params = qwen
    prompt = np.arange(1, 9) % cfg.vocab_size

    def run(slots):
        eng = _serve(model, params, max_slots=slots)
        eng.submit(prompt, max_new_tokens=5)
        return eng.run_to_completion(100)

    one, four = run(1), run(4)
    assert one == four
    # a dropped prefill decodes from an all-zeros cache: the first decode
    # token would disagree with the offline prefill's argmax
    import jax.numpy as jnp
    cache = strip(model.init_cache(1, 48))
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                              cache)
    assert one[0][0] == int(jnp.argmax(logits[0]))


# ---------------------------------------------------------------------------
# satellite: admission backpressure vs real allocator bugs
# ---------------------------------------------------------------------------

def test_admit_arena_full_is_backpressure(qwen):
    cfg, model, params = qwen
    eng = _serve(model, params, max_slots=2)

    def full_alloc(*a, **kw):
        raise AllocationError("arena full: need 1, free 0")

    eng.arena.alloc = full_alloc
    eng.submit(np.arange(1, 6), max_new_tokens=4)
    eng.step()                      # no crash: request just stays queued
    assert eng.queue_depth == 1 and eng.active_count == 0


def test_admit_propagates_non_allocation_bugs(qwen):
    """A TypeError (bad sizes, dtype bugs) in FlexArena.alloc must surface,
    not masquerade as admission backpressure."""
    cfg, model, params = qwen
    eng = _serve(model, params, max_slots=2)

    def broken_alloc(*a, **kw):
        raise TypeError("rows must be int")

    eng.arena.alloc = broken_alloc
    eng.submit(np.arange(1, 6), max_new_tokens=4)
    with pytest.raises(TypeError):
        eng.step()


# ---------------------------------------------------------------------------
# pipelined decode dispatch
# ---------------------------------------------------------------------------

def test_pipelined_decode_matches_sync(qwen):
    cfg, model, params = qwen

    def run(pipeline):
        eng = _serve(model, params, pipeline_decode=pipeline)
        _submit_all(eng, cfg, n=5)
        return eng.run_to_completion(200)

    assert run(True) == run(False)


def test_pipelined_survives_midstream_snapshots(qwen):
    """snapshot()/results() force an early harvest of the in-flight step;
    the engine must re-inject the harvested tokens, not feed zeros."""
    cfg, model, params = qwen
    ref = _serve(model, params, pipeline_decode=False)
    _submit_all(ref, cfg, n=4)
    want = ref.run_to_completion(200)

    eng = _serve(model, params, pipeline_decode=True)
    _submit_all(eng, cfg, n=4)
    steps = 0
    while eng.has_work:
        eng.step()
        eng.snapshot()              # harvests the in-flight dispatch
        steps += 1
        assert steps < 200
    assert eng.snapshot() == want


def test_eos_keeps_synchronous_path(qwen):
    """eos termination needs the token value before the next dispatch, so
    pipelining must auto-disable; streams stop at (or before) eos."""
    cfg, model, params = qwen
    eng = _serve(model, params, eos_id=3, pipeline_decode=True)
    _submit_all(eng, cfg, n=3, new=8)
    out = eng.run_to_completion(200)
    for toks in out.values():
        assert len(toks) <= 8
        if 3 in toks:
            assert toks.index(3) == len(toks) - 1   # nothing emitted past eos


# ---------------------------------------------------------------------------
# executable cache
# ---------------------------------------------------------------------------

def test_executable_cache_lru_and_counters():
    cache = ExecutableCache(capacity=2)
    assert cache.get_or_build("a", lambda: "A") == "A"
    assert cache.get_or_build("a", lambda: "A2") == "A"     # hit, no rebuild
    assert cache.builds == 1 and cache.hits == 1
    assert cache.ensure("a", lambda: "A3") == 0             # warm no-op
    cache.get_or_build("b", lambda: "B")
    cache.get_or_build("c", lambda: "C")                    # evicts oldest
    assert not cache.contains("a") and cache.contains("b")
    assert cache.builds == 3


def test_engine_reuses_decode_executable(qwen):
    """One decode program per (mesh, shapes): repeated steps never rebuild."""
    cfg, model, params = qwen
    eng = _serve(model, params)
    _submit_all(eng, cfg, n=3)
    for _ in range(3):
        eng.step()
    builds = eng.compile_builds
    eng.run_to_completion(200)
    assert eng.compile_builds == builds


# ---------------------------------------------------------------------------
# decode-step telemetry: harvest waits, KV rows, program names
# ---------------------------------------------------------------------------

def test_step_histograms_one_observation_per_decode_step(qwen):
    """harvest_wait_s, kv_live_rows and kv_reserved_rows are observed once
    per decode step; harvests outside a step (snapshot, results, an idle
    step) add none; kv_live_rows sums the KV rows each dispatch attends."""
    cfg, model, params = qwen
    obs = Telemetry()
    eng = ServeEngine(model, params, ServeConfig(
        max_slots=3, max_len=48, eos_id=-1, prefill_bucket=8), obs=obs)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n))
               for n in rng.integers(4, 14, size=5)]
    new = 6
    for p in prompts:
        eng.submit(p, max_new_tokens=new)
    reg = obs.registry
    names = ("decode_step_s", "harvest_wait_s", "kv_live_rows",
             "kv_reserved_rows")
    steps = 0
    while eng.has_work:
        eng.step()
        eng.snapshot()              # harvests the in-flight dispatch
        eng.results()
        steps += 1
        assert steps < 200
    eng.step()                      # idle
    counts = {n: reg.histogram(n).count for n in names}
    assert counts["decode_step_s"] > 0
    assert len(set(counts.values())) == 1, counts
    # the first token comes from the prefill; each later one from a
    # dispatch over prompt + k rows, k = 1 .. new - 1
    want = sum(len(p) + k for p in prompts for k in range(1, new))
    assert reg.histogram("kv_live_rows").sum == want
    assert reg.histogram("kv_reserved_rows").sum == \
        counts["kv_reserved_rows"] * 3 * 48
    assert 0.0 <= reg.histogram("harvest_wait_s").sum <= \
        reg.histogram("decode_step_s").sum


def test_programs_are_named_for_traces(qwen):
    """The decode and prefill programs show in a profiler trace as
    jit_decode_step and jit_prefill."""
    cfg, model, params = qwen
    eng = _serve(model, params)
    decode = eng._decode_exec(eng.mesh, eng._decode_bounds())
    prefill = eng._prefill_exec(eng.mesh, 8)
    assert decode.as_text().startswith("HloModule jit_decode_step,")
    assert prefill.as_text().startswith("HloModule jit_prefill,")
