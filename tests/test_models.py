"""Per-architecture smoke tests (deliverable (f)): every assigned arch
instantiates its REDUCED config and runs one forward/train step on CPU,
asserting output shapes and finiteness; plus decode-vs-prefill consistency
(the serving path computes the same function as the parallel path)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced, cells_for
from repro.distribution import strip
from repro.models import build_model

B, S = 2, 16


def _batch(cfg, rng_key=1):
    tokens = jax.random.randint(jax.random.key(rng_key), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(jax.random.key(rng_key + 1),
                                            (B, S, cfg.d_model))
    return batch


@pytest.fixture(scope="module")
def models():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_reduced(arch)
            m = build_model(cfg)
            cache[arch] = (cfg, m, strip(m.init(jax.random.key(0))))
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_loss(models, arch):
    cfg, m, params = models(arch)
    loss, metrics = m.loss(params, _batch(cfg))
    assert loss.shape == ()
    assert jnp.isfinite(loss), (arch, loss)
    assert jnp.isfinite(metrics["xent"])


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(models, arch):
    """One optimizer step: params change, everything stays finite."""
    from repro.optim import make_optimizer
    from repro.train.trainer import TrainConfig, make_train_step

    cfg, m, params = models(arch)
    opt = make_optimizer(cfg.optimizer)
    step = make_train_step(m, opt, TrainConfig(steps=4, lr=1e-3, warmup=1))
    opt_state = opt.init(params)
    # step 1: the cosine schedule's lr is 0 at step 0 (warmup ramp)
    new_params, _, metrics = step(params, opt_state, jnp.asarray(1),
                                  _batch(cfg))
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    delta = sum(float(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)).sum())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(new_params)))
    assert delta > 0.0, f"{arch}: params did not move"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_prefill(models, arch):
    cfg, m, params = models(arch)
    batch = _batch(cfg)
    tokens = batch["tokens"]
    sl = S if cfg.is_encdec else 0
    if cfg.is_encdec:
        batch["frames"] = batch["frames"]
    full_logits, _ = m.prefill(params, batch,
                               strip(m.init_cache(B, 2 * S, src_len=sl)))
    k = S // 2
    cache = strip(m.init_cache(B, 2 * S, src_len=sl))
    pre = dict(batch)
    pre["tokens"] = tokens[:, :k]
    logits, cache = m.prefill(params, pre, cache)
    for i in range(k, S):
        logits, cache = m.decode_step(params, cache, tokens[:, i:i + 1])
    err = float(jnp.max(jnp.abs(logits.astype(jnp.float32)
                                - full_logits.astype(jnp.float32))))
    assert err < 2e-1, (arch, err)


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "granite-34b",
                                  "deepseek-v2-lite-16b", "chameleon-34b"])
def test_padded_prefill_matches_exact(models, arch):
    """true_len-masked padded prefill == exact-length prefill (attention
    archs only; SSM state is padding-sensitive by design — engine uses
    exact-length prefill there)."""
    cfg, m, params = models(arch)
    batch = _batch(cfg)
    k, pad = 10, 6
    exact = dict(batch)
    exact["tokens"] = batch["tokens"][:, :k]
    le, _ = m.prefill(params, exact, strip(m.init_cache(B, 2 * S)))
    padded = dict(batch)
    padded["tokens"] = jnp.concatenate(
        [batch["tokens"][:, :k], jnp.zeros((B, pad), jnp.int32)], axis=1)
    lp, _ = m.prefill(params, padded, strip(m.init_cache(B, 2 * S)),
                      true_len=k)
    np.testing.assert_allclose(np.asarray(lp, np.float32),
                               np.asarray(le, np.float32), atol=1e-2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_shapes(arch):
    """The FULL configs are exercised abstractly (no allocation): eval_shape
    the init and one loss; assert the declared parameter count matches the
    materialized tree within 2%."""
    cfg = get_config(arch)
    m = build_model(cfg)
    shapes = jax.eval_shape(m.init, jax.random.key(0))
    total = sum(int(np.prod(l.value.shape)) for l in jax.tree.leaves(
        shapes, is_leaf=lambda x: hasattr(x, "logical")))
    declared = cfg.param_count()
    assert abs(total - declared) / declared < 0.02, (arch, total, declared)


def test_cells_for_documented_skips():
    """long_500k runs exactly for the sub-quadratic archs (DESIGN.md §4)."""
    long_archs = {a for a in ARCH_IDS
                  if any(c.name == "long_500k"
                         for c in cells_for(get_config(a)))}
    assert long_archs == {"hymba-1.5b", "falcon-mamba-7b"}
    for a in ARCH_IDS:
        names = [c.name for c in cells_for(get_config(a))]
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(names)


def test_continuous_batching_vector_positions(models):
    """Slots at different cache depths decode correctly in one batch."""
    cfg, m, params = models("minitron-4b")
    tokens = jax.random.randint(jax.random.key(3), (2, 12), 0, cfg.vocab_size)
    # row 0 prefilled with 8 tokens, row 1 with 5 (padded prefill+true_len)
    cache = strip(m.init_cache(2, 24))
    padded = jnp.where(jnp.arange(12)[None, :] <
                       jnp.asarray([[8], [5]]), tokens, 0)
    _, cache = m.prefill(params, {"tokens": padded}, cache,
                         true_len=jnp.asarray([8, 5]))
    # decode one token per row; compare against per-row references
    nxt = tokens[:, [8]] * 0 + 7
    logits, _ = m.decode_step(params, cache, nxt)
    for r, plen in enumerate((8, 5)):
        c1 = strip(m.init_cache(1, 24))
        _, c1 = m.prefill(params, {"tokens": tokens[r:r + 1, :plen]}, c1)
        ref, _ = m.decode_step(params, c1, nxt[r:r + 1])
        np.testing.assert_allclose(np.asarray(logits[r], np.float32),
                                   np.asarray(ref[0], np.float32), atol=1e-2)


# ---------------------------------------------------------------------------
# bf16 weights: Model.init casts inside one jitted program, so the device
# never holds the float32 tree — and nothing downstream changes
# ---------------------------------------------------------------------------

def _as_param_dtype(cfg, dtype):
    return dataclasses.replace(cfg, param_dtype=dtype)


@pytest.mark.parametrize("arch", ["minitron-4b", "qwen2.5-32b",
                                  "falcon-mamba-7b", "seamless-m4t-medium"])
def test_bf16_init_equals_float32_init_cast(arch):
    cfg = get_reduced(arch)
    key = jax.random.key(4)
    ref = strip(build_model(_as_param_dtype(cfg, "float32")).init(key))
    ref = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, ref)
    got = strip(build_model(_as_param_dtype(cfg, "bfloat16")).init(key))
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert g.dtype == r.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_init_on_mesh_places_leaves_and_keeps_values():
    from repro.launch.mesh import make_host_mesh
    from repro.serve import serve_engine_rules
    cfg = get_reduced("minitron-4b")
    m = build_model(cfg)
    mesh = make_host_mesh((1, 1))
    got = strip(m.init(jax.random.key(2), mesh=mesh,
                       rules=serve_engine_rules()))
    ref = strip(m.init(jax.random.key(2)))
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert g.sharding.mesh.shape == mesh.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_bf16_weights_leave_greedy_streams_unchanged():
    """Every layer casts weights to the (bf16) activation dtype where it
    uses them, so bf16 weights serve exactly the float32 weights' tokens."""
    from repro.workloads import DecodeEngine, ServeConfig
    cfg = get_reduced("minitron-4b")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (5, 11, 3)]

    def serve(dtype):
        m = build_model(_as_param_dtype(cfg, dtype))
        eng = DecodeEngine(m, strip(m.init(jax.random.key(1))),
                           ServeConfig(max_slots=2, max_len=48, eos_id=-1))
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        return eng.run_to_completion(200)

    assert serve("bfloat16") == serve("float32")
