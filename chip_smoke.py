"""Chip smoke: the serving fabric's main path on a TPU, at published widths.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --four-chips      # 2x2 composition + live move

One chip (the default): minitron-4b at full width (32 layers, d_model 3072,
24 heads / 8 KV heads, head_dim 128, d_ff 9216, vocab 256000) with bf16
weights drawn from ``--seed``, served by ``ComposedServer`` as one tenant on
the chip's (1, 1) mesh, its ``DecodeEngine`` running the compiled ragged
decode kernel.  Eight requests (prompts of 64-256 tokens, 32 new tokens
each) must complete; the compiled kernel must match its reference at the
serving shapes, and the engine's first decode step must match a plain
prefill forward.

``--four-chips``: two full-width minitron-4b tenants, each on 2 CUs with
TP 2, serve a few requests; one live recomposition 2+2 -> 1+3 follows.
Every stream must complete across the move, each tenant's params and cache
must sit on exactly its own sub-mesh before and after it, and the moved
tenant's first-step logits at TP 2 must match its own one-chip logits.

Everything runs in this one process (a chip belongs to one process).  The
script fails, and prints no result, when JAX finds no TPU.  The last line
of stdout is the JSON result; earlier lines say what was checked.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common.jax_cache import setup_compile_cache  # noqa: E402
from repro.common.platform import device_profile  # noqa: E402
from repro.distribution import strip  # noqa: E402
from repro.kernels.ragged_decode import (  # noqa: E402
    ragged_decode_attention, ragged_decode_attention_ref)
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.serve import ComposedServer, ServeConfig, TenantSpec  # noqa: E402
from repro.workloads.decode import KV_BOUND_BLOCK  # noqa: E402

ARCH = "minitron-4b"
MAX_NEW = 32
# compiled kernel vs reference, both bf16 out: |kernel - ref| <= ATOL +
# RTOL * |ref|.  bf16 rounding of the probabilities fed to the PV matmul
# (both sides) and of the output is ~4e-3 relative; the flash rescaling
# only reorders f32 sums.
KERNEL_ATOL = KERNEL_RTOL = 2e-2
# first-step logits vs the reference forward: max |diff| <= LOGITS_RTOL *
# max |ref|.  Both run 32 bf16 layers whose reductions are ordered
# differently (ragged kernel over the cache vs one prompt-length forward),
# so they agree to bf16 accumulation drift, not bit for bit.
LOGITS_RTOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_summary() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> int:
    """Largest ``peak_bytes_in_use`` over the devices (0 if unreported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def prompts(rng, vocab: int, n: int):
    return [rng.integers(1, vocab, size=int(rng.integers(64, 257)))
            for _ in range(n)]


def serve_config(slots: int) -> ServeConfig:
    return ServeConfig(max_slots=slots, max_len=512, eos_id=-1)


def first_step_logits(model, params, mesh, prompt, max_len: int):
    """The engine's first decode step for ``prompt``, and its reference.

    The prompt is prefilled into a fresh one-slot cache and its greedy
    first token decoded by ``Model.decode_step`` with the ragged kernel at
    the engine's KV bound, traced under ``mesh`` as the engine traces its
    decode program.  The reference is a plain prefill forward over prompt +
    that token.  Returns (decode-step logits, reference logits) as float32
    host arrays."""
    L = len(prompt)
    bound = -(-(L + 1) // KV_BOUND_BLOCK) * KV_BOUND_BLOCK

    def run(params, toks):
        cache = strip(model.init_cache(1, max_len))
        logits, cache = model.prefill(params, {"tokens": toks}, cache)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        step, _ = model.decode_step(params, cache, first, use_kernels=True,
                                    kv_bound=bound,
                                    live_mask=jnp.ones((1,), bool))
        ref, _ = model.prefill(
            params, {"tokens": jnp.concatenate([toks, first], axis=1)},
            strip(model.init_cache(1, max_len)))
        return step[0], ref[0]

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        step, ref = jax.jit(run)(params, jnp.asarray(prompt[None], jnp.int32))
    return (np.asarray(step, np.float32), np.asarray(ref, np.float32))


def logits_agree(step, ref, what: str) -> bool:
    err = float(np.max(np.abs(step - ref)))
    scale = float(np.max(np.abs(ref)))
    ok = bool(np.isfinite(step).all()) and err <= LOGITS_RTOL * scale
    log(f"{what}: max|diff| {err:.6g}, max|ref| {scale:.6g}, "
        f"limit {LOGITS_RTOL} x max|ref|, top-1 {int(step.argmax())} vs "
        f"{int(ref.argmax())} -> {'ok' if ok else 'FAIL'}")
    return ok


def kernel_check(seed: int) -> bool:
    """Compiled ragged kernel vs ``ragged_decode_attention_ref`` at the
    serving shapes: bf16 cache, Hkv 8, G 3, D 128, ragged lengths (block
    boundaries included), one dead slot."""
    B, T, hkv, g, D = 8, 512, 8, 3, 128
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, hkv * g, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, T, hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, T, hkv, D)), jnp.bfloat16)
    lens = jnp.asarray([1, 37, 128, 129, 255, 300, 512, 77], jnp.int32)
    live = jnp.asarray([1, 1, 1, 0, 1, 1, 1, 1], bool)
    kern = jax.jit(lambda q, k, v, n, lv: ragged_decode_attention(
        q, k, v, n, live=lv)).lower(q, k, v, lens, live).compile()
    custom = "tpu_custom_call" in kern.as_text()
    out = np.asarray(kern(q, k, v, lens, live), np.float32)
    ref = np.asarray(ragged_decode_attention_ref(q, k, v, lens, live=live),
                     np.float32)
    err = np.abs(out - ref)
    within = bool(np.all(err <= KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)))
    dead_zero = bool(np.all(out[3] == 0.0))
    ok = custom and within and dead_zero
    log(f"kernel vs ref (B={B} T={T} Hkv={hkv} G={g} D={D} bf16): "
        f"tpu_custom_call={custom} max|diff| {float(err.max()):.6g} "
        f"(atol {KERNEL_ATOL}, rtol {KERNEL_RTOL}) dead slot zero="
        f"{dead_zero} -> {'ok' if ok else 'FAIL'}")
    return ok


def decode_kernel_compiled(server) -> bool:
    """Every decode executable the fabric compiled calls a Mosaic kernel
    (neither interpreted nor replaced by the reference)."""
    decodes = [exe for key, exe in server.exec_cache.items()
               if key[0] == "decode"]
    ok = bool(decodes) and all("tpu_custom_call" in exe.as_text()
                               for exe in decodes)
    log(f"decode executables: {len(decodes)}, all with tpu_custom_call: "
        f"{ok}")
    return ok


def compile_report(server) -> None:
    h = server.metrics().merged_histogram("compile_build_s")
    log(f"compilations: engine builds {server.stats()['compile_builds']}, "
        f"executable cache {server.exec_cache.snapshot()}, "
        f"{h.count} builds took {h.sum:.3f} s (host wall clock)")


def model_summary(cfg) -> str:
    return (f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.num_heads} heads / {cfg.num_kv_heads} KV heads, head_dim "
            f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
            f"{cfg.vocab_size}, weights {cfg.param_dtype}")


def param_summary(params) -> str:
    leaves = jax.tree.leaves(params)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    nbytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    dtypes = sorted({str(x.dtype) for x in leaves})
    return f"{n} parameters, {nbytes} bytes, dtypes {dtypes}"


def complete(results, budget: int) -> bool:
    return all(len(toks) == budget for streams in results.values()
               for toks in streams.values())


def serve_one_chip(seed: int) -> bool:
    mesh = make_host_mesh((1, 1))
    t0 = time.monotonic()
    server = ComposedServer(
        mesh, [TenantSpec(ARCH, ARCH, reduced=False, serve=serve_config(8),
                          seed=seed)],
        policy=None)
    eng = server.engines[ARCH].replicas[0]
    jax.block_until_ready(eng.params)
    log(model_summary(eng.model.cfg))
    log(f"init + placement {time.monotonic() - t0:.3f} s (host wall "
        f"clock); {param_summary(eng.params)}")

    rng = np.random.default_rng(seed)
    reqs = prompts(rng, eng.model.cfg.vocab_size, 8)
    log(f"prompt lengths {[len(p) for p in reqs]}, {MAX_NEW} new tokens "
        f"each, max_slots 8, max_len 512, eos_id -1")
    for p in reqs:
        server.submit(ARCH, p, max_new_tokens=MAX_NEW)
    results = server.drain(max_steps=10 * MAX_NEW)
    streams = results[ARCH]
    emitted = sum(len(t) for t in streams.values())
    n_done = sum(len(t) == MAX_NEW for t in streams.values())
    done = complete(results, MAX_NEW) and len(streams) == 8
    log(f"requests completed {n_done}/8, tokens emitted {emitted} -> "
        f"{'ok' if done else 'FAIL'}")

    custom = decode_kernel_compiled(server)
    kern = kernel_check(seed)
    step, ref = first_step_logits(eng.model, eng.params, eng.mesh, reqs[0],
                                  eng.cfg.max_len)
    logits = logits_agree(step, ref, "first decode step vs prefill forward")
    compile_report(server)
    log(f"peak_bytes_in_use {peak_bytes()}")
    return done and custom and kern and logits


def placed_on_own_submesh(server) -> bool:
    """Each tenant's params and pooled cache live on exactly the devices of
    its own sub-mesh."""
    ok = True
    for t, grp in server.engines.items():
        want = set(server.subs[t].mesh.devices.flat)
        eng = grp.replicas[0]
        sets = {frozenset(x.sharding.device_set)
                for x in jax.tree.leaves((eng.params, eng.cache))}
        mine = sets == {frozenset(want)}
        ok &= mine
        log(f"  {t}: sub-mesh devices {sorted(d.id for d in want)}, "
            f"arrays on {sorted(sorted(d.id for d in s) for s in sets)} "
            f"-> {'ok' if mine else 'FAIL'}")
    return ok


def serve_four_chips(seed: int) -> bool:
    mesh = make_host_mesh((1, 4))
    names = ("a", "b")
    server = ComposedServer(
        mesh, [TenantSpec(n, ARCH, reduced=False, serve=serve_config(4),
                          seed=seed + i) for i, n in enumerate(names)],
        policy=None)
    log(model_summary(server.cfgs["a"]))
    log(f"composition {server.sizes()}, TP degree = grant width")
    rng = np.random.default_rng(seed)
    vocab = server.cfgs["a"].vocab_size
    for n in names:
        for p in prompts(rng, vocab, 4):
            server.submit(n, p, max_new_tokens=MAX_NEW)
    for _ in range(MAX_NEW // 2):
        server.step()
    log("placement before the move:")
    ok = placed_on_own_submesh(server)

    a = server.engines["a"].replicas[0]
    probe = prompts(np.random.default_rng(seed + 7), vocab, 1)[0]
    tp2, ref2 = first_step_logits(a.model, a.params, a.mesh, probe,
                                  a.cfg.max_len)
    ok &= logits_agree(tp2, ref2, "tenant a, TP 2: first step vs forward")

    ev = server.recompose({"a": 1, "b": 3}, reason="four-chip-smoke")
    log(f"recomposition {ev.sizes_before} -> {ev.sizes_after}, moved "
        f"{list(ev.moved)}, warm builds {ev.warm_builds}")
    log("placement after the move:")
    ok &= placed_on_own_submesh(server)
    a = server.engines["a"].replicas[0]
    tp1, ref1 = first_step_logits(a.model, a.params, a.mesh, probe,
                                  a.cfg.max_len)
    ok &= logits_agree(tp1, ref1, "tenant a, one chip: first step vs forward")
    ok &= logits_agree(tp2, tp1, "tenant a: TP 2 vs one chip")

    results = server.drain(max_steps=10 * MAX_NEW)
    emitted = sum(len(t) for s in results.values() for t in s.values())
    done = complete(results, MAX_NEW) and all(
        len(s) == 4 for s in results.values())
    log(f"streams completed across the move: {done}, tokens emitted "
        f"{emitted}")
    ok &= done and decode_kernel_compiled(server)
    compile_report(server)
    log(f"peak_bytes_in_use (max over devices) {peak_bytes()}")
    return ok


def main(argv=None) -> int:
    setup_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="two TP-2 tenants on a 2x2 host and a live 2+2 -> "
                         "1+3 recomposition (needs 4 chips)")
    args = ap.parse_args(argv)

    dev = device_summary()
    log(f"platform {dev['platform']}, device_kind {dev['kind']}, "
        f"devices {dev['count']}")
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found (JAX platform "
              f"{dev['platform']!r}); this check runs only on a TPU",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if dev["count"] < want:
        print(f"chip_smoke: needs {want} chips, found {dev['count']}",
              file=sys.stderr)
        return 2
    log(f"platform profile {device_profile().name}")
    ok = (serve_four_chips(args.seed) if args.four_chips
          else serve_one_chip(args.seed))
    if not ok:
        print("chip_smoke: a check failed (see above)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
