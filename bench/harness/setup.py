"""Set-up of one run: the chip, the compile cache, the server and its
programs.

The server is the system under test, built the way a deployment builds it:
``ComposedServer`` with one ``TenantSpec`` per tenant of the configuration,
each tenant's weights drawn on its own sub-mesh from the seed by
``Model.init``.  Set-up then builds every program the cell's traffic can
dispatch into the fabric's ``ExecutableCache``: one prefill program per
prompt bucket the prompt lengths can fall in, and one decode program per KV
bound the live lengths can reach.  Nothing is left to compile inside the
window.
"""
from __future__ import annotations

import concurrent.futures
from typing import List, Tuple

import jax

from repro.workloads.decode import KV_BOUND_BLOCK
# threads building programs during set-up (compiles release the GIL)
WARM_THREADS = 4


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def devices(chips: int, require_tpu: bool = True) -> List:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def compile_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else the checkout's ``.jax_cache/``),
    holding every program, however fast it compiled."""
    from repro.common.jax_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def round_up(n: int, block: int) -> int:
    return -(-n // block) * block


def build_server(config: dict, chips: int, seed: int):
    """``ComposedServer`` over ``chips`` devices with the configuration's
    tenants, weights from ``seed`` (tenant i draws from ``seed + i``)."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ComposedServer, ServeConfig, TenantSpec

    s = config["serve"]
    serve = ServeConfig(max_slots=s["max_slots"], max_len=s["max_len"],
                        prefill_bucket=s["prefill_bucket"],
                        eos_id=s["eos_id"], use_kernels=s["use_kernels"])
    specs = [TenantSpec(t, config["arch"], reduced=config["program_reduced"],
                        serve=serve, seed=seed + i)
             for i, t in enumerate(config["tenants"])]
    server = ComposedServer(make_host_mesh((1, chips)), specs, policy=None)
    for grp in server.engines.values():
        jax.block_until_ready(grp.replicas[0].params)
    return server


def shapes(config: dict, mix: dict) -> Tuple[List[int], List[int]]:
    """The prompt buckets and decode KV bounds this traffic can reach.

    A prompt of L tokens prefills in the bucket round_up(L, prefill_bucket).
    Its k-th decode dispatch attends L + k rows, k = 1 .. out - 1, and the
    program's bound is the largest live row count rounded up to the block."""
    s = config["serve"]
    pb, cap = s["prefill_bucket"], s["max_len"]
    p, o = mix["prompt_len"], mix["output_len"]
    buckets = list(range(round_up(p["min"], pb), round_up(p["max"], pb) + 1,
                         pb))
    lo = round_up(p["min"] + 1, KV_BOUND_BLOCK)
    hi = min(round_up(p["max"] + o["max"] - 1, KV_BOUND_BLOCK), cap)
    bounds = list(range(lo, hi + 1, KV_BOUND_BLOCK))
    return buckets, bounds


def warm(server, buckets, bounds) -> int:
    """Build every tenant's prefill and decode programs for ``buckets`` and
    ``bounds`` on its current sub-mesh; returns the programs built."""
    jobs = []
    for grp in server.engines.values():
        for eng in grp.replicas:
            jobs += [lambda e=eng, nb=nb: e._prefill_exec(e.mesh, nb)
                     for nb in buckets]
            jobs += [lambda e=eng, b=b: e._decode_exec(e.mesh, (b,))
                     for b in bounds]
    before = server.exec_cache.snapshot()["builds"]
    with concurrent.futures.ThreadPoolExecutor(WARM_THREADS) as pool:
        for f in [pool.submit(j) for j in jobs]:
            f.result()
    return server.exec_cache.snapshot()["builds"] - before

