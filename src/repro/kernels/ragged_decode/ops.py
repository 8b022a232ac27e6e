"""Public wrapper: ragged decode attention with CPU fallback.

The serving engines call this through ``gqa_step``/``cross_step`` when
``ServeConfig.use_kernels`` is on.  Dispatch follows the package idiom:

* ``impl="auto"`` — the Pallas kernel on TPU; on CPU the pure-jnp ref,
  whose live rows are bit-identical to the padded path (the engine-side
  ragged win on CPU comes from the statically KV-bounded decode programs
  that slice the cache before calling here);
* ``impl="ref"`` — the oracle;
* ``impl="interpret"`` — the Pallas kernel in interpreter mode (CPU CI).

Mosaic kernels cannot be partitioned by XLA's SPMD pass.  When the caller
traces under an abstract mesh with more than one device
(``jax.sharding.use_abstract_mesh`` — the decode engine sets it while
lowering for its sub-mesh), the kernel runs under ``jax.shard_map``: each
device attends with its own shard of the heads along ``HEAD_AXIS``, or with
every head when the KV head count does not divide that axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.ragged_decode import kernel as K
from repro.kernels.ragged_decode import ref as R

# the mesh axis the serving sharding rules split attention heads and the
# KV cache's heads over (``serve_engine_rules``: heads/kv_heads -> model)
HEAD_AXIS = "model"


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _block(T: int, bk: int) -> int:
    bk = min(bk, T)
    while T % bk:
        bk //= 2
    return max(bk, 1)


def _sharded(fn, hkv: int):
    """``fn`` under shard_map on the tracing context's abstract mesh, with
    heads split over HEAD_AXIS where they divide it; ``fn`` itself when
    there is no multi-device mesh to partition over."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size <= 1:
        return fn
    ax = (HEAD_AXIS if HEAD_AXIS in mesh.axis_names
          and hkv % mesh.shape[HEAD_AXIS] == 0 else None)
    q_spec, kv_spec = P(None, ax, None), P(None, None, ax, None)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec, P(), P(), P()),
                         out_specs=q_spec, check_vma=False)


def ragged_decode_attention(q, k, v, lengths, *, window: int = 0,
                            logit_cap: float = 0.0, is_global=None,
                            live=None, impl: str = "auto", bk: int = 128):
    """q: (B, 1, Hq, D); k, v: (B, T, Hkv, D); lengths: int32 scalar or (B,)
    true KV lengths; live: optional (B,) bool empty-slot mask ->
    (B, 1, Hq, D).  Live rows are bit-identical to
    ``layers.decode_attention``; dead rows return zeros."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return R.ragged_decode_attention_ref(
            q, k, v, lengths, window=window, logit_cap=logit_cap,
            is_global=is_global, live=live)
    interpret = impl == "interpret" or not _on_tpu()
    B = q.shape[0]
    T = k.shape[1]
    lens = jnp.clip(jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,)),
                    1, T)
    live_i = (jnp.ones((B,), jnp.int32) if live is None
              else jnp.asarray(live).astype(jnp.int32))
    if is_global is None:
        glob = jnp.zeros((1,), jnp.int32)
    else:
        glob = jnp.reshape(jnp.asarray(is_global).astype(jnp.int32), (1,))
    kernel = functools.partial(
        K.ragged_decode_kernel, window=window, logit_cap=logit_cap,
        bk=_block(T, bk), interpret=interpret)
    out = _sharded(kernel, k.shape[2])(q[:, 0], k, v, lens, live_i, glob)
    return out[:, None]
