"""ragged_decode — single-token decode attention over ragged KV lengths.

The serving decode step is a batched GEMV against a preallocated
(B, T, Hkv, D) cache where T is the slot capacity, but each row only holds
``lengths[b]`` valid entries and some slots are empty altogether.  The padded
XLA path streams all B*T rows every step; this kernel consumes only the live
portion of the stream (the Reconfigurable-Stream-Network datapath idea
applied to the FILCO serving hot path):

* grid (slot, kv_block) with a running flash-softmax state in VMEM scratch
  across the sequential kv_block dimension; each KV block holds every KV
  head, so one DMA per block serves all query heads of the row;
* per-row true lengths ride scalar prefetch, so blocks past ``lengths[b]``
  are skipped — compute via ``pl.when`` and DMA via an index map that clamps
  skipped iterations onto the previous block (same block index -> no fetch);
* an empty-slot row skip: rows with ``live[b] == 0`` do no KV work at all
  and write exact zeros.

``interpret=True`` runs the same kernel on CPU (CI's kernels-smoke job);
tests pin it bit-close against :mod:`repro.kernels.ragged_decode.ref`, whose
live rows are in turn bit-identical to the padded serving path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(lens_ref, live_ref, glob_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, bk, window, logit_cap, scale):
    b = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)
    length = lens_ref[b]
    live = live_ref[b] != 0

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live & (i * bk < length))
    def _block():
        # q rows carry each query head in its KV head's lanes and zeros in
        # the others, so one matmul over all Hkv*D lanes scores every query
        # head against its own KV head only
        q = q_ref[...]                                       # (Hq, Hkv*D)
        k = k_ref[...]                                       # (bk, Hkv*D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (Hq, bk)
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = pos < length
        if window:
            w_ok = pos > (length - 1 - window)
            mask = mask & (w_ok | (glob_ref[0] != 0))
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # explicit mask (not exp underflow): a fully window-masked first
        # block would otherwise yield exp(NEG_INF - NEG_INF) = 1
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        resc = jnp.exp(m_prev - m_new)
        v = v_ref[...]                                       # (bk, Hkv*D)
        # (Hq, Hkv*D): each row's own KV head's lanes are kept by the caller
        acc_ref[...] = acc_ref[...] * resc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_ref[...] = l_prev * resc + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _final():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = jnp.where(live, out, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "logit_cap", "bk", "interpret"))
def ragged_decode_kernel(q, k, v, lengths, live, glob, *, window: int = 0,
                         logit_cap: float = 0.0, bk: int = 128,
                         interpret: bool = False):
    """q: (B, Hq, D); k, v: (B, T, Hkv, D); lengths, live: (B,) int32;
    glob: (1,) int32 sliding-window bypass flag -> (B, Hq, D).

    ``lengths`` must be in [1, T] for live rows (callers clip); dead rows
    (``live == 0``) skip all KV traffic and return zeros.

    Layout: the cache is viewed as (B, T, Hkv*D) (a free reshape), so a KV
    block carries every KV head and its last two dims are (bk, Hkv*D) —
    Mosaic's (8, 128) tiling rule holds for any head count and head_dim.
    q is widened to (B, Hq, Hkv*D) with each head placed in its KV head's
    lanes; the kernel's output keeps the same lanes and the wrapper reads
    each head's own D back out.
    """
    B, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    G = Hq // Hkv
    assert T % bk == 0, (T, bk)
    nb = T // bk
    HD = Hkv * D
    scale = 1.0 / math.sqrt(D)
    group = jnp.arange(Hq) // G                              # q head -> kv head
    own = group[:, None] == jnp.arange(Hkv)[None, :]         # (Hq, Hkv)
    q_wide = jnp.where(own[None, :, :, None], q[:, :, None, :],
                       jnp.zeros((), q.dtype)).reshape(B, Hq, HD)
    k2 = k.reshape(B, T, HD)
    v2 = v.reshape(B, T, HD)

    def kv_index(b, i, lens, live_r, glob_r):
        # clamp skipped iterations onto the last block this row needs: the
        # pipeline sees an unchanged block index and issues no new DMA
        last = jnp.maximum(pl.cdiv(lens[b], bk), 1) - 1
        last = jnp.where(live_r[b] != 0, last, 0)
        return (b, jnp.minimum(i, last), 0)

    row = lambda b, i, *_: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((None, Hq, HD), row),
            pl.BlockSpec((None, bk, HD), kv_index),
            pl.BlockSpec((None, bk, HD), kv_index),
        ],
        out_specs=pl.BlockSpec((None, Hq, HD), row),
        scratch_shapes=[
            pltpu.VMEM((Hq, HD), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, bk=bk, window=window,
                               logit_cap=logit_cap, scale=scale)
    wide = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, HD), q.dtype),
        interpret=interpret,
    )(lengths, live, glob, q_wide, k2, v2)
    return wide.reshape(B, Hq, Hkv, D)[:, jnp.arange(Hq), group]
