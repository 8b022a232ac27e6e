"""Model facade: ``build_model(cfg)`` -> a :class:`Model` with a uniform API
for training, prefill and decode across all 10 assigned architectures.

``input_specs(cfg, cell)`` provides ShapeDtypeStruct stand-ins for every model
input of a shape cell (the dry-run contract): token ids for LM/VLM archs,
precomputed frame embeddings for the audio enc-dec (frontend STUB).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.distribution.partitioning import (Annotated, ShardingPlan,
                                             ShardingRules)
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T

PyTree = Any


def _embed_init(rng, cfg: ModelConfig):
    std = cfg.d_model ** -0.5
    return Annotated(
        jax.random.normal(rng, (cfg.padded_vocab, cfg.d_model)) * std,
        ("vocab", "embed"))


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------
    def init(self, rng, mesh=None, rules=None) -> PyTree:
        """Annotated parameter tree from ``rng``.

        One jitted program draws every leaf in float32 and casts it to
        ``cfg.param_dtype`` inside the program, so the device only ever
        holds the cast tree (a 4B-parameter model in bf16 is 8.4 GB; its
        float32 tree would not fit one 16 GB chip).  The values are those
        of a float32 init cast afterwards.

        ``mesh`` (with ``rules``; default: replicated) creates each leaf
        directly with its sharding over that mesh, instead of placing the
        whole tree on the default device first; the values are the same
        either way."""
        if mesh is None:
            return _init(self.cfg, rng)
        plan = ShardingPlan.of(jax.eval_shape(_init, self.cfg, rng))
        out = plan.shardings(mesh, rules or ShardingRules(rules={}))
        return jax.jit(_init_tree, static_argnums=0,
                       out_shardings=out)(self.cfg, rng)

    def heads_major(self, params) -> PyTree:
        """The serving layout of ``params``: every GQA ``wq``/``wk``/``wv``
        heads-major, (..., H, d, D), so the decode and prefill programs
        read each layer's slice of the stacked weight straight into its
        projection (see ``attention.heads_major``).  Every method here
        takes either layout."""
        return A.heads_major(params, self.cfg.d_model)

    # ------------------------------------------------------------------
    def _head(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _mask_pad(self, logits):
        """-inf on vocab-padding columns so sampling never emits them."""
        V = self.cfg.vocab_size
        if logits.shape[-1] == V:
            return logits
        ok = jnp.arange(logits.shape[-1]) < V
        return jnp.where(ok, logits, -1e30)

    def _encode(self, params, frames, attn_impl="blockwise", src_len=None):
        """src_len: optional per-row (B,) valid frame counts when the batch
        is right-padded — the bidirectional stack then masks each row's own
        key padding, so valid rows are independent of the padded shape
        (bucket-invariant encodes; ROADMAP enc-dec follow-up)."""
        cfg = self.cfg
        x = L.apply_norm(cfg.norm, params["frame_norm"],
                         frames.astype(cfg.activation_dtype), cfg.norm_eps)
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return T.encoder_fwd(params["encoder"], cfg, x, pos,
                             attn_impl=attn_impl, kv_len=src_len), pos

    # ------------------------------------------------------------------
    def loss(self, params, batch, *, attn_impl: str = "blockwise",
             moe_dispatch: str = "einsum", residual_spec=None,
             aux_weight: float = 0.01, ssm_impl: str = "chunked",
             attn_block: int = 512):
        """batch: {tokens, labels[, frames]} -> (loss, metrics)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.activation_dtype)
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        enc_out = enc_pos = None
        if cfg.is_encdec:
            enc_out, enc_pos = self._encode(params, batch["frames"], attn_impl)
        x, aux = T.decoder_fwd(params["decoder"], cfg, x, pos,
                               attn_impl=attn_impl, enc_out=enc_out,
                               enc_positions=enc_pos,
                               moe_dispatch=moe_dispatch,
                               residual_spec=residual_spec,
                               ssm_impl=ssm_impl, attn_block=attn_block)
        x = L.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        xent = T.chunked_softmax_xent(x, self._head(params),
                                      jnp.maximum(labels, 0), mask,
                                      logit_softcap=cfg.logit_softcap)
        loss = xent + aux_weight * aux
        return loss, {"xent": xent, "aux": aux}

    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, *, src_len: int = 0):
        """Pooled decode cache for ``batch`` slots of ``max_len`` tokens.

        src_len: cross-attention source capacity (enc-dec archs only) —
        allocates per-layer (batch, src_len, kv_heads, head_dim) cross-K/V
        buffers and a per-row ``src_len`` int32 vector recording each slot's
        *valid* source length (continuous batching mixes source lengths, so
        the mask bound is per row, not per pool).
        """
        cfg = self.cfg
        dtype = cfg.activation_dtype
        cache = T.decoder_cache_init(cfg, batch, max_len, dtype,
                                     cross_src=src_len if cfg.is_encdec else 0)
        if cfg.is_encdec:
            cache["src_len"] = jnp.full((batch,), src_len, jnp.int32)
        return cache

    @staticmethod
    def cache_slot_axes(cache):
        """Batch-slot axis per cache leaf (see transformer.cache_slot_axes)."""
        return T.cache_slot_axes(cache)

    def prefill(self, params, batch, cache, *, attn_impl: str = "blockwise",
                moe_dispatch: str = "einsum", residual_spec=None,
                true_len=None, enc_out=None, src_len=None,
                attn_block: int = 512):
        """Run the prompt through the model, filling the cache.

        true_len: optional (B,) or scalar valid prompt lengths when the
        prompt is right-padded (continuous batching).  Returns logits at the
        last *valid* position per row, and the cache with per-row positions.

        Enc-dec archs additionally accept:

        * enc_out — precomputed encoder hidden states (B, S_src, d); when
          given the encoder stack is skipped (the serving engine encodes
          sources in a separate batched, bucketed program and prefills the
          decoder per slot from the shared output);
        * src_len — int32 scalar or (B,) valid source lengths when the
          encoder output is right-padded: masks cross-attention reads and
          is recorded per row in the returned cache's ``src_len`` vector
          (the bound ``decode_step``'s cross-attention reads honour).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.activation_dtype)
        pos = jnp.broadcast_to(jnp.arange(S), tokens.shape)
        enc_pos = None
        if cfg.is_encdec:
            if enc_out is None:
                enc_out, enc_pos = self._encode(params, batch["frames"],
                                                attn_impl)
            else:
                enc_pos = jnp.broadcast_to(jnp.arange(enc_out.shape[1]),
                                           enc_out.shape[:2])
        else:
            enc_out = None
        x, cache = T.decoder_prefill(params["decoder"], cfg, x, pos, cache,
                                     attn_impl=attn_impl, enc_out=enc_out,
                                     enc_positions=enc_pos, src_len=src_len,
                                     moe_dispatch=moe_dispatch,
                                     residual_spec=residual_spec,
                                     true_len=true_len,
                                     attn_block=attn_block)
        x = L.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        if true_len is None:
            last = x[:, -1]
        else:
            idx = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (B,)) - 1
            last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        logits = self._mask_pad(jnp.einsum(
            "bd,dv->bv", last, self._head(params).astype(x.dtype)))
        out_cache = dict(cache)
        if cfg.is_encdec:
            src = enc_out.shape[1] if src_len is None else src_len
            out_cache["src_len"] = jnp.broadcast_to(
                jnp.asarray(src, jnp.int32), (B,))
        return logits, out_cache

    def encode(self, params, batch, *, attn_impl: str = "blockwise",
               lens=None):
        """Full-sequence hidden states for prefill-only / embedding
        workloads (no cache, no decode loop) -> (B, S, d).

        Enc-dec archs run the bidirectional encoder stack (over ``frames``
        when provided, else the token embeddings stand in for the
        precomputed frame embeddings — the frontend is a STUB); decoder-only
        archs (dense/MoE/SSM alike) run the causal decoder stack and return
        the final-norm hidden states.  This is what the throughput-oriented
        EncoderEngine batches: compute-bound full-sequence matmuls, priced
        as such by the class-aware recomposition policy.

        lens: optional per-row (B,) valid lengths for right-padded batches.
        A bidirectional stack masks each row's key padding with them, making
        a row's encode independent of the padded program shape (the serving
        engines' bucketed programs are then bucket-invariant); causal stacks
        are padding-proof by construction, so lens is ignored there.
        """
        cfg = self.cfg
        if cfg.is_encdec:
            frames = batch.get("frames")
            if frames is None:
                frames = jnp.take(params["embed"], batch["tokens"], axis=0)
            enc_out, _ = self._encode(params, frames, attn_impl,
                                      src_len=lens)
            return enc_out
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.activation_dtype)
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        x, _ = T.decoder_fwd(params["decoder"], cfg, x, pos,
                             attn_impl=attn_impl)
        return L.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)

    def decode_step(self, params, cache, tokens, *, moe_dispatch: str = "einsum",
                    use_kernels: bool = False, kv_bound=None, src_bound=None,
                    live_mask=None):
        """tokens: (B, 1) -> (logits (B, V), cache).

        use_kernels enables the ragged decode path: KV (and enc-dec
        cross-KV) reads are bounded to the static ``kv_bound``/``src_bound``
        prefixes the engine derives from true lengths, and ``live_mask``
        (B,) lets kernels skip empty slots.  Live rows are bit-identical to
        the padded path."""
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.activation_dtype)
        src_len = cache.get("src_len") if cfg.is_encdec else None
        extra = {k: v for k, v in cache.items()
                 if k in ("prologue", "scanned", "pos")}
        x, new_cache = T.decoder_step(params["decoder"], cfg, x, extra,
                                      src_len=src_len, moe_dispatch=moe_dispatch,
                                      use_kernels=use_kernels,
                                      kv_bound=kv_bound, src_bound=src_bound,
                                      live=live_mask)
        x = L.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        logits = self._mask_pad(jnp.einsum(
            "bd,dv->bv", x[:, 0], self._head(params).astype(x.dtype)))
        if cfg.is_encdec:
            new_cache["src_len"] = src_len
        return logits, new_cache


def _init_tree(cfg: ModelConfig, rng) -> PyTree:
    ks = jax.random.split(rng, 6)
    params: Dict[str, PyTree] = {
        "embed": _embed_init(ks[0], cfg),
        "decoder": T.decoder_init(ks[1], cfg, cross=cfg.cross_attention),
        "final_norm": L.norm_init(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            ks[2], cfg.d_model, cfg.padded_vocab, ("embed", "vocab"),
            std=cfg.d_model ** -0.5)
    if cfg.is_encdec:
        params["encoder"] = T.encoder_init(ks[3], cfg)
        if cfg.frontend == "frames":
            params["frame_norm"] = L.norm_init(cfg.norm, cfg.d_model)
    pd = jnp.dtype(cfg.param_dtype)
    if pd != jnp.float32:
        params = jax.tree.map(
            lambda a: Annotated(
                a.value.astype(pd)
                if jnp.issubdtype(a.value.dtype, jnp.floating) else a.value,
                a.logical),
            params, is_leaf=lambda x: isinstance(x, Annotated))
    return params


_init = jax.jit(_init_tree, static_argnums=0)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


# ---------------------------------------------------------------------------
# dry-run input specs
# ---------------------------------------------------------------------------

ENCDEC_DECODE_SRC = 4096   # source frames for enc-dec decode cells (DESIGN.md §4)


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
    B, S = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    act = cfg.activation_dtype
    if cell.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
        if cfg.is_encdec and cfg.frontend == "frames":
            specs["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), act)
        return specs
    if cell.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.is_encdec and cfg.frontend == "frames":
            specs["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), act)
        return specs
    if cell.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
    raise ValueError(cell.kind)
