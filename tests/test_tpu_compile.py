"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which is what the chip's compiler would
accept or refuse.  Interpret-mode tests cannot see Mosaic's tiling rules or
its VMEM limit; these can.  A compile takes a second or two.
"""
import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.kernels.mamba_scan import mamba_step_kernel
from repro.kernels.ragged_decode import ops
from repro.models import build_model
from repro.models.ssm import dims as ssm_dims


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _aval(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ragged_avals(B, T, hkv, g, D, q_sh, kv_sh, vec_sh):
    return (_aval((B, 1, hkv * g, D), jnp.bfloat16, q_sh),
            _aval((B, T, hkv, D), jnp.bfloat16, kv_sh),
            _aval((B, T, hkv, D), jnp.bfloat16, kv_sh),
            _aval((B,), jnp.int32, vec_sh),
            _aval((B,), jnp.bool_, vec_sh))


def _ragged(q, k, v, lens, live):
    return ops.ragged_decode_attention(q, k, v, lens, live=live)


def _widths(arch):
    cfg = get_config(arch)
    hkv = cfg.num_kv_heads
    return hkv, cfg.num_heads // hkv, cfg.resolved_head_dim


@pytest.mark.parametrize("arch", ["minitron-4b", "seamless-m4t-medium"])
def test_ragged_decode_kernel_compiles(topo, one_chip, arch, monkeypatch):
    """Self-attention at minitron-4b widths (Hkv 8, G 3, D 128) and
    cross-attention at seamless-m4t-medium widths (Hkv 16, G 1, D 64), bf16
    cache, T 512: Mosaic accepts the kernel's block layout."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    hkv, g, D = _widths(arch)
    avals = _ragged_avals(8, 512, hkv, g, D, one_chip, one_chip, one_chip)
    compiled = jax.jit(_ragged).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ragged_decode_kernel_compiles_tensor_parallel(topo, monkeypatch):
    """Two chips, heads split over the model axis as the serving rules
    shard them: the kernel runs under shard_map (XLA cannot partition a
    Mosaic kernel) and compiles with no collective around it."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices[:2]).reshape(1, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    heads = NamedSharding(mesh, P(None, None, "model", None))
    hkv, g, D = _widths("minitron-4b")
    avals = _ragged_avals(8, 512, hkv, g, D, heads, heads,
                          NamedSharding(mesh, P()))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        lowered = jax.jit(_ragged).lower(*avals)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text


def _minitron_engine(topo):
    """DecodeEngine at minitron-4b's widths, cut to 2 layers, with its
    serving cache (12 slots x 2048, Hkv 8, D 128, bf16), on one described
    chip; abstract params, nothing allocated."""
    from repro.serve import serve_engine_rules
    from repro.workloads import DecodeEngine, ServeConfig
    cfg = dataclasses.replace(get_config("minitron-4b"), num_layers=2)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    eng = DecodeEngine(model, params,
                       ServeConfig(max_slots=12, max_len=2048, eos_id=-1,
                                   use_kernels=True),
                       rules=serve_engine_rules())
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    return eng, mesh


def test_decode_step_updates_stacked_kv_cache_in_place(topo, monkeypatch):
    """DecodeEngine's decode program (see ``_minitron_engine``) with a KV
    bound of 1024: the layer loop writes one row per slot per layer into
    the stacked cache where it lies.  No copy and no dynamic-update-slice
    the size of the stack or of one layer's slice, no temporary that
    large, and the donated stacks alias the outputs."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    eng, mesh = _minitron_engine(topo)
    compiled = eng._build_decode(mesh, bounds=(1024,))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    stack = "bf16[2,12,2048,8,128]"
    layer = "bf16[12,2048,8,128]"
    moves = [(name, shape) for name, shape, op in re.findall(
                 r"%(\S+) = (bf16\[[\d,]+\])\S* ([\w-]+)\(", text)
             if shape in (stack, layer)
             and any(w in name or w in op
                     for w in ("copy", "dynamic-update-slice"))]
    assert not moves, moves
    layer_bytes = 12 * 2048 * 8 * 128 * 2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes, mem
    # the K and V stacks are ENTRY parameters, each aliased to an output
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    stack_params = re.findall(
        re.escape(stack) + r"\S* parameter\((\d+)\)", entry)
    assert len(stack_params) == 2, stack_params
    header = text[:text.index("\n")]
    aliased = set(re.findall(r"\{[\d,]*\}: \((\d+), ", header))
    assert set(stack_params) <= aliased, (stack_params, header[:300])
    assert mem.alias_size_in_bytes >= 2 * 2 * layer_bytes, mem


# one layer's Q/K/V weight as the stacked params give it: canonical
# (d, H, D) and heads-major (H, d, D), with and without the layer axis
_QKV_SLICES = {f"bf16[{lead}{dims}]" for lead in ("", "1,")
               for h in (24, 8)
               for dims in (f"3072,{h},128", f"{h},3072,128")}


def test_qkv_weights_stream_into_their_projections(topo, monkeypatch):
    """The decode program (KV bound 1024) and the prefill program (1536
    tokens) of ``_minitron_engine``: the compiler lowers each Q/K/V
    projection as a convolution windowed over heads, which reads its
    weight heads-major.  The engine holds the weights so, and each layer's
    slice of the stack fuses into its projection.  Held (d, H, D), each
    slice would be staged into VMEM (a fusion laid out in ``S(1)``) and
    relaid out (a copy) every layer of every step."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    eng, mesh = _minitron_engine(topo)
    decode = eng._build_decode(mesh, bounds=(1024,))
    programs = {"decode": decode, "prefill": eng._build_prefill(mesh, 1536)}
    for kind, compiled in programs.items():
        staged = [(name, shape + layout, op) for name, shape, layout, op
                  in re.findall(r"%(\S+) = (bf16\[[\d,]+\])(\{[^}]*\})? "
                                r"([\w-]+)\(", compiled.as_text())
                  if shape in _QKV_SLICES
                  and ("copy" in name or op == "copy"
                       or (op == "fusion" and "S(1)" in layout))]
        assert not staged, (kind, staged)
    mem = decode.memory_analysis()
    assert mem.temp_size_in_bytes < 12 * 2048 * 8 * 128 * 2, mem


def _mamba_avals(d_model, d_in, dt_rank, n, w, sharding, B=8):
    f32, bf16 = jnp.float32, jnp.bfloat16
    return dict(
        x1=_aval((B, 1, d_model), bf16, sharding),
        conv=_aval((B, w - 1, d_in), bf16, sharding),
        h=_aval((B, d_in, n), f32, sharding),
        live=_aval((B,), jnp.int32, sharding),
        in_proj=_aval((d_model, 2 * d_in), f32, sharding),
        conv_w=_aval((w, d_in), f32, sharding),
        conv_b=_aval((d_in,), f32, sharding),
        x_proj=_aval((d_in, dt_rank + 2 * n), f32, sharding),
        dt_proj=_aval((dt_rank, d_in), f32, sharding),
        dt_bias=_aval((d_in,), f32, sharding),
        a_log=_aval((d_in, n), f32, sharding),
        d=_aval((d_in,), f32, sharding),
        out_proj=_aval((d_in, d_model), f32, sharding))


@pytest.mark.parametrize("width", [
    pytest.param("falcon-mamba-7b", marks=pytest.mark.xfail(
        strict=True, raises=jax.errors.JaxRuntimeError,
        reason="RESOURCE_EXHAUSTED: Allocation (size=268435456) would exceed "
               "memory (size=134217728): input window allocation for "
               "operator input 4, f32[4096,16384] in_proj as one VMEM "
               "block; the fused step needs its weights tiled")),
    "d_model-256",
])
def test_mamba_step_kernel_compiles(topo, one_chip, width):
    """The fused Mamba step at falcon-mamba-7b widths (d_model 4096, d_in
    8192, N 16) is refused for VMEM: every weight rides one whole-array
    block.  At d_model 256 it compiles, which pins the f32-accumulating
    dots Mosaic's matmul requires."""
    if width == "falcon-mamba-7b":
        cfg = get_config(width)
        d_in, dt_rank, n, w = ssm_dims(cfg)
        d_model = cfg.d_model
    else:
        d_model, d_in, dt_rank, n, w = 256, 512, 16, 16, 4
    avals = _mamba_avals(d_model, d_in, dt_rank, n, w, one_chip)
    compiled = jax.jit(mamba_step_kernel).lower(**avals).compile()
    assert "tpu_custom_call" in compiled.as_text()
