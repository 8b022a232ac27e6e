"""Logical-axis partitioning: the bridge between model code and meshes.

Model code annotates every parameter with *logical* axis names
("embed", "heads", "mlp", "expert", ...).  A :class:`ShardingRules` maps
logical names to physical mesh axes.  This is how one model definition runs
unchanged on a single CPU device, the 16x16 single-pod mesh and the
2x16x16 multi-pod mesh — only the rules change.

This mirrors FILCO's split between *static parameters* (mesh topology, fixed
before launch) and *runtime parameters* (which sharding/mode each layer uses,
chosen by the DSE and applied per-layer at dispatch time).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# A logical sharding annotation: tuple of logical axis names (or None) per dim.
LogicalSpec = Tuple[Optional[Union[str, Tuple[str, ...]]], ...]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Maps logical axis names -> physical mesh axis name(s) (or None)."""

    rules: Mapping[str, Optional[Union[str, Tuple[str, ...]]]]

    def physical(self, logical: Optional[Union[str, Tuple[str, ...]]]):
        if logical is None:
            return None
        if isinstance(logical, tuple):
            out: list = []
            for l in logical:
                p = self.rules.get(l)
                if p is None:
                    continue
                out.extend(p if isinstance(p, tuple) else (p,))
            if not out:
                return None
            return tuple(out) if len(out) > 1 else out[0]
        p = self.rules.get(logical)
        return p

    def spec(self, logical_spec: LogicalSpec) -> P:
        return P(*(self.physical(ax) for ax in logical_spec))

    def shard(self, mesh: Mesh, logical_spec: LogicalSpec) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_spec))


# ---------------------------------------------------------------------------
# Default rule sets.  Axis vocabulary used across the model zoo:
#   batch        — global batch                       -> (pod, data)
#   act_seq      — residual-stream sequence dim       -> model in training
#                  (Megatron-style sequence parallelism keeps the 80-layer
#                  remat-saved residuals within HBM; DESIGN.md §6)
#   kv_seq       — KV-cache sequence dim (decode)     -> model (split-K decode)
#   embed        — weight d_model dim                 -> data under FSDP
#                  (ZeRO-3: params/grads/opt-state sharded over data; XLA
#                  inserts the per-layer all-gather / reduce-scatter)
#   vocab        — embedding / logits vocab dim       -> model
#   heads        — attention query heads              -> model
#   kv_heads     — attention kv heads                 -> None (replicated; GQA
#                  kv<=8 never divides a 16-wide model axis — K/V are expanded
#                  to q-heads inside the attention block instead)
#   mlp          — dense FFN hidden dim               -> model
#   expert       — MoE expert dim                     -> data (train EP) /
#                                                        model (serve EP)
#   expert_embed — expert weight d_model dim          -> None / data
#   expert_mlp   — expert FFN hidden dim              -> model / None
#   ssm_inner    — mamba inner dim                    -> model
#   lora         — MLA latent dim                     -> None
# ---------------------------------------------------------------------------

def train_rules(fsdp: bool = True, sequence_parallel: bool = True) -> ShardingRules:
    """Training: DP over (pod,data); TP over model; FSDP(ZeRO-3) over data;
    expert-parallelism over data; sequence-parallel residual stream."""
    return ShardingRules(
        rules={
            "batch": ("pod", "data"),
            "act_seq": "model" if sequence_parallel else None,
            "kv_seq": None,
            "embed": "data" if fsdp else None,
            "vocab": "model",
            "heads": "model",
            "kv_heads": None,
            "mlp": "model",
            "expert": "data",
            "expert_embed": None,
            "expert_mlp": "model",
            "ssm_inner": "model",
            "layers": None,
            "conv_w": None,
            "state": None,
            "lora": None,
        }
    )


def serve_rules(fsdp_weights: bool = False) -> ShardingRules:
    """Serving: batch over (pod,data); TP over model; KV cache split-K over
    model on the sequence dim (mandatory for MQA, used uniformly).

    fsdp_weights: additionally shard weight d_model dims over data — required
    when bf16 weights / model-axis exceed HBM (qwen1.5-110b, arctic-480b);
    XLA lowers the contractions to partial-sum + all-reduce over data (2-D
    tensor parallelism), the right trade at decode where activations are tiny.
    """
    return ShardingRules(
        rules={
            "batch": ("pod", "data"),
            "act_seq": None,
            "kv_seq": "model",
            "embed": "data" if fsdp_weights else None,
            "vocab": "model",
            "heads": "model",
            "kv_heads": None,
            "mlp": "model",
            "expert": "model",
            "expert_embed": "data" if fsdp_weights else None,
            "expert_mlp": None,
            "ssm_inner": "model",
            "layers": None,
            "conv_w": None,
            "state": None,
            "lora": None,
        }
    )


def single_device_rules() -> ShardingRules:
    return ShardingRules(rules={})


# ---------------------------------------------------------------------------
# Annotation plumbing: models return pytrees of (array, logical_spec) at init
# time via ``Annotated`` leaves; helpers below strip/extract them.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Annotated:
    """An array leaf carrying its logical sharding annotation."""

    value: Any
    logical: LogicalSpec

    def tree_flatten(self):
        return (self.value,), self.logical

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)


def strip(tree):
    """Annotated pytree -> plain array pytree."""
    return jax.tree.map(
        lambda x: x.value if isinstance(x, Annotated) else x,
        tree,
        is_leaf=lambda x: isinstance(x, Annotated),
    )


def logical_specs(tree):
    """Annotated pytree -> pytree of LogicalSpec (None for unannotated)."""
    return jax.tree.map(
        lambda x: x.logical if isinstance(x, Annotated) else None,
        tree,
        is_leaf=lambda x: isinstance(x, Annotated),
    )


def physical_specs(tree, rules: ShardingRules):
    """Annotated pytree -> pytree of PartitionSpec."""
    return jax.tree.map(
        lambda x: rules.spec(x.logical) if isinstance(x, Annotated) else P(),
        tree,
        is_leaf=lambda x: isinstance(x, Annotated),
    )


def shardings(tree, mesh: Mesh, rules: ShardingRules):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        physical_specs(tree, rules),
        is_leaf=lambda x: isinstance(x, P),
    )


def constrain(x, rules: ShardingRules, logical: LogicalSpec):
    """In-graph sharding constraint by logical axes (no-op without mesh)."""
    try:
        return jax.lax.with_sharding_constraint(x, rules.spec(logical))
    except (ValueError, RuntimeError):
        return x


def validate_divisibility(shape: Sequence[int], spec: P, mesh: Mesh) -> bool:
    """True iff every sharded dim divides evenly on the mesh."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for dim, ax in zip(shape, spec):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        total = int(np.prod([sizes[a] for a in axes]))
        if dim % total:
            return False
    return True


def sanitize_spec(spec: P, mesh: Mesh) -> P:
    """Drop mesh axes a PartitionSpec references that this mesh lacks (the
    'pod' axis on single-pod meshes, and on composed sub-meshes)."""
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in names)
            if not kept:
                return None
            return kept if len(kept) > 1 else kept[0]
        return entry if entry in names else None

    return P(*(keep(e) for e in spec))


def fit_spec(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """sanitize_spec + divisibility: drop sharded axes whose product does not
    evenly divide the array dim (hymba's 25 heads on a 16-wide model axis,
    batch=1 long-context cells, odd vocabularies).  Explicit NamedShardings
    must divide evenly; replication is the graceful degradation, and the
    roofline table shows its cost."""
    spec = sanitize_spec(spec, mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = list(spec) + [None] * (len(shape) - len(spec))

    def fit(dim, entry):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = []
        prod = 1
        for a in axes:
            if dim % (prod * sizes[a]) == 0:
                kept.append(a)
                prod *= sizes[a]
        if not kept:
            return None
        return tuple(kept) if len(kept) > 1 else kept[0]

    return P(*(fit(d, e) for d, e in zip(shape, entries)))


def tp_submesh(mesh: Optional[Mesh], degree: Optional[int],
               axis: str = "model") -> Optional[Mesh]:
    """Restrict a (sub-)mesh's ``axis`` to its first ``degree`` columns.

    The serving-side DSE Stage 1 optimizes each tenant's tensor-parallel
    degree *independently of its CU grant*: a tenant whose analytical
    all-reduce cost outweighs the bandwidth gain runs at ``tp < cus`` on a
    slice of its granted sub-accelerator (the remaining columns idle rather
    than slow the step down).  ``degree`` of None/0, or >= the axis size,
    returns the mesh unchanged; meshes without ``axis`` are returned as-is.
    """
    if mesh is None or not degree or axis not in mesh.axis_names:
        return mesh
    ax = mesh.axis_names.index(axis)
    if degree >= mesh.devices.shape[ax]:
        return mesh
    idx = [slice(None)] * mesh.devices.ndim
    idx[ax] = slice(0, degree)
    return Mesh(mesh.devices[tuple(idx)], mesh.axis_names)


def replica_submesh(mesh: Optional[Mesh], index: int, replicas: int,
                    axis: str = "model") -> Optional[Mesh]:
    """Slice ``mesh`` into ``replicas`` disjoint equal-width tiles along
    ``axis`` and return tile ``index`` (the data-parallel counterpart of
    :func:`tp_submesh`: a ``ReplicaGroup`` runs one independent engine per
    tile).  Columns past ``replicas * (size // replicas)`` are left idle
    when the axis does not divide evenly; ``replicas`` <= 1 returns the
    mesh unchanged, and meshes without ``axis`` are returned as-is."""
    if mesh is None or replicas <= 1 or axis not in mesh.axis_names:
        return mesh
    ax = mesh.axis_names.index(axis)
    width = mesh.devices.shape[ax] // replicas
    if width < 1:
        raise ValueError(
            f"cannot tile {mesh.devices.shape[ax]} '{axis}' columns into "
            f"{replicas} replica slices")
    if not 0 <= index < replicas:
        raise ValueError(f"replica index {index} out of range for "
                         f"{replicas} replicas")
    idx = [slice(None)] * mesh.devices.ndim
    idx[ax] = slice(index * width, (index + 1) * width)
    return Mesh(mesh.devices[tuple(idx)], mesh.axis_names)


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """The sharding-relevant skeleton of a pytree — treedef plus per-leaf
    (shape, dtype, logical spec) — captured once from an annotated tree and
    reusable for any target mesh after the values have been stripped.

    This is what lets a live serving engine recompute NamedShardings for an
    arbitrary composed sub-mesh (grow/shrink/unify) without carrying the
    Annotated wrappers through the hot path: `shardings(mesh, rules)` fits
    every leaf's logical spec to the mesh (axis filtering + divisibility
    fallback to replication) and `avals(mesh, rules)` produces the
    ShapeDtypeStructs an ahead-of-time lowering needs.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    logicals: Tuple[Optional[LogicalSpec], ...]

    @classmethod
    def of(cls, tree) -> "ShardingPlan":
        leaves, treedef = jax.tree_util.tree_flatten(
            tree, is_leaf=lambda x: isinstance(x, Annotated))
        shapes, dtypes, logicals = [], [], []
        for leaf in leaves:
            val = leaf.value if isinstance(leaf, Annotated) else leaf
            shapes.append(tuple(getattr(val, "shape", ())))
            dtypes.append(np.dtype(getattr(val, "dtype", np.float32)))
            logicals.append(leaf.logical if isinstance(leaf, Annotated)
                            else None)
        return cls(treedef, tuple(shapes), tuple(dtypes), tuple(logicals))

    @property
    def annotated(self) -> bool:
        return any(l is not None for l in self.logicals)

    def annotate(self, tree):
        """Re-wrap a stripped tree's leaves with this plan's logical specs
        (the inverse of :func:`strip`)."""
        leaves = self.treedef.flatten_up_to(tree)
        return self.treedef.unflatten(
            [v if l is None else Annotated(v, l)
             for v, l in zip(leaves, self.logicals)])

    def specs(self, mesh: Mesh, rules: ShardingRules) -> list:
        return [fit_spec(rules.spec(l) if l is not None else P(), shape, mesh)
                for shape, l in zip(self.shapes, self.logicals)]

    def shardings(self, mesh: Mesh, rules: ShardingRules):
        """Pytree of NamedShardings on `mesh` (matches the stripped tree)."""
        return self.treedef.unflatten(
            [NamedSharding(mesh, s) for s in self.specs(mesh, rules)])

    def avals(self, mesh: Optional[Mesh], rules: Optional[ShardingRules]):
        """Pytree of ShapeDtypeStructs (with shardings when mesh is given)
        for ahead-of-time lowering."""
        if mesh is None:
            leaves = [jax.ShapeDtypeStruct(s, d)
                      for s, d in zip(self.shapes, self.dtypes)]
        else:
            rules = rules or ShardingRules(rules={})
            leaves = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, p))
                      for s, d, p in zip(self.shapes, self.dtypes,
                                         self.specs(mesh, rules))]
        return self.treedef.unflatten(leaves)
