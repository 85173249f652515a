"""Logical->physical sharding rules (DP / TP / EP / SP / ZeRO-1).

Parameters and activations carry *logical* axis names; a `ShardingRules`
table maps logical names to mesh axes for the current mesh.  Checkpoints
store the logical names only (MANA-2.0 lesson: the upper half must never
reference lower-half/physical resources), so a restart may rebind them to
a different mesh shape (elastic restart).
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Optional, Sequence, Tuple

if TYPE_CHECKING:  # annotations only — jax itself is imported lazily
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# jax is imported INSIDE the functions that build physical shardings:
# the elastic transport-era restore path (`repro.core.restore` /
# `repro.core.split_state.reshard_state`) shares this module's logical
# vocabulary from jax-free processes (socket rank children fork per
# restart attempt; a jax-sized address space would dominate the fork).

# Logical axis vocabulary --------------------------------------------------
# "batch"   -> data-parallel axes (pod, data)
# "vocab"   -> tensor-parallel (model)
# "heads"   -> tensor-parallel (model)
# "kv_heads"-> tensor-parallel iff divisible, else replicated
# "ffn"     -> tensor-parallel (model)
# "expert"  -> expert-parallel (model) in ep mode, else unsharded
# "d_inner" -> tensor-parallel (model)  (mamba inner channels)
# "expert_held" -> unsharded: the experts one chip holds of an expert-
#              parallel layer are already its share (no exchange here)
# "kv_latent"-> unsharded: MLA's compressed kv, shared by every head
# "layers"  -> unsharded for params; ZeRO-1 shards it for optimizer state
# "seq"     -> sequence-parallel (model) when SP is enabled; else unsharded
# None      -> replicated

# logical names sharded across the DATA-parallel direction.  In the
# transport era the rank world IS the (1-D) data axis, so these are the
# names the elastic reshard (`split_state.reshard_state`) splits/merges
# across world sizes; everything else is replicated unless claimed by
# the ZeRO-1 rule below.
WORLD_LOGICAL_AXES: Tuple[str, ...] = ("batch",)


def zero1_pick_dim(entries: Sequence, shape: Sequence[int], dsize: int,
                   *, allow_uneven: bool = False) -> Optional[int]:
    """The ZeRO-1 dim choice, factored out so `zero1_shard` (mesh
    shardings; even tiling required by jit) and the transport-era
    elastic reshard (numpy `array_split`; uneven allowed) cannot
    disagree: the first currently-unsharded dim eligible for the data
    shard, or None to fall back to replication/param spec."""
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and (allow_uneven or dim % dsize == 0):
            return i
    return None


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """All data-parallel mesh axes present in this mesh ('pod' + 'data')."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


class ShardingRules:
    def __init__(self, mesh: Mesh, *, moe_mode: str = "ep",
                 seq_shard: bool = False, kv_time_shard: bool = False):
        self.mesh = mesh
        self.moe_mode = moe_mode
        self.seq_shard = seq_shard
        self.kv_time_shard = kv_time_shard
        batch = batch_axes(mesh)
        model = "model" if "model" in mesh.axis_names else None
        self.table = {
            "batch": batch if batch else None,
            "vocab": model,
            "heads": model,
            "kv_heads": model,   # resolved per-shape below (divisibility)
            "ffn": model,
            "d_inner": model,
            "expert": model if moe_mode == "ep" else None,
            "expert_ffn": model if moe_mode == "tp" else None,
            "expert_held": None,
            "kv_latent": None,
            "seq": model if seq_shard else None,
            "cache_time": model if kv_time_shard else None,
            "layers": None,
            "embed": None,
            "dt": None,
            None: None,
        }

    def model_axis_size(self) -> int:
        return self.mesh.shape.get("model", 1)

    def spec(self, logical: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        """Translate logical axes to a PartitionSpec.

        If `shape` is given, any mapping that does not divide evenly is
        dropped (replicated): jit ARGUMENT shardings must tile evenly,
        and model dims are pre-padded (configs.base padding) so anything
        still uneven is deliberately replicated.  "seq" is exempt: it is
        only ever applied via with_sharding_constraint on intermediates,
        where GSPMD may pad.
        """
        from jax.sharding import PartitionSpec as P
        allow_uneven = {"seq"}
        out = []
        used: set = set()
        for i, name in enumerate(logical):
            phys = self.table.get(name, None)
            if phys is None:
                out.append(None)
                continue
            axes = phys if isinstance(phys, tuple) else (phys,)
            if any(a in used for a in axes):
                # each mesh axis may shard one dim; first mapping wins
                # (e.g. kv_heads takes 'model' before cache_time can)
                out.append(None)
                continue
            if shape is not None:
                total = 1
                for a in axes:
                    total *= self.mesh.shape[a]
                if shape[i] % total != 0 and name not in allow_uneven:
                    out.append(None)
                    continue
            used.update(axes)
            # unwrap 1-tuples: jax no longer treats P(('data',),) as
            # equal to P('data',), and downstream code compares specs
            out.append(axes[0] if len(axes) == 1 else phys)
        return P(*out)

    def named(self, logical: Sequence[Optional[str]],
              shape: Optional[Sequence[int]] = None) -> NamedSharding:
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, self.spec(logical, shape))


def make_rules(mesh: Mesh, **kw) -> ShardingRules:
    return ShardingRules(mesh, **kw)


def logical_to_physical(rules: ShardingRules, logical_tree, shape_tree=None):
    """Map a pytree of logical-axis tuples to PartitionSpecs."""
    import jax
    if shape_tree is None:
        return jax.tree.map(
            lambda lg: rules.spec(lg), logical_tree,
            is_leaf=lambda x: isinstance(x, tuple),
        )
    return jax.tree.map(
        lambda lg, sh: rules.spec(lg, sh), logical_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def zero1_shard(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """ZeRO-1: additionally shard optimizer state over the data axis.

    Picks the first dimension that is currently unsharded and divisible by
    the data-axis size and assigns it to 'data' (and 'pod' if present and
    still divisible).  Falls back to the param spec when nothing divides.
    """
    from jax.sharding import PartitionSpec as P
    if "data" not in mesh.axis_names:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = {a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}
    if "data" in used:
        return spec  # already data-sharded (e.g. FSDP params)
    dsize = mesh.shape["data"]
    i = zero1_pick_dim(entries, shape, dsize)
    if i is not None:
        dim = shape[i]
        if "pod" in mesh.axis_names and dim % (dsize * mesh.shape["pod"]) == 0:
            entries[i] = ("pod", "data")
        else:
            entries[i] = "data"
        return P(*entries)
    return spec
