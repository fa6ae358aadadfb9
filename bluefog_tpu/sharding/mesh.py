"""Gossip-of-meshes geometry: each gossip rank is a whole pjit mesh.

The hybrid mesh is ``('bf', <inner axes...>)`` — the outer ``bf`` axis
carries the decentralized data-parallel gossip (``neighbor_allreduce`` /
window deposits between ranks), the inner axes (``fsdp``/``tp``/``pp``)
shard each rank's model *within* its mesh.  This module owns the
geometry both execution paths share:

- **device side** (:class:`GossipMesh`): build the ``jax.sharding.Mesh``
  (ICI snake order via ``parallel.make_hybrid_mesh``) or its
  ``AbstractMesh`` twin for tracing/tests off-TPU;
- **host side** (:func:`shard_shape` / :func:`shard_slices` /
  :class:`ShardView`): pure-numpy slice arithmetic for a leaf's shard
  under a :class:`~bluefog_tpu.sharding.rules.RuleTable` spec — what the
  spec-aware :class:`~bluefog_tpu.runtime.async_windows.TreePacker` and
  the shard-local window gossip use.  The wire model follows: a window
  deposit moves ``shard_bytes``, never ``full_bytes``, and the two
  differ by exactly ``prod(sizes of mentioned axes)``.

Host-side coordinates are dicts ``{axis_name: index}``; a leaf dim whose
spec entry names several axes (``('fsdp', 'tp')``) is split row-major in
the listed order, matching XLA's NamedSharding convention.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np
from jax.sharding import PartitionSpec

from bluefog_tpu.sharding.rules import spec_entry_axes

__all__ = [
    "GossipMesh",
    "ShardView",
    "num_shards",
    "inner_coords",
    "shard_shape",
    "shard_slices",
    "shard_size_ratio",
]


def _spec_entries(spec: PartitionSpec, ndim: int) -> List[Tuple[str, ...]]:
    """Per-dim axis tuples, padded with replicated entries to ``ndim``."""
    entries = [spec_entry_axes(e) for e in tuple(spec)]
    if len(entries) > ndim:
        raise ValueError(
            f"spec {spec} has {len(entries)} entries for a {ndim}-d leaf")
    entries += [()] * (ndim - len(entries))
    return entries


def num_shards(axes: Mapping[str, int]) -> int:
    """Total inner-mesh size: how many shards one rank's mesh holds."""
    n = 1
    for s in axes.values():
        n *= int(s)
    return n


def inner_coords(axes: Mapping[str, int]) -> List[Dict[str, int]]:
    """Every inner-mesh coordinate, row-major in ``axes``'s key order —
    the iteration order shard ids use everywhere (window names, serving
    reassembly)."""
    names = list(axes.keys())
    return [dict(zip(names, idx))
            for idx in itertools.product(*(range(int(axes[n]))
                                           for n in names))]


def shard_shape(shape: Sequence[int], spec: PartitionSpec,
                axes: Mapping[str, int]) -> Tuple[int, ...]:
    """Shape of one shard of a ``shape``-d leaf under ``spec``.

    Every mentioned axis must divide its dim evenly — ragged shards are
    refused loudly (XLA pads them; the host wire must not)."""
    shape = tuple(int(s) for s in shape)
    out = []
    for dim, entry in zip(shape, _spec_entries(spec, len(shape))):
        div = 1
        for ax in entry:
            # an axis the mesh does not have = one shard along it: this
            # is what makes ``axes={}`` the gathered single-chip
            # reference of any spec tree.  Typo'd axis names are caught
            # loudly where specs are authored (RuleTable(axes=)) and by
            # the BF-SHD lint, not here.
            div *= int(axes.get(ax, 1))
        if dim % div:
            raise ValueError(
                f"dim {dim} not divisible by axes {entry} (= {div}) "
                f"in spec {spec} for shape {shape}")
        out.append(dim // div)
    return tuple(out)


def shard_slices(shape: Sequence[int], spec: PartitionSpec,
                 axes: Mapping[str, int], coord: Mapping[str, int]
                 ) -> Tuple[slice, ...]:
    """Index slices selecting coordinate ``coord``'s shard of a leaf."""
    shape = tuple(int(s) for s in shape)
    local = shard_shape(shape, spec, axes)
    out = []
    for dim, loc, entry in zip(shape, local, _spec_entries(spec, len(shape))):
        idx = 0
        for ax in entry:  # row-major over the listed axes
            if ax not in axes:
                continue  # absent axis = one shard (see shard_shape)
            idx = idx * int(axes[ax]) + int(coord[ax])
        start = idx * loc
        out.append(slice(start, start + loc))
    return tuple(out)


def shard_size_ratio(spec: PartitionSpec, axes: Mapping[str, int]) -> int:
    """``full_size / shard_size`` for a leaf under ``spec`` — the wire
    savings factor of shard-local gossip over gather-then-gossip."""
    r = 1
    for entry in (tuple(spec) or ()):
        for ax in spec_entry_axes(entry):
            r *= int(axes.get(ax, 1))
    return r


@dataclass(frozen=True)
class ShardView:
    """One inner-mesh coordinate's view of a spec'd tree — the plan the
    spec-aware :class:`~bluefog_tpu.runtime.async_windows.TreePacker`
    packs through.

    Attributes:
      specs: pytree of :class:`PartitionSpec` matching the template
        (from :meth:`RuleTable.resolve_tree` — the single source of
        truth).
      axes: ``{inner_axis: size}``.
      coord: ``{inner_axis: index}`` — which shard this view is.
    """

    specs: Any
    axes: Mapping[str, int] = field(default_factory=dict)
    coord: Mapping[str, int] = field(default_factory=dict)

    def __post_init__(self):
        missing = set(self.axes) - set(self.coord)
        if missing:
            raise ValueError(f"coord missing axes {sorted(missing)}")
        for ax, i in self.coord.items():
            if not 0 <= int(i) < int(self.axes[ax]):
                raise ValueError(
                    f"coord {ax}={i} out of range [0, {self.axes[ax]})")

    def spec_leaves(self, template) -> List[PartitionSpec]:
        """Flattened specs aligned with ``template``'s leaf order."""
        import jax

        spec_flat = jax.tree_util.tree_leaves(
            self.specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
        n = len(jax.tree_util.tree_leaves(template))
        if len(spec_flat) != n:
            raise ValueError(
                f"spec tree has {len(spec_flat)} leaves, template {n}")
        return spec_flat

    def leaf_shape(self, shape: Sequence[int], spec: PartitionSpec
                   ) -> Tuple[int, ...]:
        return shard_shape(shape, spec, self.axes)

    def leaf_slices(self, shape: Sequence[int], spec: PartitionSpec
                    ) -> Tuple[slice, ...]:
        return shard_slices(shape, spec, self.axes, self.coord)


class GossipMesh:
    """The hybrid ``(bf, inner...)`` mesh, as one object both sides use.

    ``bf`` ranks gossip over the outer axis; each rank's model is
    sharded over the inner axes.  :meth:`jax_mesh` builds the real
    device mesh (gossip axis outermost so inner collectives land on
    nearest-neighbor ICI); :meth:`abstract` the tracing twin;
    :meth:`views` the per-coordinate host plans for a resolved spec
    tree."""

    def __init__(self, bf: int, inner: Mapping[str, int], *,
                 bf_axis: str = "bf"):
        if bf < 1:
            raise ValueError(f"bf size must be >= 1, got {bf}")
        if bf_axis in inner:
            raise ValueError(f"inner axes shadow the gossip axis {bf_axis!r}")
        self.bf = int(bf)
        self.bf_axis = bf_axis
        self.inner: Dict[str, int] = {k: int(v) for k, v in inner.items()}

    @property
    def inner_size(self) -> int:
        return num_shards(self.inner)

    @property
    def axis_sizes(self) -> Dict[str, int]:
        return {self.bf_axis: self.bf, **self.inner}

    def coords(self) -> List[Dict[str, int]]:
        return inner_coords(self.inner)

    def jax_mesh(self, devices=None, *, use_ici_order: bool = True):
        from bluefog_tpu.parallel.tensor import make_hybrid_mesh

        return make_hybrid_mesh(self.axis_sizes, devices=devices,
                                use_ici_order=use_ici_order)

    def abstract(self):
        from jax.sharding import AbstractMesh

        sizes = self.axis_sizes
        return AbstractMesh(tuple(sizes.values()), tuple(sizes.keys()))

    def views(self, specs) -> List[ShardView]:
        return [ShardView(specs=specs, axes=self.inner, coord=c)
                for c in self.coords()]

    def __repr__(self) -> str:
        return (f"GossipMesh({self.bf_axis}={self.bf}, "
                + ", ".join(f"{k}={v}" for k, v in self.inner.items()) + ")")
