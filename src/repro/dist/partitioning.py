"""PartitionSpec rules: pytree of shapes -> pytree of NamedShardings.

Rules are shape- and path-aware, and every rule is guarded by divisibility —
a dimension is only sharded when the mesh axis divides it evenly, so the
same functions serve the 8-device CI meshes and the 512-chip production
meshes without special-casing.

  * params     — stacked expert weights (``w_gate``/``w_up``/``w_down``,
                 leading (R,) scan dim then E) shard their expert dim over
                 'model' (expert parallelism); dense 2-D+ weights take
                 tensor parallelism on a trailing dim over 'model' and —
                 with ``fsdp=True`` — ZeRO-style sharding of one remaining
                 dim over the data axes. Scalars/vectors replicate.
  * opt state  — mirrors the param rules leaf-for-leaf (AdamW mu/nu inherit
                 the param layout; the step counter replicates).
  * decode     — KV/recurrent caches shard their batch dim over the data
                 axes; position scalars replicate.
  * batches    — dim 0 over the data axes, with a no-shard guard: a batch
                 whose leading dim is 1 (or not divisible) replicates —
                 B=1 decode must never be scattered across hosts.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def make_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types.

    Every program here places operands with ``NamedSharding`` and lets XLA
    propagate (or runs ``shard_map`` regions); ``jax.make_mesh`` defaults
    to Explicit axes, under which the replicated glue between those
    regions raises ``ShardingTypeError``."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with Auto axis types — how the
    distributed solvers accept a mesh built by plain ``jax.make_mesh``."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _mesh_axes(mesh):
    """(data_spec, data_size, model_size). data_spec merges every non-model
    axis (('pod','data') on multi-pod meshes)."""
    names = tuple(mesh.axis_names)
    data_axes = tuple(a for a in names if a != "model")
    sizes = dict(zip(names, mesh.devices.shape))
    dsize = 1
    for a in data_axes:
        dsize *= sizes[a]
    msize = sizes.get("model", 1)
    if not data_axes:
        data_spec = None
    elif len(data_axes) == 1:
        data_spec = data_axes[0]
    else:
        data_spec = data_axes
    return data_spec, dsize, msize


def _divisible(dim: int, by: int) -> bool:
    return by > 1 and dim >= by and dim % by == 0


def replicated(mesh, tree: Any) -> Any:
    """Fully-replicated shardings shaped like ``tree``."""
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def param_shardings(mesh, params: Any, fsdp: bool = True) -> Any:
    """NamedShardings for a parameter pytree (shapes or concrete arrays).

    ``fsdp=False`` is the serving layout: weights replicated over the data
    axes, tensor/expert-parallel over 'model' only — decode then reads
    weights from local HBM with no per-token parameter all-gathers.
    """
    data_spec, dsize, msize = _mesh_axes(mesh)

    def spec_for(path, leaf) -> P:
        shape = tuple(leaf.shape)
        nd = len(shape)
        if nd <= 1:
            return P()  # norms / biases / scalars: replicate
        spec = [None] * nd
        name = jax.tree_util.keystr(path)
        if any(k in name for k in _EXPERT_KEYS) and nd >= 3:
            # stacked experts (..., E, d_in, d_out): EP over 'model' on E
            e_ax = nd - 3
            if _divisible(shape[e_ax], msize):
                spec[e_ax] = "model"
        else:
            # tensor parallelism: trailing dim first (output features)
            for i in (nd - 1, nd - 2):
                if i >= 0 and _divisible(shape[i], msize):
                    spec[i] = "model"
                    break
        if fsdp and data_spec is not None:
            for i in range(nd):
                if spec[i] is None and _divisible(shape[i], dsize):
                    spec[i] = data_spec
                    break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, spec_for(p, l)), params)


def opt_state_shardings(mesh, opt: Any) -> Any:
    """Optimizer state inherits the param layout (ZeRO-style): mu/nu carry
    the same path suffixes as params, so the param rules apply verbatim;
    the scalar count replicates via the nd<=1 rule."""
    return param_shardings(mesh, opt)


def _batch_dim_sharding(mesh, leaf, batch_axis: int) -> NamedSharding:
    data_spec, dsize, _ = _mesh_axes(mesh)
    shape = tuple(leaf.shape)
    spec = [None] * len(shape)
    if (data_spec is not None and len(shape) > batch_axis
            and shape[batch_axis] > 1 and _divisible(shape[batch_axis],
                                                     dsize)):
        spec[batch_axis] = data_spec
    return NamedSharding(mesh, P(*spec))


def decode_state_shardings(mesh, state: Any) -> Any:
    """Shardings for a ``DecodeState``: scanned block caches carry a leading
    (R,) dim so their batch axis is 1; tail caches and enc-dec memory lead
    with batch. The (B,) per-slot position vector replicates (it is tiny
    and every collective over it would cost more than the copy)."""
    block = jax.tree.map(lambda l: _batch_dim_sharding(mesh, l, 1),
                         state.block_caches)
    tails = jax.tree.map(lambda l: _batch_dim_sharding(mesh, l, 0),
                         state.tail_caches)
    pos = NamedSharding(mesh, P())
    memory = (jax.tree.map(lambda l: _batch_dim_sharding(mesh, l, 0),
                           state.memory)
              if state.memory is not None else None)
    return type(state)(block_caches=block, tail_caches=tails, pos=pos,
                       memory=memory)


def batch_shardings(mesh, batch: Any) -> Any:
    """Data-parallel input sharding with the B=1 no-shard guard."""
    return jax.tree.map(lambda l: _batch_dim_sharding(mesh, l, 0), batch)
