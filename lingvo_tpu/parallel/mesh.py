"""Device mesh + sharding lowering: the GSPMD backbone.

TPU-native replacement for the reference's sharding machinery
(`gshard_utils.py:39-135` Split/Replicate/MeshSplit, `TensorShardingSpec:237`,
`base_layer.py:262-280` split_dims_mapping params, device-mesh shapes like
`synthetic_packed_input.py:68`). The reference annotates TF tensors with XLA
sharding ops; here the same annotations are mesh-axis NAMES carried on
`WeightParams.tensor_split_dims_mapping`, lowered to
`jax.sharding.NamedSharding` — identical compiler path (GSPMD), zero custom
partitioning code.

Canonical axis names (SURVEY.md §2.9 mapping):
  'data'    — batch/data parallelism (gradient psum rides ICI)
  'model'   — tensor parallelism (Megatron-style, heads/ffn-hidden)
  'expert'  — MoE expert parallelism (all-to-all dispatch)
  'stage'   — pipeline stages
  'seq'     — sequence/context parallelism (ring attention)
"""

from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from lingvo_tpu.core.nested_map import NestedMap

DATA_AXIS = "data"
MODEL_AXIS = "model"
EXPERT_AXIS = "expert"
STAGE_AXIS = "stage"
SEQ_AXIS = "seq"


def MakeMesh(axis_sizes: dict[str, int] | None = None,
             devices: Sequence[Any] | None = None) -> Mesh:
  """Builds a Mesh from {axis_name: size}; -1 once means 'all remaining'.

  Axis order follows insertion order of axis_sizes; put the fastest-varying
  (ICI-adjacent) axis last — on TPU slices jax orders devices so that
  trailing mesh dims map to nearest neighbors (what 'model'/'seq' want).
  """
  devices = list(devices if devices is not None else jax.devices())
  n = len(devices)
  axis_sizes = dict(axis_sizes or {DATA_AXIS: -1})
  unknown = [k for k, v in axis_sizes.items() if v == -1]
  known = int(np.prod([v for v in axis_sizes.values() if v != -1])) or 1
  if unknown:
    assert len(unknown) == 1, "only one -1 axis allowed"
    assert n % known == 0, (n, axis_sizes)
    axis_sizes[unknown[0]] = n // known
  total = int(np.prod(list(axis_sizes.values())))
  assert total == n, f"mesh {axis_sizes} != {n} devices"
  shape = tuple(axis_sizes.values())
  dev_array = np.asarray(devices).reshape(shape)
  return Mesh(dev_array, tuple(axis_sizes.keys()))


def SpecFromSplitDims(split_dims_mapping: Sequence[Any] | None
                      ) -> PartitionSpec:
  """tensor_split_dims_mapping (axis names / None per dim) -> PartitionSpec."""
  if split_dims_mapping is None:
    return PartitionSpec()
  return PartitionSpec(*[
      tuple(a) if isinstance(a, (list, tuple)) else a
      for a in split_dims_mapping
  ])


def _FilterSpecToMesh(spec: PartitionSpec, mesh: Mesh,
                      shape: Sequence[int] | None = None) -> PartitionSpec:
  """Drops axis names absent from `mesh` and shardings that don't divide the
  dim evenly (GSPMD would pad; we keep weights exact instead)."""
  axes = set(mesh.axis_names)
  out = []
  for i, entry in enumerate(spec):
    names = entry if isinstance(entry, tuple) else (
        (entry,) if entry is not None else ())
    names = tuple(nm for nm in names if nm in axes)
    if shape is not None and names:
      total = int(np.prod([mesh.shape[nm] for nm in names]))
      if shape[i] % total != 0:
        names = ()
    out.append(names if len(names) > 1 else (names[0] if names else None))
  return PartitionSpec(*out)


def ShardingForWeight(mesh: Mesh, wp, path: str = "") -> NamedSharding:
  """WeightParams -> NamedSharding (replicated when unannotated)."""
  spec = SpecFromSplitDims(getattr(wp, "tensor_split_dims_mapping", None))
  spec = _FilterSpecToMesh(spec, mesh, wp.shape)
  return NamedSharding(mesh, spec)


def ThetaShardings(mesh: Mesh, layer, theta: NestedMap | None = None,
                   stack_axis_name: str | None = None) -> NestedMap:
  """Sharding pytree for a layer's theta, from its WeightParams annotations.

  Pass `theta` when the layer stacks weights (RepeatedTransformerLayer /
  PipelinedLayer): a theta leaf with one extra leading dim vs its spec gets
  that dim replicated — or sharded over `stack_axis_name` (e.g. 'stage').
  """
  specs = layer.VariableSpecs()

  def _One(wp, leaf=None):
    sdm = list(wp.tensor_split_dims_mapping or [None] * len(wp.shape))
    shape = list(wp.shape)
    if leaf is not None and np.ndim(leaf) == len(shape) + 1:
      sdm = [stack_axis_name] + sdm
      shape = [np.shape(leaf)[0]] + shape
    spec = _FilterSpecToMesh(SpecFromSplitDims(sdm), mesh, shape)
    return NamedSharding(mesh, spec)

  # WeightParams is an unregistered dataclass => a pytree leaf already.
  if theta is None:
    return jax.tree_util.tree_map(_One, specs)
  return jax.tree_util.tree_map(_One, specs, theta)


def TrainStateShardings(mesh: Mesh, task, state: NestedMap,
                        fsdp_axis: str | None = None) -> NestedMap:
  """Shardings for a full train state (theta + opt slots + step).

  Optimizer slot tensors inherit the sharding of their weight where shapes
  match (Adam m/v), and the reduced-dim sharding for factored Adafactor
  slots (vr/vc drop the last/second-to-last dim respectively) — the
  TPU-native equivalent of the reference's sharded optimizer slots
  (`optimizer.py:905-1275`).

  fsdp_axis: if set (usually 'data'), ZeRO-style-shard every state tensor
  additionally over that axis, on the first dim that divides evenly and is
  not already model-sharded. f32 master weights, momentum, and factored
  slots then live data-sharded; GSPMD all-gathers the bf16 compute copy per
  scan step (FSDP) and reduce-scatters gradients — what lets 175B-scale
  states fit per-device HBM when tensor parallelism alone cannot (the
  reference's XLAShardingAdafactor slot sharding, taken one step further).
  """
  flat_specs = dict(task.VariableSpecs().FlattenItems())
  replicated = NamedSharding(mesh, PartitionSpec())
  fsdp_size = mesh.shape[fsdp_axis] if (
      fsdp_axis and fsdp_axis in mesh.axis_names) else 0

  def _AddFsdp(spec: PartitionSpec, shape) -> PartitionSpec:
    if not fsdp_size or fsdp_size == 1:
      return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))

    def _Names(entry):
      return entry if isinstance(entry, tuple) else (
          (entry,) if entry is not None else ())

    if any(fsdp_axis in _Names(e) for e in entries):
      return spec  # already sharded over it (on any dim)
    for i, (entry, dim) in enumerate(zip(entries, shape)):
      names = _Names(entry)
      taken = int(np.prod([mesh.shape[nm] for nm in names])) if names else 1
      if dim % (taken * fsdp_size) == 0:
        new = tuple(names) + (fsdp_axis,)
        entries[i] = new if len(new) > 1 else new[0]
        return PartitionSpec(*entries)
    return spec

  def _ForPath(path: str, leaf):
    # state paths look like: theta.a.b.w / opt_states[0].slots.a.b.w.vr /
    # ema_theta.a.b.w
    parts = path.split(".")
    if parts[0] == "theta" or parts[0] == "ema_theta":
      var_path = ".".join(parts[1:])
      slot = None
    elif parts[0].startswith("opt_states"):
      # strip leading opt_states[i] (+ optional 'slots'/'m'/'inner' wrappers)
      rest = parts[1:]
      while rest and rest[0] in ("slots", "inner", "accum", "m", "v", "ms",
                                 "mom", "acc"):
        rest = rest[1:]
      if not rest:
        return replicated
      slot = None
      if rest[-1] in ("vr", "vc", "v", "m"):
        slot = rest[-1]
        rest = rest[:-1]
      var_path = ".".join(rest)
    else:
      return replicated
    wp = flat_specs.get(var_path)
    if wp is None:
      return replicated
    if wp.tensor_split_dims_mapping is None and not fsdp_size:
      return replicated
    sdm = list(wp.tensor_split_dims_mapping or [None] * len(wp.shape))
    shape = list(wp.shape)
    if slot == "vr":  # reduced over last dim
      sdm, shape = sdm[:-1], shape[:-1]
    elif slot == "vc":  # reduced over second-to-last dim
      sdm, shape = sdm[:-2] + sdm[-1:], shape[:-2] + shape[-1:]
    if len(shape) != len(np.shape(leaf)) or list(np.shape(leaf)) != shape:
      # stacked (repeat-layer) leaves: leading dim added
      if (len(np.shape(leaf)) == len(shape) + 1 and
          list(np.shape(leaf))[1:] == shape):
        sdm = [None] + sdm
        shape = [np.shape(leaf)[0]] + shape
      else:
        return replicated
    spec = _FilterSpecToMesh(SpecFromSplitDims(sdm), mesh, shape)
    spec = _AddFsdp(spec, shape)
    return NamedSharding(mesh, spec)

  items = state.FlattenItems()
  return state.Pack([_ForPath(k, v) for k, v in items])


def BatchShardings(mesh: Mesh, batch: NestedMap,
                   batch_axes: Sequence[str] = (DATA_AXIS,)) -> NestedMap:
  """Shards every batch leaf's leading dim over the data axes."""
  axes = tuple(a for a in batch_axes if a in mesh.axis_names)
  spec = PartitionSpec(axes if len(axes) > 1 else (axes[0] if axes else None))
  sharding = NamedSharding(mesh, spec)
  return batch.Transform(lambda _: sharding)


def PutBatch(mesh: Mesh, batch: NestedMap,
             batch_axes: Sequence[str] = (DATA_AXIS,)) -> NestedMap:
  """Host batch -> device arrays sharded over the data axes."""
  shardings = BatchShardings(mesh, batch, batch_axes)
  import jax.numpy as jnp
  return jax.tree_util.tree_map(
      lambda x, s: jax.device_put(jnp.asarray(x), s), batch, shardings)


def MeshContext(mesh: Mesh):
  """Enters `mesh` as the ambient mesh so PartitionSpec-based
  with_sharding_constraint hints (MoE dispatch, pipeline buffers) reach
  GSPMD. Use around jit calls: `with mesh_lib.MeshContext(mesh): ...`."""
  return jax.set_mesh(mesh)


def CurrentMesh():
  """The ambient mesh entered by MeshContext, or None."""
  m = jax.sharding.get_abstract_mesh()
  return m if tuple(m.axis_names) else None


def ShardMap(fn, mesh=None, *, in_specs, out_specs, check_vma=None):
  """`jax.shard_map` over `mesh`, or over the ambient mesh when None —
  raises when there is none, since shard_map without a mesh cannot mean
  anything."""
  if mesh is None:
    mesh = CurrentMesh()
    assert mesh is not None, "ShardMap outside a MeshContext"
  kw = {} if check_vma is None else {"check_vma": check_vma}
  return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, **kw)


def WithShardingConstraint(x, spec_or_names):
  """MeshSplit equivalent (ref gshard_utils.MeshSplit): annotate inside jit.

  No-op when there is no mesh context (explicitly detected — annotations are
  best-effort across mesh configs, like the reference's MeshSplit with
  device_mesh=None). Axis names absent from the current mesh are dropped;
  anything else invalid (e.g. wrong-rank spec) raises loudly.
  """
  if isinstance(spec_or_names, PartitionSpec):
    spec = spec_or_names
  else:
    spec = SpecFromSplitDims(spec_or_names)
  mesh = CurrentMesh()
  if mesh is None:
    return x
  mesh_axes = tuple(mesh.axis_names)
  filtered = []
  for entry in spec:
    names = entry if isinstance(entry, tuple) else (
        (entry,) if entry is not None else ())
    names = tuple(nm for nm in names if nm in mesh_axes)
    filtered.append(names if len(names) > 1 else (
        names[0] if names else None))
  return jax.lax.with_sharding_constraint(x, PartitionSpec(*filtered))


def CurrentMeshAxisSize(name: str):
  """Size of axis `name` in the ambient mesh, or None if no such axis."""
  m = CurrentMesh()
  if m is None or name not in tuple(m.axis_names):
    return None
  return int(m.shape[name])
