"""BaseLayer: Params-configured, functionally-pure JAX layers.

Re-designs the reference's layer system (`lingvo/core/base_layer.py:204`) the
TPU-native way. The reference's load-bearing idea — computation is
`FProp(theta, inputs)` with an explicitly passed weight pytree
(`base_layer.py:381`) — maps 1:1 onto JAX; what changes is variable creation:
instead of TF variables held by the layer, a layer only *declares* weight specs
(`CreateVariable`), and `InstantiateVariables(key)` materializes a pure
NestedMap theta with deterministic per-name PRNG folds (parity with the
reference's name-derived seeds, `py_utils.py:1555`).

Sharding: layers carry `device_mesh`-era params re-cast as mesh-axis names —
`weight_split_dims_mapping` / `activation_split_dims_mapping`
(cf. `base_layer.py:262-280`) hold axis-name tuples that lower to
`jax.sharding.PartitionSpec` via `lingvo_tpu.parallel.mesh`.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from lingvo_tpu.core import hyperparams
from lingvo_tpu.core import py_utils
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.core.py_utils import WeightInit, WeightParams


def StackedVariableSpecs(body: "BaseLayer", n: int) -> NestedMap:
  """body's VariableSpecs with a leading stack dim of n (replicated axis).

  Keeps VariableSpecs (param counts, sharding derivation) truthful for
  scan-over-layers / pipeline layers whose theta leaves are stacked.
  """

  def _Stack(wp: WeightParams) -> WeightParams:
    sdm = wp.tensor_split_dims_mapping
    return WeightParams(
        shape=(n,) + tuple(wp.shape),
        init=wp.init,
        dtype=wp.dtype,
        collections=wp.collections,
        tensor_split_dims_mapping=((None,) + tuple(sdm))
        if sdm is not None else None)

  return jax.tree_util.tree_map(_Stack, body.VariableSpecs())


def StackedInstantiateVariables(body: "BaseLayer", key: jax.Array,
                                n: int) -> NestedMap:
  """n independently-seeded copies of body's theta, stacked on axis 0.

  Shared by scan-over-layers (RepeatedTransformerLayer) and pipeline stages
  (PipelinedLayer); the caller must have FinalizePaths()'d the tree.
  """

  def _One(i):
    return body.InstantiateVariables(jax.random.fold_in(key, i))

  return jax.vmap(_One)(jnp.arange(n))


class StackSlice:
  """Layer `index` of a variable stacked over a scan's trips, NOT YET TAKEN:
  the dynamic slice is made where the variable is used (`TakeSlices`, which
  `BaseLayer.CastTheta` calls), not where the scan hands it out. A serving
  scan over layers hands its body such leaves (`SlicedTheta`), so that a use
  inside a `lax.cond` branch (core/ragged.OverLiveRows) slices the stack
  THERE, fused into the product that reads it: a slice made outside is an
  operand of the conditional, which the compiler writes out, the layer's
  weights copied once a step (+40% on a wide step, my chip run, PR 50)."""

  def __init__(self, stack, index):
    self.stack, self.index = stack, index

  def Take(self):
    # `index` is a scan's own counter, never negative: without
    # `allow_negative_indices` the slice wraps it in no `jnp` comparison,
    # each a trace of its own a leaf a use (582 more trace events in the
    # tiny Phi-4-flash sibling's step: tests/test_step_trace.py)
    stack = self.stack
    row = jax.lax.dynamic_slice(
        stack, (self.index,) + (np.int32(0),) * (stack.ndim - 1),
        (1,) + stack.shape[1:], allow_negative_indices=False)
    return jax.lax.squeeze(row, (0,))


def PathKeys(path) -> tuple:
  """A `tree_util` key path as the tuple of its keys' names."""
  return tuple(str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", ""))))
               for k in path)


def SlicedTheta(stacked: NestedMap, index, whole=()) -> NestedMap:
  """`stacked` with every leaf a `StackSlice` at `index`, but those whose
  path (`PathKeys`) is in `whole`: a layer addresses them in the stack
  itself (`StackAddressed`)."""
  return jax.tree_util.tree_map_with_path(
      lambda path, stack: stack if PathKeys(path) in whole
      else StackSlice(stack, index), stacked)


def TakeSlices(theta):
  """`theta` with every `StackSlice` leaf taken; other leaves as they are."""
  return jax.tree_util.tree_map(
      lambda x: x.Take() if isinstance(x, StackSlice) else x, theta)


class BaseLayer:
  """Base class for all layers.

  Lifecycle:
    p = MyLayer.Params().Set(...); layer = p.Instantiate()
    theta = layer.InstantiateVariables(jax.random.PRNGKey(0))
    out = layer.FProp(theta, inputs)
  """

  @classmethod
  def Params(cls) -> hyperparams.InstantiableParams:
    p = hyperparams.InstantiableParams(cls)
    p.Define("name", "", "Layer name; forms variable paths.")
    p.Define("dtype", jnp.float32, "Weight dtype.")
    p.Define(
        "fprop_dtype", None,
        "Activation dtype (e.g. jnp.bfloat16 for TPU). None = use dtype.")
    p.Define("params_init", WeightInit.Xavier(),
             "Default weight initializer for this layer.")
    p.Define(
        "random_seed", None,
        "If set, overrides the name-derived seed fold for deterministic "
        "tests.")
    p.Define(
        "mesh_axis_names", None,
        "Logical mesh axis names this layer's shardings refer to "
        "(informational; specs name axes directly).")
    p.Define(
        "weight_split_dims_mapping", None,
        "Per-dim mesh axis names for this layer's main weight(s); lowered to "
        "PartitionSpec (ref: base_layer.py:262-280).")
    p.Define(
        "activation_split_dims_mapping", None,
        "Per-dim mesh axis names for this layer's output activations; applied "
        "via with_sharding_constraint (ref: gshard_utils.MeshSplit).")
    return p

  def __init__(self, params: hyperparams.InstantiableParams):
    if not params.name and self._NameIsRequired():
      params = params.Copy().Set(name=type(self).__name__.lower())
    self._params = params.Copy()
    self._params.Freeze()
    self._children: dict[str, Any] = {}
    self._variable_specs: dict[str, WeightParams] = {}
    self._path: str | None = None
    self._CreateChildrenHook()

  def _NameIsRequired(self) -> bool:
    return True

  def _CreateChildrenHook(self):
    """Subclasses create children/variables in __init__; hook kept for mixins."""

  # ---- properties ----------------------------------------------------------

  @property
  def params(self) -> hyperparams.InstantiableParams:
    return self._params

  @property
  def p(self) -> hyperparams.InstantiableParams:
    return self._params

  @property
  def children(self) -> dict[str, Any]:
    return dict(self._children)

  @property
  def fprop_dtype(self):
    return self.p.fprop_dtype if self.p.fprop_dtype is not None else self.p.dtype

  @property
  def path(self) -> str:
    """Full slash path from the root layer; unique per layer instance.

    Assigned by the root's InstantiateVariables (or FinalizePaths). Used for
    deterministic per-layer PRNG folds and forward-state update keys, so two
    sibling layers never share a trace-time identity.
    """
    return self._path if self._path is not None else self.p.name

  def FinalizePaths(self, root_path: str | None = None) -> None:
    """Assigns full paths to this layer tree (idempotent from the root)."""
    self._AssignPaths(root_path or self.p.name)

  def _AssignPaths(self, path: str) -> None:
    self._path = path
    for cname, child in self._children.items():
      if isinstance(child, list):
        for i, c in enumerate(child):
          c._AssignPaths(f"{path}/{cname}_{i}")
      else:
        child._AssignPaths(f"{path}/{cname}")

  def __getattr__(self, name: str) -> Any:
    # Children are accessible as attributes (self.fc, self.atten, ...).
    children = self.__dict__.get("_children")
    if children is not None and name in children:
      return children[name]
    raise AttributeError(
        f"{type(self).__name__} has no attribute/child {name!r}")

  # ---- construction API ----------------------------------------------------

  def CopyBaseParams(self, child_p: hyperparams.InstantiableParams
                     ) -> hyperparams.InstantiableParams:
    """Propagates dtype/fprop_dtype/init down to a child (ref :287)."""
    p = self.p
    if "dtype" in child_p and child_p.dtype == jnp.float32 and p.dtype != jnp.float32:
      child_p.dtype = p.dtype
    if "fprop_dtype" in child_p and child_p.fprop_dtype is None:
      child_p.fprop_dtype = p.fprop_dtype
    if ("params_init" in child_p and
        child_p.params_init == WeightInit.Xavier() and
        p.params_init != WeightInit.Xavier()):
      child_p.params_init = p.params_init
    return child_p

  def CreateChild(self, name: str, child_params: hyperparams.InstantiableParams):
    """Instantiates a child layer under `name`."""
    if name in self._children:
      raise ValueError(f"Child {name!r} already exists on {self.p.name}")
    cp = child_params.Copy()
    if "name" in cp and not cp.name:
      cp.name = name
    self.CopyBaseParams(cp)
    self._children[name] = cp.Instantiate()
    return self._children[name]

  def CreateChildren(self, name: str,
                     params_list: Sequence[hyperparams.InstantiableParams]):
    """Instantiates a list of child layers under `name`."""
    if name in self._children:
      raise ValueError(f"Children {name!r} already exist on {self.p.name}")
    out = []
    for i, child_params in enumerate(params_list):
      cp = child_params.Copy()
      if "name" in cp and not cp.name:
        cp.name = f"{name}_{i}"
      self.CopyBaseParams(cp)
      out.append(cp.Instantiate())
    self._children[name] = out
    return out

  def CreateVariable(self, name: str, wp: WeightParams):
    """Declares a weight spec; materialized later by InstantiateVariables."""
    if name in self._variable_specs:
      raise ValueError(f"Variable {name!r} already declared on {self.p.name}")
    self._variable_specs[name] = wp

  # ---- variable materialization --------------------------------------------

  def _OwnVariableSpecs(self) -> dict[str, WeightParams]:
    return dict(self._variable_specs)

  def VariableSpecs(self) -> NestedMap:
    """Full spec tree (self + children), mirroring theta's structure."""
    out = NestedMap()
    for name, wp in self._variable_specs.items():
      out[name] = wp
    for cname, child in self._children.items():
      if isinstance(child, list):
        subs = [c.VariableSpecs() for c in child]
        if any(len(s) for s in subs):
          out[cname] = subs
      else:
        sub = child.VariableSpecs()
        if len(sub):
          out[cname] = sub
    return out

  def InstantiateVariables(self, key: jax.Array) -> NestedMap:
    """Materializes theta: a NestedMap of arrays mirroring the layer tree."""
    if self._path is None:
      self.FinalizePaths()
    theta = NestedMap()
    for name, wp in self._variable_specs.items():
      var_path = f"{self.path}/{name}"
      if self.p.random_seed is not None:
        vkey = jax.random.fold_in(
            jax.random.PRNGKey(self.p.random_seed),
            py_utils.GenerateSeedFromName(var_path))
      else:
        vkey = py_utils.FoldInName(key, var_path)
      theta[name] = py_utils.InitWeight(vkey, wp)
    for cname, child in self._children.items():
      if isinstance(child, list):
        subs = [c.InstantiateVariables(key) for c in child]
        if any(len(s) for s in subs):
          theta[cname] = subs
      else:
        sub = child.InstantiateVariables(key)
        if len(sub):
          theta[cname] = sub
    return theta

  # ---- fprop ---------------------------------------------------------------

  def ChildTheta(self, theta: NestedMap, name: str):
    """theta subtree for child `name`; empty map(s) if it has no variables.

    Children without variables are pruned from theta by InstantiateVariables,
    so FProps must fetch child theta through this accessor.
    """
    if name in theta:
      return theta[name]
    child = self._children[name]
    if isinstance(child, list):
      return [NestedMap() for _ in child]
    return NestedMap()

  def FProp(self, theta: NestedMap, *args, **kwargs):
    raise NotImplementedError(f"{type(self).__name__}.FProp")

  def __call__(self, theta: NestedMap, *args, **kwargs):
    return self.FProp(theta, *args, **kwargs)

  def ToFPropDtype(self, x):
    return py_utils.MaybeBfloat16(x, self.fprop_dtype)

  def CastTheta(self, theta: NestedMap) -> NestedMap:
    """Casts floating theta leaves to fprop dtype (bf16 activations policy);
    a leaf a serving scan has not sliced yet (`StackSlice`) is taken here."""
    theta = TakeSlices(theta)
    dtype = self.fprop_dtype
    if dtype == self.p.dtype:
      return theta
    return jax.tree_util.tree_map(
        lambda x: py_utils.MaybeBfloat16(x, dtype), theta)

  # ---- decode state (Step API) --------------------------------------------

  def InitStates(self, theta: NestedMap, *args, **kwargs) -> NestedMap:
    """Initial streaming/decode state (ref Step API, `step.py`)."""
    return NestedMap()

  def ExtendStep(self, theta: NestedMap, *args, **kwargs):
    raise NotImplementedError(
        f"{type(self).__name__} does not support incremental decoding")
