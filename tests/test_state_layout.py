"""What the decode state is made of: serving/state_layout.py's one detection
of page, token-offset and slot axes and its one gather / scatter, over every
shape of stack that serves; the three stacks' MixerLayers() and the one census
(serving/kv_cache.StackCensus) over the registered tiny presets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)
from lingvo_tpu import model_registry
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import state_layout

_PAGES, _PAGE, _SLOTS = 7, 8, 3


def _Task(name, **overrides):
  mp = model_registry.GetParams("lm." + name, "Train")
  tp = mp.task
  tp.input = mp.input
  tp.Set(**overrides)
  task = tp.Instantiate()
  task.FinalizePaths()
  return task


# case -> (preset, task overrides, kv_cache_dtype, {leaf path: (page axis,
# offset axis, slot axis)} for the leaves that have any; every other leaf of
# the state has none)
_CASES = {
    "dense_repeated": (
        "synthetic_packed_input.DenseLmTiny", {}, None, {
            "['body']['self_atten']['key']": (1, 2, None),
            "['body']['self_atten']['value']": (1, 2, None)}),
    "dense_stacked": (
        "synthetic_packed_input.DenseLmTiny", {"use_repeat_layer": False},
        None, {
            f"['x_layers'][{i}]['self_atten']['{kv}']": (0, 1, None)
            for i in range(2) for kv in ("key", "value")}),
    # a sidecar [layers, pages, heads, page] keeps its offsets on another
    # axis than the pool beside it [layers, pages, page, heads, head]
    "int8_sidecars": (
        "synthetic_packed_input.DenseLmTiny", {}, "int8", {
            "['body']['self_atten']['key']": (1, 2, None),
            "['body']['self_atten']['key_scale']": (1, 3, None),
            "['body']['self_atten']['value']": (1, 2, None),
            "['body']['self_atten']['value_scale']": (1, 3, None)}),
    "repeated_hybrid": (
        "synthetic_packed_input.DenseLmSsmHybridTiny", {}, None, {
            "['body']['x_layers'][0]['self_atten']['state']": (None, None, 1),
            "['body']['x_layers'][1]['self_atten']['key']": (1, 2, None),
            "['body']['x_layers'][1]['self_atten']['value']": (1, 2, None)}),
    # layers of two kinds over one pool, and expert layers' counts beside it
    "two_kind_block_kv_pool": (
        "smallthinker.SmallThinkerTiny", {}, None, {
            "['body']['kv_pool']['key']": (1, 2, None),
            "['body']['kv_pool']['value']": (1, 2, None)}),
    "block_sequence_slot_states": (
        "phi4flash.Phi4MiniFlashTiny", {}, None, {
            "['blocks'][0][0]['conv']": (None, None, 1),
            "['blocks'][0][0]['scan']": (None, None, 1),
            "['blocks'][1][0]['conv']": (None, None, 1),
            "['blocks'][1][0]['scan']": (None, None, 1),
            "['kv_pool']['key']": (0, 1, None),
            "['kv_pool']['value']": (0, 1, None)}),
    "block_sequence_gate_and_stacked_slot_states": (
        "brumby.BrumbyTiny", {}, None, {
            "['blocks'][0][0]['norm']": (None, None, 1),
            "['blocks'][0][0]['state']": (None, None, 1),
            "['kv_pool']['gate']": (0, 2, None),
            "['kv_pool']['key']": (0, 1, None),
            "['kv_pool']['value']": (0, 1, None)}),
}


def _Filled(states, seed):
  """`states` with every leaf's bytes random (a pattern of its own a leaf)."""
  leaves, treedef = jax.tree_util.tree_flatten(states)
  rng = np.random.RandomState(seed)
  out = [jnp.asarray(rng.randint(-100, 100, size=x.shape).astype(x.dtype))
         for x in leaves]
  return jax.tree_util.tree_unflatten(treedef, out)


def _Take(leaf, axes, idx):
  """numpy's own reading of `idx` on `axes`: the indexed axes first."""
  x = np.moveaxis(np.asarray(leaf), axes, range(len(axes)))
  return x[tuple(np.asarray(i) for i in idx)]


@pytest.mark.parametrize("case", sorted(_CASES))
def test_layout_names_the_leaves_and_moves_them_bitwise(case):
  preset, overrides, kv_dtype, want = _CASES[case]
  task = _Task(preset, **overrides)
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  layout = state_layout.Detect(task, theta, _PAGES, _PAGE, _SLOTS, kv_dtype)
  zeros = task.InitPagedDecodeState(theta, _PAGES, _PAGE, _SLOTS, kv_dtype)
  paths = [jax.tree_util.keystr(p)
           for p, _ in jax.tree_util.tree_leaves_with_path(zeros)]
  assert len(layout.leaves) == len(paths)
  got = {p: tuple(ax) for p, ax in zip(paths, layout.leaves)
         if any(a is not None for a in ax)}
  assert got == want

  src = _Filled(zeros, seed=1)
  src_leaves = jax.tree_util.tree_leaves(src)
  kinds = {"page": ((jnp.asarray([1, 3], jnp.int32),),
                    (jnp.asarray([5, 4], jnp.int32),)),
           "token": ((jnp.asarray([[1, 3]], jnp.int32),
                      jnp.asarray([[2, 7]], jnp.int32)),
                     (jnp.asarray([[5, 4]], jnp.int32),
                      jnp.asarray([[0, 6]], jnp.int32))),
           "slot": ((jnp.int32(0),), (jnp.int32(2),))}
  for kind, (at, to) in kinds.items():
    names = state_layout._KIND_AXES[kind]
    axes = [tuple(getattr(ax, n) for n in names) for ax in layout.leaves]
    held = [a for a in axes if a[0] is not None]
    unwrap = (lambda i: i[0] if len(i) == 1 else i)
    blocks = layout.gather(src, kind, unwrap(at))
    assert len(blocks) == len(held)
    for block, leaf_axes, leaf in zip(
        blocks, held, [x for x, a in zip(src_leaves, axes)
                       if a[0] is not None]):
      index = [slice(None)] * leaf.ndim
      for axis, i in zip(leaf_axes, at):
        index[axis] = np.asarray(i)
      np.testing.assert_array_equal(np.asarray(block),
                                    np.asarray(leaf)[tuple(index)])
    # into fresh pages / another slot of a state that holds nothing
    moved = jax.tree_util.tree_leaves(
        layout.scatter(zeros, kind, unwrap(to), blocks))
    for new, old, leaf_axes in zip(moved, src_leaves, axes):
      if leaf_axes[0] is None:
        np.testing.assert_array_equal(np.asarray(new), 0)   # not touched
        continue
      np.testing.assert_array_equal(_Take(new, leaf_axes, to),
                                    _Take(old, leaf_axes, at))
      assert np.count_nonzero(np.asarray(new)) == np.count_nonzero(
          _Take(old, leaf_axes, at))                 # and nothing else is
    # copy-on-write: the same, within one state
    copied = jax.tree_util.tree_leaves(
        layout.copy(src, kind, unwrap(at), unwrap(to)))
    for new, old, leaf_axes in zip(copied, src_leaves, axes):
      if leaf_axes[0] is None:
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
        continue
      np.testing.assert_array_equal(_Take(new, leaf_axes, to),
                                    _Take(old, leaf_axes, at))
      np.testing.assert_array_equal(_Take(new, leaf_axes, at),
                                    _Take(old, leaf_axes, at))


# preset -> (the stack's class, [(mixer's class, layers of the stack that are
# it)], the census): what serving/spec_decode.MixerLayers / MixerCensus and
# quant/kv.StackKvCensus gave before the stacks answered for themselves
_PRESETS = {
    ("synthetic_packed_input.DenseLmTiny", False): (
        "StackedTransformerLayers", [("MultiHeadedAttention", 1)] * 2,
        dict(num_attention=2, num_ssm=0, decode_state_bytes_per_slot=0,
             kv_cache_dtype="float32", kv_bytes_per_token=1024,
             attention_layers=2)),
    ("synthetic_packed_input.DenseLmTiny", True): (
        "RepeatedTransformerLayer", [("MultiHeadedAttention", 2)],
        dict(num_attention=2, num_ssm=0, decode_state_bytes_per_slot=0,
             kv_cache_dtype="float32", kv_bytes_per_token=1024,
             attention_layers=2)),
    ("synthetic_packed_input.DenseLmSsmHybridTiny", True): (
        "RepeatedTransformerLayer",
        [("GatedSSMLayer", 1), ("MultiHeadedAttention", 1)],
        dict(num_attention=1, num_ssm=1, decode_state_bytes_per_slot=4096,
             kv_cache_dtype="float32", kv_bytes_per_token=512,
             attention_layers=1)),
    # a body that trains only keeps no decode state
    ("synthetic_packed_input.MoELmTiny", True): (
        "RepeatedTransformerLayer", [],
        dict(num_attention=0, num_ssm=0, decode_state_bytes_per_slot=0,
             kv_cache_dtype=None, kv_bytes_per_token=0, attention_layers=0)),
    ("smallthinker.SmallThinkerTiny", True): (
        "RepeatedTransformerLayer", [("MultiHeadedAttention", 1)] * 4,
        dict(num_attention=4, num_ssm=0, decode_state_bytes_per_slot=0,
             kv_cache_dtype="float32", kv_bytes_per_token=1024,
             attention_layers=4)),
    ("phi4flash.Phi4MiniFlashTiny", None): (
        "BlockSequence",
        [("Mamba1Layer", 2), ("DifferentialAttention", 2), ("Mamba1Layer", 1),
         ("DifferentialAttention", 1), ("DifferentialAttention", 1)],
        dict(num_attention=4, num_ssm=3, decode_state_bytes_per_slot=12672,
             kv_cache_dtype="float32", kv_bytes_per_token=768,
             attention_layers=4)),
    ("nemotron_h.Nemotron3NanoTiny", None): (
        "BlockSequence",
        [("Mamba2Layer", 2), ("Mamba2Layer", 1), ("PooledAttention", 1),
         ("Mamba2Layer", 1)],
        dict(num_attention=1, num_ssm=4, decode_state_bytes_per_slot=22528,
             kv_cache_dtype="float32", kv_bytes_per_token=128,
             attention_layers=1)),
    ("brumby.BrumbyTiny", None): (
        "BlockSequence", [("PowerRetention", 3)],
        dict(num_attention=3, num_ssm=3, decode_state_bytes_per_slot=58752,
             kv_cache_dtype="float32", kv_bytes_per_token=792,
             attention_layers=3)),
}


def test_the_stacks_list_their_mixers_and_the_one_census_prices_them():
  for (preset, repeat), (stack, mixers, census) in _PRESETS.items():
    task = _Task(preset, **(
        {} if repeat is None else {"use_repeat_layer": repeat}))
    assert type(task.stack).__name__ == stack, preset
    assert [(type(m).__name__, reps)
            for m, reps in task.stack.MixerLayers()] == mixers, preset
    assert kv_cache.StackCensus(task) == census, preset
  # the engine's override of the cache dtype prices the pages
  task = _Task("synthetic_packed_input.DenseLmTiny")
  int8 = kv_cache.StackCensus(task, "int8")
  assert (int8["kv_cache_dtype"], int8["kv_bytes_per_token"]) == ("int8", 320)
  # a task with no stack (a non-LM task under GShardDecode) has no census
  assert kv_cache.StackCensus(object()) is None
