"""LFM2-24B-A2B's mechanisms at a size the CPU holds, against the plain
reference (benchmarks/references/lfm2.py): gated short-convolution layers
whose only state is a tail a slot, rotated head-normed grouped attention at
head size 64 (two KV heads a row of the pool), a leading dense layer in front
of sigmoid-routed experts with a selection bias, in ONE `BlockSequence` whose
attention layers do not line up with the dense / expert boundary; and the
tiny registered sibling served by ServingLoop in chunks and decode steps
through the pool and the slot state."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import lfm2 as ref
from benchmarks.tools import lfm2_controls as controls
from lingvo_tpu import model_registry
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core import ssm as ssm_lib
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.models.lm import layers as lm_layers
from lingvo_tpu.models.lm.params import lfm2
from lingvo_tpu.ops import ragged_block_attend as rba
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import state_layout

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

# the served f32 model against the f32 reference: both sum the same products
# in another order (sorted experts, paged attention by blocks), which reads
# 5e-6 on logits of about 1; the same weights rounded to bf16 read 1e-2 and
# more (test_bf16_weights_fail_the_tolerance)
_LOGIT_TOL = 2e-4
_PAGE = 8
# what the cell's file states of its weights, at the tiny size: the routers
# read the eighth of the stream no layer writes, a selection bias that would
# show if it weighed, head norms whose scales differ by dim, an attention
# branch as large as a convolution's
_WEIGHTS = dict(attention_out_scale=8.0, router_scale=40.0,
                router_reads_share=0.125, router_bias_spread=0.15,
                head_norm_spread=1.0)


def _Task(model="lm.lfm2.Lfm2Tiny", **task_params):
  mp = model_registry.GetParams(model, "Train")
  tp = mp.task
  tp.input = mp.input
  for key, value in task_params.items():
    tp.SetPath(key.replace("__", "."), value)
  task = tp.Instantiate()
  task.FinalizePaths()
  return task


def _Seeded(task, key=7, **weights):
  return ref.SeededWeights(task.InstantiateVariables(jax.random.PRNGKey(key)),
                           **{**_WEIGHTS, **weights})


@pytest.fixture(scope="module")
def tiny():
  """(task, theta): the dense convolution layer, then two periods of an
  attention layer and three convolution layers."""
  task = _Task()
  return task, _Seeded(task)


def _ReferenceLogits(theta, seq, at, width=128):
  ids = np.zeros((1, width), np.int32)
  ids[0, :len(seq)] = seq
  return np.asarray(jax.jit(lambda th, i, a: ref.LogitsAt(th, i, a, 0.0))(
      theta, jnp.asarray(ids), jnp.asarray([at], jnp.int32)))[0]


def _Forward(task, theta, ids):
  return np.asarray(task.ComputePredictions(theta, NestedMap(
      ids=jnp.asarray(ids), paddings=jnp.zeros(ids.shape))).logits)


# -- the stack as data ---------------------------------------------------------


def test_the_published_pattern_is_a_list_of_kinds():
  import json
  with open(ref._CONFIG) as f:
    cfg = json.load(f)
  kinds = lfm2.LayerKinds(40)
  # layer_types: attention at 2, 6, ..., 38, convolution elsewhere
  assert [{"short_conv": "conv", "gqa_rope": "full_attention"}[
      k.split("+")[0]] for k in kinds] == cfg["layer_types"]
  # num_dense_layers 2, both of them convolution layers
  assert [k.split("+")[1] for k in kinds] == ["dense"] * 2 + ["experts"] * 38
  blocks = lm_layers.KindBlocks(kinds)
  assert [k for ks, r in blocks for k in ks * r] == kinds
  assert blocks[0] == (["short_conv+dense"], 2)
  assert blocks[1] == (["gqa_rope+experts"] + ["short_conv+experts"] * 3, 9)
  # the first stage's: ONE leading dense layer and published layers 2-9,
  # two whole periods as one scanned stretch; what the cell runs
  assert lfm2.StageKinds() == [kinds[0]] + kinds[2:10]
  assert lfm2.StageKinds() == cfg["task_params"]["layer_kinds"]
  assert lm_layers.KindBlocks(lfm2.StageKinds()) == [
      (["short_conv+dense"], 1),
      (["gqa_rope+experts"] + ["short_conv+experts"] * 3, 2)]


def _Count(model, **task_params):
  return sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(
      _Task(model, **task_params).VariableSpecs()))


def test_the_published_model_counts_its_parameters_from_shapes():
  conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
  atten = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64     # q, o; k, v; head norms
  norms = 2 * 2048
  dense = 3 * 2048 * 11776
  experts = 64 * 3 * 2048 * 1536 + 2048 * 64 + 64       # router and its bias
  table = 65536 * 2048
  assert (conv, atten, 64 * 3 * 2048 * 1536, dense, table) == (
      16783360, 10485888, 603979776, 72351744, 134217728)   # ISSUE 63's
  want = (2 * (conv + dense + norms) + 28 * (conv + experts + norms)
          + 10 * (atten + experts + norms) + table + 2048)
  assert _Count("lm.lfm2.Lfm2_24B_A2B") == want
  assert 23.5e9 < want < 24.5e9                             # "24B-A2B"
  # the stage the cell holds: 5,178M parameters, 10.36 GB in bf16
  stage = ((conv + dense + norms) + 6 * (conv + experts + norms)
           + 2 * (atten + experts + norms) + table + 2048)
  assert _Count("lm.lfm2.Lfm2_24B_A2B", num_layers=9,
                layer_kinds=lfm2.StageKinds()) == stage == 5177950976


def test_a_stage_is_a_lead_block_and_one_scanned_block(tiny):
  task, theta = tiny
  assert task.stack.PageWindows() == [0, 0]
  assert task.stack.LayerKinds() == {
      "ShortConvLayer+TransformerFeedForwardLayer": 1,
      "PooledAttention+DroplessMoELayer": 2,
      "ShortConvLayer+DroplessMoELayer": 6}
  assert task.stack._repeats == [1, 2]
  eng = engine_lib.ServingLoop(task, theta, page_size=_PAGE, num_pages=48,
                               max_batch=2, max_seq_len=128,
                               prefill_token_budget=16)
  # the bytes of 48 pages at both attention layers; a token's row of the
  # pool holds two KV heads of 64 side by side (4 KV heads: 2 rows of 128)
  assert eng.alloc.num_pages == 48 * 2
  pools = [tuple(x.shape) for x in jax.tree_util.tree_leaves(eng._states)
           if x.ndim == 4 and x.shape[1] == _PAGE]
  assert pools == [(48 * 2 + 1, _PAGE, 2, 128)] * 2          # K and V, once
  assert rba.TileHeads(8, 4, 64) == 2 and rba.TileHeads(32, 8, 64) == 2
  # heads of 128, ungrouped heads, and a head size that is neither: as ever
  assert rba.TileHeads(32, 4, 128) == rba.TileHeads(8, 8, 64) == 1
  assert rba.TileHeads(6, 2, 16) == 1
  # seven tails a slot, f32, and nothing else of a slot's
  census = eng.Stats()["mixers"]
  assert census["decode_state_bytes_per_slot"] == 7 * 2 * 48 * 4


def test_the_layout_finds_the_tails_by_structure(tiny):
  """`state_layout.Detect` tells the seven tails (slot leaves), the pool's K
  and V (page leaves) and the experts' counts (neither) apart with no word
  from the mixer: spill, hand-off and copy-on-write move them as they move
  any slot state."""
  task, theta = tiny
  layout = state_layout.Detect(task, theta, 20, _PAGE, 3)
  states = jax.eval_shape(
      lambda th: task.InitPagedDecodeState(th, 20, _PAGE, 3), theta)
  flat = jax.tree_util.tree_flatten_with_path(states)[0]
  names = [jax.tree_util.keystr(path) for path, _ in flat]
  slots = [n for n, axes in zip(names, layout.leaves) if axes.slot is not None]
  pages = [n for n, axes in zip(names, layout.leaves) if axes.page is not None]
  assert len(slots) == 4 and all("conv" in n for n in slots)
  assert sum(leaf.shape[0] for (_, leaf), axes in zip(flat, layout.leaves)
             if axes.slot is not None) == 7                 # stacked by repeat
  assert len(pages) == 2 and all("kv_pool" in n for n in pages)


# -- (a) the whole model -------------------------------------------------------


@pytest.mark.parametrize("row,at", [(0, 63), (1, 30), (1, 1)])
def test_whole_model_forward_is_the_references(tiny, row, at):
  task, theta = tiny
  ids = np.random.RandomState(4).randint(1, 128, (2, 64)).astype(np.int32)
  logits = _Forward(task, theta, ids)
  np.testing.assert_allclose(logits[row, at],
                             _ReferenceLogits(theta, ids[row], at),
                             atol=_LOGIT_TOL)


def test_the_seeds_own_weights_agree_too():
  """Nothing of `SeededWeights`' changes is needed for f32 to agree."""
  task = _Task()
  theta = ref.SeededWeights(task.InstantiateVariables(jax.random.PRNGKey(3)))
  ids = np.random.RandomState(9).randint(1, 128, (1, 64)).astype(np.int32)
  np.testing.assert_allclose(_Forward(task, theta, ids)[0, 63],
                             _ReferenceLogits(theta, ids[0], 63),
                             atol=_LOGIT_TOL)


def test_bf16_weights_fail_the_tolerance(tiny):
  """The tolerance sees the nearest precision below the one the test
  states: the same model with its weights rounded to bf16."""
  task, theta = tiny
  rounded = jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
      if jnp.issubdtype(x.dtype, jnp.floating) else x, theta)
  ids = np.random.RandomState(4).randint(1, 128, (1, 64)).astype(np.int32)
  got = _Forward(task, rounded, ids)[0, 63]
  assert np.abs(got - _ReferenceLogits(theta, ids[0], 63)).max() > (
      10 * _LOGIT_TOL)


def test_routers_read_dimensions_no_layer_writes(tiny):
  """`router_reads_share`: the first eighth of the stream holds the
  embedding in every layer (those columns of every branch's output
  projection are zero) and the routers read nothing else."""
  _, theta = tiny
  reads = 48 // 8
  axis = {"w_post": -3, "w_out": -1, "w_down": -1}
  seen = set()
  for path, leaf in jax.tree_util.tree_flatten_with_path(theta.stack)[0]:
    keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
    name = "w_down" if keys[-2:] == ["ffn_out", "w"] else keys[-1]
    if name in axis:
      written = np.moveaxis(np.asarray(leaf), axis[name], -1)
      assert (written[..., :reads] == 0).all(), keys
      assert (written[..., reads:] != 0).any(), keys
      seen.add(name)
    if name == "w_router":
      assert (np.asarray(leaf)[..., reads:, :] == 0).all()
      assert (np.asarray(leaf)[..., :reads, :] != 0).all()
  assert seen == set(axis)


def test_a_routers_gain_follows_the_stack_order():
  """`router_layer_gain`: one factor an expert layer in the order the stack
  runs them, a scanned block's repeats one after the other."""
  task = _Task()
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  gains = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]
  got = ref.SeededWeights(theta, router_layer_gain=gains)
  for j in range(4):
    before = np.asarray(theta.stack.block_1.x_layers[j].fflayer.w_router)
    after = np.asarray(got.stack.block_1.x_layers[j].fflayer.w_router)
    for rep in range(2):
      np.testing.assert_allclose(after[rep], before[rep] * gains[4 * rep + j],
                                 rtol=1e-6)


def test_the_bias_chooses_and_does_not_weigh(tiny):
  """The seeded bias moves the choice (another top-3 than the scores' own
  for some token) and the weights are the chosen SCORES over their sum."""
  _, theta = tiny
  ff = theta.stack.block_1.x_layers[1].fflayer
  ref._ARCH.clear()
  ref._ARCH.update(ref._Arch(48))
  u = jnp.asarray(np.random.RandomState(0).randn(64, 48), jnp.float32)
  idx, w = ref.Route(ff, 0, u)
  scores = jax.nn.sigmoid(u @ ff.w_router[0])
  plain = jax.lax.top_k(scores, 3)[1]
  assert (np.sort(np.asarray(idx), -1) != np.sort(np.asarray(plain), -1)).any()
  chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
  np.testing.assert_allclose(
      np.asarray(w), chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
      rtol=1e-6)


# -- the mixer alone -----------------------------------------------------------


def test_the_mixers_forward_is_its_equations():
  p = ssm_lib.ShortConvLayer.Params().Set(name="c", input_dim=16)
  layer = p.Instantiate()
  layer.FinalizePaths()
  theta = layer.InstantiateVariables(jax.random.PRNGKey(1))
  assert {k: v.shape for k, v in theta.items()} == {
      "w_in": (16, 48), "conv_w": (3, 16), "w_out": (16, 16)}
  assert layer.StateBytesPerSlot() == 2 * 16 * 4
  x = np.random.RandomState(2).randn(2, 9, 16).astype(np.float32)
  got, _ = layer.FProp(theta, jnp.asarray(x), NestedMap())
  w_in, taps, w_out = (np.asarray(theta[k], np.float64) for k in (
      "w_in", "conv_w", "w_out"))
  b, c, xx = np.split(x.astype(np.float64) @ w_in, 3, -1)
  u = np.concatenate([np.zeros((2, 2, 16)), b * xx], 1)
  conv = sum(taps[k] * u[:, k:k + 9] for k in range(3))  # no bias, no silu
  np.testing.assert_allclose(np.asarray(got), (c * conv) @ w_out, atol=1e-5)
  with pytest.raises(NotImplementedError, match="packed segments"):
    layer.FProp(theta, jnp.asarray(x), NestedMap(),
                segment_ids=jnp.ones((2, 9), jnp.int32))


# -- head size 64 in the grouped kernel: two KV heads a row of the pool --------


def _PairedCase(h, n, n_kv, page, window=0, seed=0, dtype=jnp.float32):
  """A decode row deep in its pages, a chunk that starts inside a page and a
  fresh prompt; pools as `[pages, P, KV heads, H]` and as the paired rows;
  every page no query may see holds NaN."""
  rng = np.random.RandomState(seed)
  b, t_pages = 3, 8
  lens, pos0 = [1, 37, 20], [5 * page + 3, page + 3, 0]
  row_of, q_end = [], []
  for r, (n_tok, p0) in enumerate(zip(lens, pos0)):
    row_of += [r] * n_tok
    q_end += [p0 + j + 1 for j in range(n_tok)]
  t = 64
  row_of += [0] * (t - len(row_of))
  q_end += [0] * (t - len(q_end))
  np_total = b * t_pages + 1
  tables = rng.permutation(np_total - 1).reshape(b, t_pages).astype(np.int32)
  k = rng.randn(np_total, page, n_kv, h).astype(np.float32)
  v = rng.randn(np_total, page, n_kv, h).astype(np.float32)
  seen = np.zeros(np_total, bool)
  for r, (n_tok, p0) in enumerate(zip(lens, pos0)):
    last = (p0 + n_tok - 1) // page
    first = max(p0 + 1 - window, 0) // page if window else 0
    seen[tables[r, first:last + 1]] = True
  q = jnp.asarray(rng.randn(t, n, h), dtype) / np.sqrt(h)
  pools = [jnp.asarray(x, dtype) for x in (k, v)]
  pair = lambda x: x.reshape(np_total, page, n_kv // 2, 2 * h)
  paired = [pair(x) for x in pools]
  # (the twin walks every token through the pages of the longest row, masked:
  # it is handed the pools as they are; the kernel names live pairs alone)
  poisoned = [pair(jnp.where(jnp.asarray(seen)[:, None, None, None], x,
                             jnp.nan)) for x in pools]
  args = (jnp.asarray(tables), jnp.asarray(row_of, jnp.int32),
          jnp.asarray(q_end, jnp.int32))
  return q, pools, paired, poisoned, args, page


@pytest.mark.parametrize("shape", [
    dict(h=16, n=8, n_kv=4, page=16), dict(h=16, n=8, n_kv=4, page=16,
                                           window=24),
    dict(h=16, n=16, n_kv=2, page=8), dict(h=64, n=8, n_kv=4, page=128)],
                         ids=["h16", "h16_window", "h16_group8", "h64_p128"])
def test_two_heads_a_row_of_the_pool_attend_as_heads_of_their_own(shape):
  """The XLA twin and the grouped kernel (interpret mode) over a pool whose
  rows hold two KV heads side by side read what the twin reads over the pool
  by heads: a decode row (the 8-row rung), a chunk block with clear pages and
  masked ones, a fresh prompt; every page no query may see is NaN."""
  q, pools, paired, poisoned, args, page = _PairedCase(**shape)
  kw = dict(page_size=page, window=shape.get("window", 0))
  want = rba.RaggedAttend(q, *pools, *args, lowering="xla", **kw)
  twin = rba.RaggedAttend(q, *paired, *args, lowering="xla", **kw)
  np.testing.assert_array_equal(np.asarray(twin), np.asarray(want))
  got = rba.RaggedAttend(q, *poisoned, *args, lowering="pallas",
                         interpret=True, **kw)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
  # control: a KV head read from its tile-mate
  mated = [jnp.roll(x, x.shape[-1] // 2, axis=-1) for x in poisoned]
  wrong = rba.RaggedAttend(q, *mated, *args, lowering="pallas",
                           interpret=True, **kw)
  assert np.abs(np.asarray(wrong) - np.asarray(want)).max() > 1e-2


def test_the_kernel_says_what_it_does_not_serve():
  q, pools, paired, _, args, page = _PairedCase(h=16, n=8, n_kv=4, page=16)
  # heads that are no lane multiple, one a row of the pool: as before
  with pytest.raises(NotImplementedError, match="tile the lanes"):
    rba.RaggedAttend(q, *pools, *args, page_size=page, lowering="pallas",
                     interpret=True)
  # pairs whose rows are no lane multiple, on the chip
  with pytest.raises(NotImplementedError, match="two side by side"):
    rba.RaggedAttend(q, *paired, *args, page_size=page, lowering="pallas",
                     interpret=False)


# -- the other models' programs are what they were -----------------------------

# The step program of three tiny models as the PARENT of PR 63 (`7d8e42b`)
# lowers it under JAX 0.9.0 on the CPU (tests/test_head_cols' engines at their
# mixed step, f32), and the grouped kernel at heads of 128 through the
# interpreter, a full and a window layer: lines of `lower().as_text()` and the
# first 16 hex digits of its sha256. The recipe is the test: run it on a
# parent's tree to take a number again. The two `grouped_kernel_*` pins were
# RE-TAKEN FROM PR 64's OWN TREE, which changes that kernel on purpose (a
# decode row's program walks a span of its pages: the plan's list, the pools in
# HBM, the span's pages in one block; 3464 / "6462fd6c086a8839" and 3506 /
# "0e22dfab41628703" at PR 63): they hold later PRs to PR 64's text. The
# `nemotron_h` pin was RE-TAKEN FROM PR 66's OWN TREE, which changes its
# Mamba-2 layers' packed convolution on purpose (`core/ssm._PackedConv`: the
# tails' share by a one-hot product, no gather a tap; `_PackedConvTail`: a
# select between the tail's shifts; 6106 / "1b1bed999b069ddd" at PR 63's
# parent). The three step programs above it are PR 63's parent's, untouched.
_PARENT = {
    "dense": (1290, "1876dbf11e99e5cf"),
    "smallthinker": (3612, "5a744b3068ca2ff2"),
    "trinity": (3921, "63ccd2a42e9e9e9e"),
    "nemotron_h": (5877, "3d62a2b3985911f7"),
    "grouped_kernel_full": (6021, "98c73ed9d51e02cb"),
    "grouped_kernel_window": (6095, "8e0508c01e516255"),
}


def _GroupedKernelText(window):
  t, b, tp, n, n_kv, h, page = 24, 3, 4, 8, 2, 128, 16
  sds = jax.ShapeDtypeStruct
  pool = sds((b * tp + 1, page, n_kv, h), jnp.float32)
  tok = sds((t,), jnp.int32)
  return jax.jit(lambda q, k, v, tb, r, e: rba.RaggedAttend(
      q, k, v, tb, r, e, page_size=page, window=window, lowering="pallas",
      interpret=True)).lower(sds((t, n, h), jnp.float32), pool, pool,
                             sds((b, tp), jnp.int32), tok, tok).as_text()


@pytest.mark.parametrize("what", list(_PARENT))
def test_heads_of_128_lower_the_parents_program(what):
  """The paired rows, the two layer kinds, the new mixer and the two
  counters leave the step programs of the dense, SmallThinker, Trinity and
  Nemotron tiny models, and the grouped kernel at heads of 128, the parent's
  text byte for byte."""
  from tests import test_head_cols
  if what.startswith("grouped_kernel"):
    text = _GroupedKernelText(24 if what.endswith("window") else 0)
  else:
    task, theta = {**test_head_cols._FAMILIES,
                   **test_head_cols._NEWER_FAMILIES}[what](jnp.float32)
    eng, calls, _ = test_head_cols._MixedStepEngine(task, theta)
    text = eng._ragged_fn.lower(*calls.calls[-1][0]).as_text()
  lines, digest = _PARENT[what]
  got = (len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()[:16])
  assert got[0] == lines, got
  if jax.__version__ == "0.9.0":      # the text is that version's
    assert got[1] == digest, got


@pytest.mark.parametrize("model", [
    "lm.smallthinker.SmallThinkerTiny", "lm.trinity.TrinityTiny",
    "lm.nemotron_h.Nemotron3NanoTiny", "lm.granite_hybrid.Granite40HSmallTiny",
    "lm.synthetic_packed_input.DenseLmTiny"])
def test_the_new_kinds_are_neutral_for_the_other_models(model):
  """A stack that names neither kind builds no variable of theirs and enters
  neither scope; its pool keeps a KV head a row."""
  task = _Task(model)
  paths = {jax.tree_util.keystr(path) for path, _ in
           jax.tree_util.tree_flatten_with_path(task.VariableSpecs())[0]}
  assert not any("conv_w" in p and "w_in" in p for p in paths)
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  ids = jnp.asarray(np.random.RandomState(1).randint(1, 128, (1, 16)))
  text = jax.jit(lambda th: task.ComputePredictions(th, NestedMap(
      ids=ids, paddings=jnp.zeros(ids.shape))).logits).lower(
          theta).as_text(debug_info=True)
  assert "short_conv" not in text
  for mixer, _ in getattr(task.stack, "MixerLayers", lambda: [])():
    assert getattr(mixer, "_PoolTileHeads", lambda: 1)() == 1


# -- (b) the tiny sibling through ServingLoop ----------------------------------


class _Probe:
  """Every step through the task's ragged step with its logits kept:
  {(slot, position): logits [V]} of every valid token, and each step's rows
  (where each started, how many tokens it brought)."""

  def __init__(self, engine, task):
    self.engine, self.seen, self.steps = engine, {}, []
    self._fn = jax.jit(lambda th, st, ids, rows, tables: task.RaggedStep(
        th, ids[None], st, tables, rows))
    self._inner = engine._compile_log.Call
    engine._compile_log.Call = self._Call

  def _Call(self, name, fn, *args):
    if name != "ragged":
      return self._inner(name, fn, *args)
    theta, states, tok_ids, rows, tables = args[:5]
    logits, new_states = self._fn(theta, states, tok_ids, rows, tables)
    logits = np.asarray(logits[0].astype(jnp.float32))
    for col in np.flatnonzero(np.asarray(rows.valid)):
      key = int(np.asarray(rows.row_of)[col]), int(np.asarray(rows.pos)[col])
      self.seen[key] = logits[col]
    self.steps.append((np.asarray(rows.row_q_pos).copy(),
                       np.asarray(rows.row_len).copy()))
    counts = jnp.concatenate(engine_lib._MoeCountLeaves(new_states), axis=0)
    return jnp.asarray(logits.argmax(-1), jnp.int32), counts, new_states


def _PoisonDeadPages(eng):
  """Into the pool, what no query may read: NaN in every page no row holds,
  a huge number in every page a row holds with nothing live in it yet."""
  kp, page = eng._kind_pages, eng.page_size
  held, live = set(), set()
  for seq in eng.sched.slots:
    if seq is not None:
      for layer in range(len(kp.windows)):
        first, pages = kp.Held(seq.id, layer)
        held.update(pages)
        if seq.pos > 0:
          live.update(pages[:(seq.pos - 1) // page - first + 1])
  free = jnp.asarray([p for p in range(kp.alloc.num_pages) if p not in held],
                     jnp.int32)
  stale = jnp.asarray(sorted(held - live), jnp.int32)
  pool = eng._states.kv_pool
  for name in ("key", "value"):
    pool[name] = pool[name].at[free].set(jnp.nan).at[stale].set(3e4)


def _PoisonFreeSlots(eng):
  """NaN into the tail of every slot no row holds: a row that takes the slot
  starts from zeros, whatever it held."""
  free = jnp.asarray([i for i, s in enumerate(eng.sched.slots) if s is None],
                     jnp.int32)
  for block in eng._states.blocks:
    for layer in block:
      if "conv" in layer:
        layer.conv = layer.conv.at[:, free].set(jnp.nan)


def _Serve(task, theta, prompts, new_tokens, poison=False, max_batch=None,
           after_step=None):
  eng = engine_lib.ServingLoop(task, theta, page_size=_PAGE, num_pages=48,
                               max_batch=max_batch or len(prompts),
                               max_seq_len=128, prefill_token_budget=16)
  probe = _Probe(eng, task)
  handles = [eng.Submit(p, new_tokens) for p in prompts]
  for step in range(600):
    if all(h.done for h in handles):
      break
    eng.StepOnce()
    if poison:
      _PoisonFreeSlots(eng)
      if poison != "slots":
        _PoisonDeadPages(eng)
    if after_step is not None:
      after_step(eng, step)
  assert all(h.done for h in handles)
  return eng, probe, [h.Result() for h in handles]


# a step packs 18 tokens (a budget of 16 and a column a slot), shared by the
# rows in order: a first row of 17, 16 or 15 leaves the second row 1, 2 or 3
# tokens of the first step, so its next chunk starts 1, 2 or 3 tokens into the
# row: the tail holds one input and a zero, two inputs, or the last two of
# three. 50 then runs over four chunks; 10 is shorter than a chunk.
# (the scheduler hands a prompt no less than two tokens of a step: the row
# that goes on ONE token in is a prompt of one token at its first decode step)
_PROMPTS = {"boundary_1": [1, 50], "boundary_2": [16, 50],
            "boundary_3": [15, 50], "shorter_than_a_chunk": [10],
            "uneven_chunks_in_one_step": [50, 10, 37]}


def _Prompts(case):
  rng = np.random.RandomState(5)
  return [rng.randint(1, 128, n).astype(np.int32) for n in _PROMPTS[case]]


@pytest.fixture(scope="module")
def served(tiny):
  cache = {}

  def _Get(case, poison=False):
    if (case, poison) not in cache:
      cache[case, poison] = _Serve(*tiny, _Prompts(case), 6, poison=poison)
    return cache[case, poison]

  return _Get


def _CheckAgainstReference(theta, probe, prompts, outs, slots=None):
  for k, (prompt, out) in enumerate(zip(prompts, outs)):
    slot = k if slots is None else slots[k]
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])
    for at in (len(prompt) - 1, len(seq) - 2):
      np.testing.assert_allclose(
          probe.seen[slot, at], _ReferenceLogits(theta, seq, at),
          atol=_LOGIT_TOL, err_msg=f"row {slot} position {at}")


@pytest.mark.parametrize("case", sorted(_PROMPTS))
def test_chunked_prefill_and_decode_match_the_reference(tiny, served, case):
  """Prefill in chunks (a budget of 16 shared by the rows of a step) and
  decode steps through the pool and the slot state, with NaN in every page
  and every slot's tail that nothing live holds: the step's logits at the
  end of the prompt and at the last token fed back equal the reference's
  full forward there."""
  _, theta = tiny
  eng, probe, outs = served(case, poison=True)
  _CheckAgainstReference(theta, probe, _Prompts(case), outs)
  assert all(np.isfinite(v).all() for v in probe.seen.values())
  if case.startswith("boundary"):
    into, row = int(case[-1]), 0 if case == "boundary_1" else 1
    # the row brought `into` tokens, then went on from there
    assert any(q[row] == 0 and n[row] == into for q, n in probe.steps)
    assert any(q[row] == into and n[row] >= min(into, 2)
               for q, n in probe.steps)
  assert eng.Stats()["kv_pages"]["in_use"] == 0


def test_a_slot_taken_again_starts_from_zeros(tiny):
  """One slot, two requests one after the other, the free slot's tails
  poisoned in between: the second row reads nothing of the first. (The
  pages are left alone here: a page handed out in the step that first writes
  it would keep the poison in the slots past the row's horizon, where a
  masked key weighs zero times NaN.)"""
  task, theta = tiny
  prompts = _Prompts("uneven_chunks_in_one_step")[:2]
  eng, probe, outs = _Serve(task, theta, prompts, 4, poison="slots",
                            max_batch=1)
  assert eng.Stats()["scheduler"]["finished"] == 2
  _CheckAgainstReference(theta, probe, prompts[1:], outs[1:], slots=[0])


def test_a_row_moved_to_another_slot_mid_prompt_goes_on(tiny):
  """What preemption's spill and restore do to a row, through the layout's
  own gather and scatter (the engine refuses `scheduler_mode='priority'`
  over a stack of kinds, as for every BlockSequence): mid-prompt the row's
  slot state is gathered, the slot poisoned, and the state scattered back;
  the row finishes as the reference says."""
  task, theta = tiny
  prompts = _Prompts("boundary_2")[1:]
  moved = []

  def _SpillAndRestore(eng, step):
    if step != 1:
      return
    layout = eng._Layout()
    kept = layout.Gather(eng._states, "slot", 0)
    assert [tuple(b.shape[-2:]) for b in kept] == [(2, 48)] * 4
    assert not np.allclose(np.asarray(kept[0]), 0)
    eng._states = layout.Scatter(
        eng._states, "slot", 0, [jnp.full_like(b, jnp.nan) for b in kept])
    eng._states = layout.Scatter(eng._states, "slot", 0, kept)
    moved.append(step)

  with pytest.raises(ValueError, match="priority"):
    engine_lib.ServingLoop(task, theta, page_size=_PAGE, num_pages=48,
                           max_batch=1, max_seq_len=128,
                           scheduler_mode="priority")
  _, probe, outs = _Serve(task, theta, prompts, 4, after_step=_SpillAndRestore)
  assert moved == [1]
  _CheckAgainstReference(theta, probe, prompts, outs)


# -- (d) every control the CPU can show ----------------------------------------


_CPU_CONTROLS = [c for c in controls.CONTROLS
                 if c not in ("none", "fp8_weights", "fp8_experts")]


@pytest.mark.parametrize("control", _CPU_CONTROLS)
def test_a_control_fails_the_tolerance(tiny, served, control):
  """The program with ONE thing broken (benchmarks/tools/lfm2_controls.py,
  what the chip's controls break) against the reference that keeps it,
  served in chunks and decode steps: the logits at the prompt's end read far
  over the tolerance that the sound program keeps."""
  task, theta = tiny
  prompts = _Prompts("boundary_2")
  _, sound, outs = served("boundary_2", poison=True)
  want = _ReferenceLogits(theta, prompts[1], len(prompts[1]) - 1)
  at = (1, len(prompts[1]) - 1)
  assert np.abs(sound.seen[at] - want).max() < _LOGIT_TOL
  try:
    controls.Break(control)
    _, probe, _ = _Serve(_Task(), theta, prompts, 2)
  finally:
    controls.Restore()
  assert np.abs(probe.seen[at] - want).max() > 10 * _LOGIT_TOL, control


def test_the_patches_are_gone(tiny):
  task, theta = tiny
  ids = np.random.RandomState(4).randint(1, 128, (1, 64)).astype(np.int32)
  np.testing.assert_allclose(_Forward(_Task(), theta, ids)[0, 63],
                             _ReferenceLogits(theta, ids[0], 63),
                             atol=_LOGIT_TOL)


# -- counters and scopes -------------------------------------------------------


def test_the_engine_counts_tails_and_slot_state(served):
  """`conv_tail_rows`: seven tails a live row a step; `slot_state_bytes`:
  each read and written once; both on the step records the cell's readers
  take, beside the experts' over both blocks."""
  eng, probe, outs = served("uneven_chunks_in_one_step", poison=True)
  stats = eng.Stats()
  live = sum(int((n > 0).sum()) for _, n in probe.steps)
  assert stats["conv_tail_rows"] == 7 * live
  assert stats["slot_state_bytes"] == 7 * live * 2 * (2 * 48 * 4)
  # a row's first K - 1 = 2 tokens of a step read its tail, in seven layers
  assert stats["conv_tail_tokens"] == 7 * sum(
      int(np.minimum(n, 2).sum()) for _, n in probe.steps)
  assert 7 * live < stats["conv_tail_tokens"] < 2 * 7 * live
  tokens = sum(_PROMPTS["uneven_chunks_in_one_step"]) + sum(
      len(o) - 1 for o in outs)
  assert stats["moe_tokens_routed"] == 8 * 3 * tokens
  records = [r for r in eng.trace.Steps() if r.counters]
  for name in ("conv_tail_rows", "slot_state_bytes", "moe_tokens_routed",
               "conv_tail_tokens"):
    assert name in records[-1].counters, name
  assert stats["layer_kinds"] == {
      "ShortConvLayer+TransformerFeedForwardLayer": 1,
      "PooledAttention+DroplessMoELayer": 2,
      "ShortConvLayer+DroplessMoELayer": 6}


def test_a_stack_without_tails_counts_none():
  task = _Task("lm.trinity.TrinityTiny")
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  eng = engine_lib.ServingLoop(task, theta, page_size=_PAGE, num_pages=48,
                               max_batch=2, max_seq_len=128,
                               prefill_token_budget=16)
  h = eng.Submit(np.arange(1, 20, dtype=np.int32), 2)
  while not h.done:
    eng.StepOnce()
  stats = eng.Stats()
  assert stats["conv_tail_rows"] == stats["slot_state_bytes"] == 0
  assert stats["conv_tail_tokens"] == 0
  assert all(not {"conv_tail_rows", "conv_tail_tokens"} & set(r.counters or {})
             for r in eng.trace.Steps())


def test_the_step_program_enters_the_scopes(tiny):
  """`short_conv` inside `atten` and `short_conv_taps` inside it, `qk_norm`
  and `rope` as they are: what `short_conv_ms` and `short_conv_taps_ms`
  read."""
  from lingvo_tpu.observe import schema
  task, theta = tiny
  states = task.InitPagedDecodeState(theta, 20, _PAGE, num_slots=2)
  rows = ragged_lib.BuildRaggedRows(np.array([5, 1]), np.array([0, 9]), 8, 16)
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))
  tables = jnp.zeros((2, 2, 4), jnp.int32)
  text = jax.jit(task.RaggedStep).lower(
      theta, jnp.zeros((1, 8), jnp.int32), states, tables, rows).as_text(
          debug_info=True)
  for scope in ("atten/short_conv", "short_conv/short_conv_taps",
                "atten/qk_norm", "atten/rope", "atten/ragged_attend"):
    assert scope in text, scope
  assert schema.DEVICE_SCOPES["short_conv"][0] == "atten"
  assert schema.DEVICE_SCOPES["short_conv_taps"][0] == "short_conv"
  assert {"conv_tail_rows", "slot_state_bytes", "conv_tail_tokens"} <= set(
      schema.ENGINE_COUNTER_KEYS)
