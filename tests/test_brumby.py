"""Brumby-14B-Base's mechanism at a size the CPU holds, against the plain
reference (benchmarks/references/brumby.py) and against itself: the feature
map of the second power, the three forms of power retention, the whole model,
and the tiny registered sibling served by ServingLoop in chunks and decode
steps through slot state and the open chunk's pages, across folds."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import brumby as ref
from lingvo_tpu import model_registry
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.ops import power_retention as op
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import spec_decode

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

# the served f32 model against the f32 reference
_LOGIT_TOL = 2e-4
# gates that remember hundreds of tokens, heads that differ, a mixer whose
# output weighs: what the cell's weights do, at the tiny model's sizes
_WEIGHTS = dict(gate_offset=5.0, gate_head_spread=1.0, gate_scale=0.5,
                retention_out_scale=2.0)


def _Task(name="BrumbyTiny", depth=None, **task_params):
  mp = model_registry.GetParams("lm.brumby." + name, "Train")
  tp = mp.task
  tp.input = mp.input
  if depth is not None:
    tp.num_layers = depth
  for key, value in task_params.items():
    tp.SetPath(key, value)
  task = tp.Instantiate()
  task.FinalizePaths()
  return task


@pytest.fixture(scope="module")
def tiny():
  """{lowering: task}, and the one theta both serve."""
  tasks = {low: _Task(**{"mixer_tpl.lowering": low})
           for low in ("xla", "pallas")}
  theta = tasks["xla"].InstantiateVariables(jax.random.PRNGKey(7))
  return tasks, ref.SeededWeights(theta, **_WEIGHTS)


# -- the feature map -----------------------------------------------------------


@pytest.mark.parametrize("h", [8, 16, 128])
def test_feature_map_is_the_square_of_the_dot_product(h):
  rng = np.random.RandomState(h)
  a = rng.randn(7, h).astype(np.float32)
  b = rng.randn(7, h).astype(np.float32)
  got = np.sum(np.asarray(op.Phi(jnp.asarray(a), query=True))
               * np.asarray(op.Phi(jnp.asarray(b))), -1)
  want = np.sum(a.astype(np.float64) * b, -1) ** 2
  np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4)


def test_the_stored_feature_dimension_against_the_distinct_products():
  """8,256 distinct products at H = 128; the state stores 8,320 (an offset a
  lane tile, the offset H / 2 twice over), which 16 slots hold beside the
  weights: the issue's bound is about 9,200."""
  assert op.MonomialDim(128) == 8256 == 128 * 129 // 2
  assert op.StoredDim(128) == 8320 == 65 * 128
  assert op.MonomialDim(128) <= op.StoredDim(128) <= 9200
  assert op.Offsets(128) % op.TileOffsets(128) == 0
  # every distinct product is a stored feature, and only the offset H / 2's
  # are stored twice
  h = 16
  pairs = [frozenset((i, (i + o) % h)) for o in range(op.Offsets(h))
           for i in range(h)]
  assert len(pairs) == op.StoredDim(h)
  assert len(set(pairs)) == op.MonomialDim(h)
  assert len(pairs) - len(set(pairs)) == h // 2
  # S and z of a layer at the published sizes, as stored and as counted
  assert op.StateBytes(8, 128) == 4 * 8 * 8320 * 129 == 34_344_960
  state, norm = jax.eval_shape(lambda: op.InitState(16, 8, 128))
  assert state.shape == (16, 8, 128, 8320) and norm.shape == (16, 8, 65, 128)


# -- the three forms -----------------------------------------------------------


def _Inputs(seed, b=2, t=24, n=4, nk=2, h=16, gate=0.01):
  rng = np.random.RandomState(seed)
  q = jnp.asarray(rng.randn(b, t, n, h), jnp.float32) / np.sqrt(h)
  k = jnp.asarray(rng.randn(b, t, nk, h), jnp.float32)
  v = jnp.asarray(rng.randn(b, t, nk, h), jnp.float32)
  log_g = jnp.asarray(-np.abs(rng.randn(b, t, nk)) * gate, jnp.float32)
  return q, k, v, log_g


@pytest.mark.parametrize("gate", [0.001, 0.3, 8.0],
                         ids=["near_one", "between", "near_zero"])
def test_attention_recurrent_and_chunked_forms_agree(gate):
  """Within 1e-5 of the largest output. A token's denominator is a sum of
  squared scores, and a row's first tokens' are one or two of them, which
  f32 carries to 1e-7 absolute whatever their size: the forms are held to
  each other where the normaliser's eps is 1e-2, and at the layer's own 1e-6
  over the tokens whose sum of weights passes 0.1."""
  q, k, v, log_g = _Inputs(3, gate=gate)
  for eps, floor in ((1e-2, 0.0), (1e-6, 0.1)):
    want = op.AttentionForm(q, k, v, log_g, eps)
    s = jnp.einsum("btcgh,bsch->bcgts", q.reshape(2, 24, 2, 2, 16), k)
    cum = jnp.cumsum(log_g, 1)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((24, 24), bool))[
        None, :, :, None], cum[:, :, None] - cum[:, None], -jnp.inf))
    weights = jnp.sum(jnp.square(s) * decay.transpose(0, 3, 1, 2)[:, :, None],
                      -1).transpose(0, 3, 1, 2).reshape(2, 24, 4)
    keep = np.asarray(weights >= floor)[..., None]
    scale = float(jnp.abs(want).max())
    for got in (op.RecurrentForm(q, k, v, log_g, eps),
                op.ChunkedForm(q, k, v, log_g, eps, 8),
                op.ChunkedForm(q, k, v, log_g, eps, 24)):
      diff = np.where(keep, np.abs(np.asarray(got - want)), 0.0)
      assert diff.max() <= 1e-5 * scale, (eps, diff.max(), scale)


# -- the layer and the model ---------------------------------------------------


def test_published_model_counts_its_layers_and_parameters():
  """Shapes only, nothing allocated: 14,769,945,600 at 40 layers."""
  task = _Task("Brumby14BBase")
  shapes = jax.eval_shape(task.InstantiateVariables, jax.random.PRNGKey(0))
  count = lambda tree: sum(int(np.prod(x.shape))
                           for x in jax.tree_util.tree_leaves(tree))
  stack = task.stack
  assert stack.LayerKinds() == {
      "PowerRetention+TransformerFeedForwardLayer": 40}
  d, f = 5120, 17408
  layer = count(shapes.stack.block_0.x_layers[0]) // 40
  assert layer == (2 * d * 40 * 128 + 2 * d * 8 * 128 + d * 8 + 3 * d * f
                   + 2 * 128 + 2 * d) == 330_352_896
  total = count(shapes)
  assert total == 40 * layer + 2 * 151936 * d + d == 14_769_945_600
  census = kv_cache.StackCensus(task)
  # a retention layer holds pages AND a slot state: counted under both
  assert census == {"num_attention": 40, "num_ssm": 40,
                    "decode_state_bytes_per_slot": 40 * 34_344_960,
                    "attention_layers": 40, "kv_cache_dtype": "float32",
                    "kv_bytes_per_token": 40 * 8 * (2 * 128 * 4 + 4)}
  assert stack.PageWindows() == [1] * 40
  mixer = stack._bodies[0][0].mixer
  assert mixer.StoredFeatureDim() == 8320
  assert mixer.KvBytesPerToken() == 8 * (2 * 128 * 4 + 4)   # f32 at the seed


def _ReferenceLogits(theta, seq, at, width=128):
  ids = np.zeros((1, width), np.int32)
  ids[0, :len(seq)] = seq
  return np.asarray(jax.jit(lambda th, i, a: ref.LogitsAt(th, i, a, 0.0))(
      theta, jnp.asarray(ids), jnp.asarray([at], jnp.int32)))[0]


def test_whole_model_forward_is_the_references(tiny):
  tasks, theta = tiny
  ids = np.random.RandomState(4).randint(1, 128, (2, 64)).astype(np.int32)
  logits = tasks["xla"].ComputePredictions(theta, NestedMap(
      ids=jnp.asarray(ids), paddings=jnp.zeros((2, 64)))).logits
  for row, at in ((0, 63), (1, 30), (1, 2)):
    want = _ReferenceLogits(theta, ids[row], at)
    np.testing.assert_allclose(np.asarray(logits[row, at]), want,
                               atol=_LOGIT_TOL)


def test_the_references_controls_are_other_layers(tiny):
  """What benchmarks/tools/brumby_controls.py states to the reference (the
  first power, no normaliser) moves its logits far past the tolerance."""
  _, theta = tiny
  seq = np.random.RandomState(4).randint(1, 128, 40).astype(np.int32)
  want = _ReferenceLogits(theta, seq, 39)
  for stated in ({"degree": 1}, {"normalise": False}):
    other = ref.SeededWeights(theta, **stated)     # states, changes nothing
    diff = np.abs(_ReferenceLogits(other, seq, 39) - want).max()
    assert diff > 100 * _LOGIT_TOL, (stated, diff)
  ref.SeededWeights(theta)                          # the file's again


# -- the packed step's two lowerings ------------------------------------------


def _PackedCase(dtype, seed=0):
  """Three rows in one step: one deep in its open chunk, one that crosses
  two page boundaries, one at its first token; a state that is not zero."""
  rng = np.random.RandomState(seed)
  n, nk, h, page, slots, t = 4, 2, 16, 8, 3, 24
  row_len, row_q_pos = [1, 13, 6], [21, 6, 0]
  rows = jax.tree_util.tree_map(jnp.asarray, ragged_lib.BuildRaggedRows(
      row_len, row_q_pos, t, 16))
  tables = jnp.asarray(np.arange(slots * 6).reshape(slots, 6), jnp.int32)
  f32 = jnp.float32
  pool = NestedMap(
      key=jnp.asarray(rng.randn(19, page, nk, h), dtype),
      value=jnp.asarray(rng.randn(19, page, nk, h), dtype),
      gate=jnp.asarray(-np.abs(rng.randn(19, nk, page)) * 0.1, f32))
  # a page's gates are cumulated from its first token
  pool.gate = jnp.cumsum(pool.gate, axis=-1)
  state = jnp.asarray(rng.randn(slots, nk, h, op.StoredDim(h)), f32)
  norm = jnp.asarray(np.abs(rng.randn(slots, nk, op.Offsets(h), h)) + 1, f32)
  q = jnp.asarray(rng.randn(t, n, h), f32) / np.sqrt(h)
  k = jnp.asarray(rng.randn(t, nk, h), f32)
  v = jnp.asarray(rng.randn(t, nk, h), dtype)
  log_g = jnp.asarray(-np.abs(rng.randn(t, nk)) * 0.1, f32)
  return (q, k, v, log_g, state, norm, pool, tables, rows), np.asarray(
      rows.valid)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_the_kernels_are_the_xla_form(dtype, tol):
  """The four kernels (interpret mode) against the gathers of the XLA form:
  outputs, the state and z after the step's folds, the pages written. With
  16-bit pages the kernels' products run as they do on the chip (the
  state's query in two 16-bit halves, the fold in three passes)."""
  args, valid = _PackedCase(dtype)
  want = op.PackedRetention(*args, eps=1e-6, lowering="xla")
  got = op.PackedRetention(*args, eps=1e-6, lowering="pallas")
  scale = float(jnp.abs(want[0]).max())
  np.testing.assert_allclose(np.asarray(got[0])[valid],
                             np.asarray(want[0])[valid], atol=tol * scale)
  for a, b in ((got[1], want[1]), (got[2], want[2])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=tol * float(jnp.abs(b).max()) / 10)
  for name in ("key", "value", "gate"):
    np.testing.assert_array_equal(
        np.asarray(got[3][name][:18].astype(jnp.float32)),
        np.asarray(want[3][name][:18].astype(jnp.float32)))
  # row 1 crossed two boundaries and row 2 none: two folds, and a reset
  plan = op.BuildStepPlan(args[-1], 3, 8)
  assert int(plan.entries) == 3 and np.asarray(plan.folds).tolist() == [0, 2, 0]
  assert np.asarray(plan.e_zero)[:3].tolist() == [False, False, True]
  assert np.asarray(plan.e_add)[:3].tolist() == [True, True, False]


def test_a_stacked_state_is_read_and_written_in_place():
  """`layer`: the block's stack of states with the repeat's index is the
  layer's own state, and the other layers' are not touched."""
  args, valid = _PackedCase(jnp.float32, seed=1)
  q, k, v, log_g, state, norm, pool, tables, rows = args
  want = op.PackedRetention(*args, eps=1e-6, lowering="xla")
  stack = lambda x: jnp.stack([x + 1.0, x, x - 1.0])
  for lowering in ("xla", "pallas"):
    got = op.PackedRetention(q, k, v, log_g, stack(state), stack(norm), pool,
                             tables, rows, eps=1e-6, lowering=lowering,
                             layer=jnp.asarray(1))
    np.testing.assert_allclose(np.asarray(got[0])[valid],
                               np.asarray(want[0])[valid], atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[1][1]), np.asarray(want[1]),
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got[1][0]),
                                  np.asarray(state + 1.0))
    np.testing.assert_array_equal(np.asarray(got[2][2]),
                                  np.asarray(norm - 1.0))


def test_step_counts_are_the_plans():
  rows = ragged_lib.BuildRaggedRows([1, 13, 6, 0], [21, 6, 0, 5], 24, 16)
  live, folds, attended = op.StepCounts(rows.row_q_pos, rows.row_len, 8)
  plan = op.BuildStepPlan(jax.tree_util.tree_map(jnp.asarray, rows), 4, 8)
  assert live == 3 and folds == int(np.asarray(plan.folds).sum()) == 2
  # a token attends its row's open chunk and the row's tokens up to itself
  assert attended == (5 + 1) + sum(6 + i + 1 for i in range(13)) + sum(
      i + 1 for i in range(6))


# -- the tiny sibling through ServingLoop --------------------------------------


class _Probe:
  """Every step through the task's ragged step with its logits kept:
  {(slot, position): logits [V]} of every valid token, and every step's
  (row_len, row_q_pos)."""

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
    self.steps.append((np.asarray(rows.row_len).tolist(),
                       np.asarray(rows.row_q_pos).tolist()))
    return jnp.asarray(logits.argmax(-1), jnp.int32), new_states


def _Engine(task, theta, slots, **kw):
  return engine_lib.ServingLoop(task, theta, page_size=8, num_pages=24,
                                max_batch=slots, max_seq_len=128,
                                prefill_token_budget=16, **kw)


def _Serve(task, theta, prompts, new_tokens, between=None, slots=None):
  eng = _Engine(task, theta, slots or len(prompts))
  probe = _Probe(eng, task)
  handles = [eng.Submit(p, new_tokens) for p in prompts]
  for step in range(600):
    if all(h.done for h in handles):
      break
    eng.StepOnce()
    if between is not None:
      between(eng, step)
  assert all(h.done for h in handles)
  return eng, probe, [h.Result() for h in handles]


# pages of 8 and a budget of 16 a step. 37: chunks of 16, 16 and 5, whose
# edges fall on and inside pages; 10 and 21 beside it share the budget, so
# their chunks end inside a page (3, 3, 4; 10, 11); 20 new tokens decode
# across two or three page boundaries each
_PROMPTS = {"three_rows_share_the_budget": [37, 10, 21],
            "a_row_alone": [43], "shorter_than_a_page": [5]}


def _Prompts(case):
  rng = np.random.RandomState(5)
  return [rng.randint(1, 128, n).astype(np.int32) for n in _PROMPTS[case]]


def _HoldToReference(theta, prompts, outs, seen, tol, slots=None):
  for i, (prompt, out) in enumerate(zip(prompts, outs)):
    slot = i if slots is None else slots[i]
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])
    for at in (len(prompt) - 1, len(prompt) + 7, len(seq) - 2):
      want = _ReferenceLogits(theta, seq, at)
      np.testing.assert_allclose(seen[slot, at], want, atol=tol,
                                 err_msg=f"row {i} position {at}")


@pytest.fixture(scope="module")
def served(tiny):
  """{(lowering, case): (engine, probe, streamed tokens)}, served once."""
  cache = {}

  def _Get(lowering, case):
    if (lowering, case) not in cache:
      tasks, theta = tiny
      cache[lowering, case] = _Serve(tasks[lowering], theta, _Prompts(case),
                                     20)
    return cache[lowering, case]

  return _Get


@pytest.mark.parametrize("lowering,case", [
    ("xla", c) for c in _PROMPTS] + [("pallas", "three_rows_share_the_budget")])
def test_chunked_prefill_and_decode_across_folds_match_the_reference(
    tiny, served, lowering, case):
  """Prefill in chunks whose edges fall inside a page, then 20 decode steps
  across two folds and more, through slot state and the open chunk's pages:
  the step's logits at the end of the prompt, eight tokens on and at the
  last token fed back equal the reference's full forward there."""
  _, theta = tiny
  eng, probe, outs = served(lowering, case)
  _HoldToReference(theta, _Prompts(case), outs, probe.seen, _LOGIT_TOL)
  stats = eng.Stats()
  assert stats["kv_pages"]["in_use"] == 0
  assert stats["state_slots"]["in_use"] == 0
  # every row's decode crossed at least two page boundaries
  for n in _PROMPTS[case]:
    assert (n + 19) // 8 - n // 8 >= 2


def test_the_engine_serves_the_stack_on_its_normal_path(tiny, served):
  """Pages and a slot state in one mixer: one pool that ends in the file's
  KV heads and head size, no state leaf that looks like it, the open
  chunk's pages let go behind the cursor, and the counters."""
  tasks, theta = tiny
  eng, probe, outs = served("xla", "three_rows_share_the_budget")
  assert eng.paged_path == "xla"
  assert eng.mixers == {"num_attention": 3, "num_ssm": 3,
                        "decode_state_bytes_per_slot": 3 * op.StateBytes(2, 16)}
  st = eng.Stats()
  assert st["layer_kinds"] == {"PowerRetention+TransformerFeedForwardLayer": 3}
  assert eng._kind_pages.windows == (1, 1, 1)
  assert eng._kind_pages.caps == (3, 3, 3)      # (1 + 16 - 2) // 8 + 2
  page = 8
  pools = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path): x.shape
           for path, x in jax.tree_util.tree_flatten_with_path(eng._states)[0]
           if x.ndim >= 4 and x.shape[-3] == page}
  assert pools == {"kv_pool/key": (73, 8, 2, 16),
                   "kv_pool/value": (73, 8, 2, 16)}
  assert eng._states.kv_pool.gate.shape == (73, 2, 8)
  block = eng._states.blocks[0][0]
  assert block.state.shape == (3, 3, 2, 16, 144)
  assert block.norm.shape == (3, 3, 2, 9, 16)
  # a row held at most the pages of one step's span a layer, and let go of
  # every page it folded
  kv = st["kv_pages"]
  assert kv["kinds"]["full"]["peak_in_use"] == 0
  assert kv["window_pages_released"] > 0 and kv["peak_in_use"] <= 3 * 3 * 3
  tokens = sum(_PROMPTS["three_rows_share_the_budget"]) + sum(
      len(o) for o in outs) - 3
  want_rows = sum(sum(1 for n in row_len if n) for row_len, _ in probe.steps)
  assert st["retention_rows"] == want_rows
  assert st["retention_folds"] == sum(
      (n + 19) // 8 for n in _PROMPTS["three_rows_share_the_budget"])
  assert st["retention_chunk_tokens"] > tokens
  records = [r for r in eng.trace.Steps() if r.counters]
  assert {"retention_rows", "retention_folds",
          "retention_chunk_tokens"} <= set(records[-1].counters)


def test_a_reused_slot_with_its_old_state_and_released_pages_poisoned(tiny):
  """Two requests through the one slot, one after the other. Before the
  second, the slot's state is what the first left (and is made worse: NaN),
  and every page of the pool is poisoned too: the second reads neither."""
  tasks, theta = tiny
  a, b = _Prompts("three_rows_share_the_budget")[:2]
  eng = _Engine(tasks["xla"], theta, 1)
  probe = _Probe(eng, tasks["xla"])
  first = eng.Submit(a, 4)
  while not first.done:
    eng.StepOnce()
  assert float(jnp.abs(eng._states.blocks[0][0].state).max()) > 0
  for layer in eng._states.blocks[0]:
    layer.state = jnp.full_like(layer.state, jnp.nan)
    layer.norm = jnp.full_like(layer.norm, jnp.nan)
  pool = eng._states.kv_pool
  # K, V and gates of every page a row ever held: large, and finite (a page
  # is read whole, and what lies behind a row's horizon is masked by a
  # select of the weights, not of the values)
  trash = pool.key.shape[0] - 1
  for name in ("key", "value", "gate"):
    pool[name] = pool[name].at[:trash].set(1e4)
  probe.seen.clear()
  second = eng.Submit(b, 12)
  while not second.done:
    eng.StepOnce()
  _HoldToReference(theta, [b], [second.Result()], probe.seen, _LOGIT_TOL)


def test_a_slot_that_is_not_reset_shows(tiny, monkeypatch):
  """The control to the test above: with the reset left out
  (`BuildStepPlan(reset=False)`, what benchmarks/tools/brumby_controls.py
  breaks) the second request reads the first's state."""
  tasks, theta = tiny
  monkeypatch.setattr(op, "BuildStepPlan", functools.partial(
      op.BuildStepPlan, reset=False))
  a, b = _Prompts("three_rows_share_the_budget")[:2]
  eng = _Engine(tasks["xla"], theta, 1)
  probe = _Probe(eng, tasks["xla"])
  first = eng.Submit(a, 4)
  while not first.done:
    eng.StepOnce()
  probe.seen.clear()
  second = eng.Submit(b, 12)
  while not second.done:
    eng.StepOnce()
  seq = np.concatenate([b, np.asarray(second.Result(), np.int32)])
  at = len(seq) - 2
  diff = np.abs(probe.seen[0, at] - _ReferenceLogits(theta, seq, at)).max()
  assert diff > 20 * _LOGIT_TOL, diff


def test_a_fold_that_is_lost_shows(tiny):
  """With the state zeroed before the prompt's last chunk (every fold so
  far lost), its last token reads far off the reference."""
  tasks, theta = tiny

  def _Drop(eng, step):
    if step == 1:                                 # 32 of 43 tokens are in
      for layer in eng._states.blocks[0]:
        layer.state = jnp.zeros_like(layer.state)
        layer.norm = jnp.zeros_like(layer.norm)

  prompts = _Prompts("a_row_alone")
  _, probe, outs = _Serve(tasks["xla"], theta, prompts, 2, between=_Drop)
  seq = np.concatenate([prompts[0], np.asarray(outs[0], np.int32)])
  diff = np.abs(probe.seen[0, 42] - _ReferenceLogits(theta, seq, 42)).max()
  assert diff > 20 * _LOGIT_TOL, diff


def test_a_state_kept_in_bf16_shows_in_f32(tiny, monkeypatch):
  """What the cell's `correct` cannot see on a bf16 stream (PERF.md section
  7): with S and z rounded to bf16 wherever the fold writes them (what
  benchmarks/tools/brumby_controls.py --control bf16_state does in the
  kernel) the f32 model's logits leave the reference's by more than this
  file's tolerance, so the state's f32 is held here."""
  tasks, theta = tiny
  fold = op._XlaFold

  def _Rounded(*args, **kw):
    return tuple(x.astype(jnp.bfloat16).astype(x.dtype)
                 for x in fold(*args, **kw))

  monkeypatch.setattr(op, "_XlaFold", _Rounded)
  for leaf in op.InitState(2, 2, 16):
    assert leaf.dtype == jnp.float32
  prompts = _Prompts("a_row_alone")
  _, probe, outs = _Serve(tasks["xla"], theta, prompts, 2)
  seq = np.concatenate([prompts[0], np.asarray(outs[0], np.int32)])
  diff = np.abs(probe.seen[0, 42] - _ReferenceLogits(theta, seq, 42)).max()
  assert diff > 5 * _LOGIT_TOL, diff


def test_slot_state_survives_a_spill_and_a_restore(tiny):
  """Mid-prompt, the slot's state rows go to the host (the engine's slot
  gather), the device's are overwritten, and come back (the engine's slot
  scatter): the stream is unchanged."""
  tasks, theta = tiny

  def _SpillRestore(eng, step):
    if step == 1:
      rows = eng._SpillStateRow(0)
      assert sorted(r.shape for r in rows) == sorted(
          [(3, 2, 16, 144), (3, 2, 9, 16)])
      for layer in eng._states.blocks[0]:
        layer.state = jnp.full_like(layer.state, 7.0)
        layer.norm = jnp.full_like(layer.norm, 7.0)
      eng._RestoreStateRow(0, rows)

  prompts = _Prompts("a_row_alone")
  _, probe, outs = _Serve(tasks["xla"], theta, prompts, 4,
                          between=_SpillRestore)
  seq = np.concatenate([prompts[0], np.asarray(outs[0], np.int32)])
  for at in (42, len(seq) - 2):
    np.testing.assert_allclose(probe.seen[0, at],
                               _ReferenceLogits(theta, seq, at),
                               atol=_LOGIT_TOL)


@pytest.mark.parametrize("kw,names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec=spec_decode.SelfDraft(k=2, num_layers=1)), "spec"),
    (dict(scheduler_mode="priority"), "priority"),
])
def test_paths_of_one_block_table_refuse_the_stack(tiny, kw, names):
  tasks, theta = tiny
  with pytest.raises(ValueError, match=names):
    _Engine(tasks["xla"], theta, 2, **kw)


def test_kind_pages_of_a_stack_with_no_full_layer():
  """Every layer holds only what lies behind its cursor's page: a row keeps
  the page of its cursor and lets go of the ones before it."""
  alloc = kv_cache.PageAllocator(24, 8, page_bytes=1)
  pages = kv_cache.KindPages(alloc, [1, 1], 16, 2, 16)
  assert pages.caps == (3, 3) and pages.Footprint(100) == 6
  pages.Admit("a", 0, 100)
  assert pages.Held("a", 0)[0] == 0 and len(pages.Held("a", 0)[1]) == 3
  assert pages.Advance("a", 7) == 0          # the cursor is in page 0 still
  assert pages.Advance("a", 8) == 2          # page 0 of both layers is behind
  assert pages.Held("a", 1)[0] == 1 and len(pages.Held("a", 1)[1]) == 3
  assert pages.Advance("a", 37) == 6         # pages 1, 2 and 3 of both
  assert pages.Held("a", 0)[0] == 4
  assert pages.in_use == {"full": 0, "window": 6}
  pages.Free("a")
  assert alloc.Stats()["in_use"] == 0
