"""Trinity-Mini's mechanisms at a size the CPU holds, against the plain
reference (benchmarks/references/trinity.py): grouped attention whose q and k
are normed over a head before any rotation and whose output is gated before
its projection, window layers that rotate beside a full layer that carries no
position, four norms a layer, a leading dense layer in front of sigmoid-routed
experts with a selection bias and a shared expert, in ONE `BlockSequence`
told by names that say mixer and feed-forward apart; and the tiny registered
sibling served by ServingLoop in chunks and decode steps through one pool of
pages that its window layers let go of."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import trinity as ref
from benchmarks.tools import trinity_controls as controls
from lingvo_tpu import model_registry
from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.models.lm import layers as lm_layers
from lingvo_tpu.models.lm.params import trinity
from lingvo_tpu.serving import engine as engine_lib

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

# the served f32 model against the f32 reference: both sum the same products
# in another order (sorted experts, paged attention by blocks), which reads
# 2e-6 on logits of about 1; the same weights rounded to bf16 read 1e-2 and
# more (test_bf16_weights_fail_the_tolerance)
_LOGIT_TOL = 2e-4
_WINDOW, _PAGE = 24, 8
# what the cell's file states of its weights, at the tiny size: the routers
# read the eighth of the stream no layer writes, a selection bias that would
# show if it weighed, head norms whose scales differ by dim
_WEIGHTS = dict(router_scale=6.0, router_reads_share=0.125,
                router_bias_spread=0.15, head_norm_spread=1.0)


def _Task(model="lm.trinity.TrinityTiny", **task_params):
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
  """(task, theta): the dense lead layer, three window layers, the full one."""
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
  kinds = trinity.LayerKinds(32)
  # layer_types: sliding, sliding, sliding, full, eight times over
  assert [k.split("+")[0] for k in kinds] == (["gqa_window"] * 3 + ["gqa"]) * 8
  # num_dense_layers 2
  assert [k.split("+")[1] for k in kinds] == ["dense"] * 2 + ["experts"] * 30
  blocks = lm_layers.KindBlocks(kinds)
  assert [k for ks, r in blocks for k in ks * r] == kinds
  assert blocks[0] == (["gqa_window+dense"], 2)
  assert blocks[1] == (["gqa_window+experts", "gqa+experts",
                        "gqa_window+experts", "gqa_window+experts"], 7)
  # one stage's: the leading dense layer and one whole period behind the
  # dense ones (published layers 0 and 4-7), what the cell runs
  assert trinity.StageKinds() == [kinds[0]] + kinds[4:8]
  assert lm_layers.KindBlocks(trinity.StageKinds()) == [
      (["gqa_window+dense"], 1), (["gqa_window+experts"], 3),
      (["gqa+experts"], 1)]


def test_the_published_model_counts_its_parameters_from_shapes():
  mp = model_registry.GetParams("lm.trinity.TrinityMini", "Train")
  tp = mp.task
  tp.input = mp.input
  task = tp.Instantiate()
  total = sum(int(np.prod(s.shape))
              for s in jax.tree_util.tree_leaves(task.VariableSpecs()))
  atten = 2048 * 4096 * 3 + 2 * 2048 * 512 + 256   # q, o, gate; k, v; q/k norm
  norms = 4 * 2048
  dense = atten + norms + 3 * 2048 * 6144
  experts = (atten + norms + 129 * 3 * 2048 * 1024 + 2048 * 128 + 128)
  assert (dense, experts) == (65020160, 839131520)     # ISSUE 61's arithmetic
  want = 2 * dense + 30 * experts + 2 * 200192 * 2048 + 2048
  assert total == want
  assert 26.0e9 < total < 26.2e9                        # "26B-A3B"


def test_a_stage_is_a_lead_block_and_a_scanned_block_in_one_pool(tiny):
  task, theta = tiny
  assert task.stack.PageWindows() == [_WINDOW] * 4 + [0]
  assert task.stack.LayerKinds() == {
      "PooledAttention+TransformerFeedForwardLayer": 1,
      "PooledAttention+DroplessMoELayer": 4}
  assert task.stack._repeats == [1, 3, 1]
  eng = engine_lib.ServingLoop(task, theta, page_size=_PAGE, num_pages=48,
                               max_batch=2, max_seq_len=128,
                               prefill_token_budget=16)
  kp = eng._kind_pages
  assert kp.windows == (_WINDOW,) * 4 + (0,) and kp.alloc is eng.alloc
  # the bytes of 48 pages at all five layers: 240 pages of one layer each
  assert eng.alloc.num_pages == 48 * 5
  pools = [tuple(x.shape) for x in jax.tree_util.tree_leaves(eng._states)
           if x.ndim == 4]
  assert pools == [(48 * 5 + 1, _PAGE, 2, 16)] * 2          # K and V, once
  kv = eng.Stats()["kv_pages"]
  assert kv["window_cap_pages"] == (_WINDOW + 16 - 2) // _PAGE + 2


# -- (a) the whole model -------------------------------------------------------


@pytest.mark.parametrize("row,at", [(0, 63), (1, 30), (1, 2)])
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


# -- (d) every control the CPU can show ----------------------------------------


_CPU_CONTROLS = [c for c in controls.CONTROLS
                 if c not in ("none", "fp8_weights", "fp8_experts", "lead_layer_experts")]


@pytest.mark.parametrize("control", _CPU_CONTROLS)
def test_a_control_fails_the_tolerance(tiny, control):
  """The program with ONE thing broken (benchmarks/tools/trinity_controls.py,
  what the chip's controls break) against the reference that keeps it: the
  whole-sequence forward at a position three windows in reads far over the
  tolerance that the sound program keeps."""
  task, theta = tiny
  ids = np.random.RandomState(4).randint(1, 128, (1, 96)).astype(np.int32)
  want = _ReferenceLogits(theta, ids[0], 95)
  assert np.abs(_Forward(task, theta, ids)[0, 95] - want).max() < _LOGIT_TOL
  controls.Break.page_size = _PAGE
  try:
    controls.Break(control)
    broken = _Task(**{k.replace(".", "__"): v for k, v in
                      controls.BrokenTaskParams(control, {}).items()})
    got = _Forward(broken, controls.BrokenWeights(control, theta), ids)[0, 95]
  finally:
    controls.Restore()
    controls.Break.page_size = 128
  assert np.abs(got - want).max() > 10 * _LOGIT_TOL, control
  # ... and the patches are gone
  assert np.abs(_Forward(task, theta, ids)[0, 95] - want).max() < _LOGIT_TOL


def test_a_leading_expert_layer_is_refused_by_the_reference(tiny):
  """The file states ONE leading dense layer: weights whose first layer
  holds a router are another model's, whatever their numbers."""
  kinds = controls.BrokenTaskParams(
      "lead_layer_experts", {"layer_kinds": trinity.StageKinds()})
  assert kinds["layer_kinds"][0] == "gqa_window+experts"
  task = _Task(layer_kinds=kinds["layer_kinds"])
  assert task.stack._repeats == [4, 1]
  theta = _Seeded(task)
  with pytest.raises(AssertionError):
    _ReferenceLogits(theta, np.arange(1, 33, dtype=np.int32), 31)


def test_the_bias_chooses_and_does_not_weigh(tiny):
  """The seeded bias moves the choice (another top-3 than the scores' own
  for some token) and the weights are the chosen SCORES over their sum."""
  task, theta = tiny
  ff = theta.stack["block_2"].x_layers[0].fflayer
  ref._ARCH.clear()
  ref._ARCH.update(ref._Arch(48))
  ref._ARCH.update(ref._STATED)
  u = jnp.asarray(np.random.RandomState(0).randn(64, 48), jnp.float32)
  idx, w = ref.Route(ff, 0, u)
  scores = jax.nn.sigmoid(u @ ff.w_router[0])
  plain = jax.lax.top_k(scores, 3)[1]
  assert (np.sort(np.asarray(idx), -1) != np.sort(np.asarray(plain), -1)).any()
  chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
  np.testing.assert_allclose(
      np.asarray(w), 2.826 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)


def test_routers_read_dimensions_no_layer_writes(tiny):
  """`router_reads_share`: the first eighth of the stream holds the scaled
  embedding in every layer (each branch's output norm is zero there), its
  table entries powers of two, so a bf16 program's product with
  bf16(sqrt(D)) is exact."""
  task, theta = tiny
  reads = 48 // 8
  emb = np.asarray(theta.emb.emb)[:, :reads]
  mantissa, _ = np.frexp(emb)
  assert np.isin(np.abs(mantissa), (0.0, 0.5)).all()
  # as layers.SharedEmbeddingSoftmaxLayer.EmbLookup multiplies: a Python
  # float is weakly typed, so the factor is bf16(sqrt(D))
  product = (jnp.asarray(emb, jnp.bfloat16) * math.sqrt(48.0)).astype(
      jnp.float32)
  np.testing.assert_array_equal(
      np.asarray(product), emb * float(jnp.asarray(math.sqrt(48.0),
                                                   jnp.bfloat16)))
  for path, leaf in jax.tree_util.tree_flatten_with_path(theta)[0]:
    keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
    if keys[-2:] == ["post_ln", "scale"]:
      assert (np.asarray(leaf)[..., :reads] == -1).all()
    if keys[-1] == "w_router":
      assert (np.asarray(leaf)[..., reads:, :] == 0).all()
      assert (np.asarray(leaf)[..., :reads, :] != 0).all()


# -- the new Params are neutral for every other model --------------------------


@pytest.mark.parametrize("model", [
    "lm.smallthinker.SmallThinkerTiny", "lm.phi4flash.Phi4MiniFlashTiny",
    "lm.nemotron_h.Nemotron3NanoTiny", "lm.brumby.BrumbyTiny",
    "lm.mistral4.MistralSmall4Tiny", "lm.granite_hybrid.Granite40HSmallTiny",
    "lm.synthetic_packed_input.DenseLmTiny"])
def test_defaults_of_the_new_params_are_neutral(model):
  """A stack that states none of them builds no variable of theirs and
  enters none of their scopes: the other configurations' programs are what
  they were (`qk_norm` is a power-retention layer's own already)."""
  task = _Task(model)
  assert task.p.post_norm is False
  paths = {jax.tree_util.keystr(path) for path, _ in
           jax.tree_util.tree_flatten_with_path(task.VariableSpecs())[0]}
  assert not any("post_ln" in p or "q_norm'" in p or "k_norm'" in p
                 for p in paths)
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  ids = jnp.asarray(np.random.RandomState(1).randint(1, 128, (1, 16)))
  text = jax.jit(lambda th: task.ComputePredictions(th, NestedMap(
      ids=ids, paddings=jnp.zeros(ids.shape))).logits).lower(
          theta).as_text(debug_info=True)
  assert "atten_gate" not in text and "post_norm" not in text
  if "brumby" not in model:
    assert "qk_norm" not in text


def test_attention_defaults_build_no_norm_and_no_gate():
  p = attention_lib.MultiHeadedAttention.Params()
  assert p.qk_norm_epsilon is None and p.output_gate is False
  layer = p.Set(name="a", input_dim=32, hidden_dim=32, num_heads=4
                ).Instantiate()
  layer.FinalizePaths()
  assert set(layer.InstantiateVariables(jax.random.PRNGKey(0))) >= {
      "w_query", "w_key", "w_value", "w_post"}
  assert not {"w_gate", "q_norm", "k_norm"} & set(
      layer.InstantiateVariables(jax.random.PRNGKey(0)))


def test_the_dense_decode_contracts_carry_norm_and_gate():
  """ExtendStep and Prefill of a plain multi-head layer with the head norm
  and the gate are FProp's, token by token and chunk by chunk."""
  p = attention_lib.MultiHeadedAttention.Params().Set(
      name="a", input_dim=32, hidden_dim=32, num_heads=4, use_bias=False,
      enable_per_dim_scale=False, use_rotary_position_emb=True,
      qk_norm_epsilon=1e-5, output_gate=True)
  layer = p.Instantiate()
  layer.FinalizePaths()
  theta = layer.InstantiateVariables(jax.random.PRNGKey(1))
  theta.q_norm.scale = jnp.linspace(-0.5, 0.5, 8)
  theta.k_norm.scale = jnp.linspace(0.4, -0.4, 8)
  x = jnp.asarray(np.random.RandomState(2).randn(2, 12, 32), jnp.float32)
  want, _ = layer.FProp(theta, x, causal=True)
  states = layer.InitStates(theta, 2, 12)
  got, states = layer.Prefill(theta, x[:, :8], states)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, :8]),
                             atol=2e-5)
  for t in range(8, 12):
    step, states = layer.ExtendStep(theta, x[:, t:t + 1], states)
    np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(want[:, t]),
                               atol=2e-5)


@pytest.mark.parametrize("cls", ["LocalSelfAttention",
                                 "ChunkwiseSelfAttention"])
def test_layers_without_norm_and_gate_refuse_them_by_name(cls):
  p = getattr(attention_lib, cls).Params().Set(
      name="a", input_dim=32, hidden_dim=32, num_heads=4, output_gate=True)
  if cls == "LocalSelfAttention":
    p.Set(block_size=4, left_context=4, right_context=0)
  else:
    p.Set(chunk_size=4)
  layer = p.Instantiate()
  layer.FinalizePaths()
  theta = layer.InstantiateVariables(jax.random.PRNGKey(0))
  with pytest.raises(NotImplementedError, match="output_gate"):
    layer.FProp(theta, jnp.zeros((1, 8, 32)))


# -- (b) the tiny sibling through ServingLoop ----------------------------------


class _Probe:
  """Every step through the task's ragged step with its logits kept:
  {(slot, position): logits [V]} of every valid token."""

  def __init__(self, engine, task):
    self.engine, self.seen = engine, {}
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
    counts = jnp.concatenate(engine_lib._MoeCountLeaves(new_states), axis=0)
    return jnp.asarray(logits.argmax(-1), jnp.int32), counts, new_states


def _PoisonDeadPages(eng):
  """Into the pool, what no query may read: NaN in every page no row holds
  (never handed out, or let go of by a window for good), a huge number in
  every page a row holds with nothing live in it yet."""
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


def _Serve(task, theta, prompts, new_tokens, poison=False):
  eng = engine_lib.ServingLoop(task, theta, page_size=_PAGE, num_pages=48,
                               max_batch=len(prompts), max_seq_len=128,
                               prefill_token_budget=16)
  probe = _Probe(eng, task)
  handles = [eng.Submit(p, new_tokens) for p in prompts]
  for _ in range(600):
    if all(h.done for h in handles):
      break
    eng.StepOnce()
    if poison:
      _PoisonDeadPages(eng)
  assert all(h.done for h in handles)
  return eng, probe, [h.Result() for h in handles]


# chunks of 16 against a window of 24: 90 runs past three windows, so chunk
# boundaries fall inside a window and across its edge; 40 ends mid-window;
# 10 is shorter than a chunk; 25 is one token past the window, its second
# chunk the first to leave a key behind
_PROMPTS = {"longer_than_three_windows": [90], "shorter_than_a_chunk": [10],
            "one_past_the_window": [25],
            "uneven_chunks_in_one_step": [90, 10, 50]}


def _Prompts(case):
  rng = np.random.RandomState(5)
  return [rng.randint(1, 128, n).astype(np.int32) for n in _PROMPTS[case]]


@pytest.fixture(scope="module")
def served(tiny):
  cache = {}

  def _Get(case, poison=False):
    if (case, poison) not in cache:
      cache[case, poison] = _Serve(*tiny, _Prompts(case), 8, poison=poison)
    return cache[case, poison]

  return _Get


@pytest.mark.parametrize("case", sorted(_PROMPTS))
def test_chunked_prefill_and_decode_match_the_reference(tiny, served, case):
  """Prefill in chunks (a budget of 16 shared by the rows of a step) and 8
  decode steps through the one pool of pages: the step's logits at the end
  of the prompt and at the last token fed back equal the reference's full
  forward there (logits, not sampled tokens)."""
  _, theta = tiny
  eng, probe, outs = served(case)
  for slot, (prompt, out) in enumerate(zip(_Prompts(case), outs)):
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])
    for at in (len(prompt) - 1, len(seq) - 2):
      np.testing.assert_allclose(
          probe.seen[slot, at], _ReferenceLogits(theta, seq, at),
          atol=_LOGIT_TOL, err_msg=f"row {slot} position {at}")
  kv = eng.Stats()["kv_pages"]
  # a cursor past window + a page lets go of a page in each window layer
  if max(_PROMPTS[case]) + 8 > _WINDOW + _PAGE:
    assert kv["window_pages_released"] > 0
    assert kv["window_pages_released"] % 4 == 0      # four window layers
  else:
    assert kv["window_pages_released"] == 0
  assert kv["in_use"] == 0
  assert kv["kinds"]["window"]["in_use"] == kv["kinds"]["full"]["in_use"] == 0


@pytest.mark.parametrize("case", ["longer_than_three_windows",
                                  "uneven_chunks_in_one_step"])
def test_released_window_pages_are_never_read(served, case):
  """With NaN in every page no live row may read, after every step, the
  engine streams the same tokens and its logits stay finite: a released
  page is behind every query that follows, in the lead block's window layer
  and in the scanned block's three."""
  _, _, clean = served(case)
  _, probe, outs = served(case, poison=True)
  assert outs == clean
  assert all(np.isfinite(v).all() for v in probe.seen.values())


def test_the_engine_counts_experts_and_window_pages_over_both_blocks(served):
  """Four expert layers in two blocks (a scanned three and the full layer's)
  and four window layers in two (the lead block's and the scanned three):
  every valid token's three pairs are counted once an expert layer, and the
  step records carry the counters the cell's readers take."""
  eng, _, outs = served("uneven_chunks_in_one_step")
  stats = eng.Stats()
  tokens = sum(_PROMPTS["uneven_chunks_in_one_step"]) + sum(
      len(o) - 1 for o in outs)
  assert stats["moe_tokens_routed"] == 4 * 3 * tokens
  assert "moe_pairs_elsewhere" not in stats or stats[
      "moe_pairs_elsewhere"] == 0
  assert 0 < stats["moe_experts_active"] <= stats["steps"] * 4 * 8
  assert stats["moe_expert_load_max"] * 8 >= stats["moe_tokens_routed"]
  records = [r for r in eng.trace.Steps() if r.counters]
  assert records
  for name in ("moe_tokens_routed", "moe_experts_active",
               "moe_expert_load_max", "moe_expert_load_mean",
               "window_pages_released", "window_pages_allocated"):
    assert name in records[-1].counters, name
  assert records[-1].counters["window_pages_released"] > 0
  assert stats["layer_kinds"] == {
      "PooledAttention+TransformerFeedForwardLayer": 1,
      "PooledAttention+DroplessMoELayer": 4}


def test_the_step_program_enters_the_three_scopes(tiny):
  """`qk_norm` and `atten_gate` inside `atten`, `post_norm` inside `atten`
  and inside `ffn`: what `atten_gate_ms` and `post_norm_ms` read."""
  from lingvo_tpu.core import ragged as ragged_lib
  task, theta = tiny
  states = task.InitPagedDecodeState(theta, 20, _PAGE, num_slots=2)
  rows = ragged_lib.BuildRaggedRows(np.array([5, 1]), np.array([0, 9]), 8, 16)
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))
  tables = jnp.zeros((5, 2, 4), jnp.int32)
  text = jax.jit(task.RaggedStep).lower(
      theta, jnp.zeros((1, 8), jnp.int32), states, tables, rows).as_text(
          debug_info=True)
  for scope in ("atten/qk_norm", "atten/atten_gate", "atten/post_norm",
                "ffn/post_norm"):
    assert scope in text, scope
