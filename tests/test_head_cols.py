"""The head runs over the columns a draw is read from (docs/serving_engine.md,
"the step program").

- `TransformerLm.RaggedStep(head_cols=c)` is `RaggedStep()[:, c]` for the
  tiny dense, SmallThinker and Phi-4-flash tasks, on arguments an engine
  really dispatched (a chunk, a finishing prompt, a decode row), and
  the default call still returns `[1, T, V]`,
- a step that holds a mid-prompt chunk, a finishing prompt, decode rows and
  an empty slot: every row's draw lands at its `out_col` (the chunk's last
  column holds one nothing reads), every other column of `sampled [T]` is 0,
  and empty slots (whose `row_cols` point at column 0) leave column 0 to
  the live row it belongs to,
- whole `ServingLoop` runs, two steps in flight and the `feed` gather used,
  stream the tokens of a twin whose step program draws from the full-width
  logits, at temperature 0 and at 0.8 with top-k and per-request seeds,
- draft sources: `head_rows` is a slot's draw and its verify lane, and a
  program whose lane is no narrower than the pack keeps the full head; both
  stream the plain greedy tokens,
- structure: the lowered plain step program holds no `[T, V]` array, and
  `Stats()["head_rows"]` is `max_batch`,
- the contract the benchmark's probe stands on: a stand-in for one "ragged"
  call that takes its first five arguments and returns (argmax over `[T]`,
  new_states), after which the engine goes on as from any step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu import model_registry
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core import sampling
from lingvo_tpu.models.lm.params import phi4flash
from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import scheduler as scheduler_lib
from lingvo_tpu.serving import spec_decode

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

from tests.conftest import InstantiateLm, TinyLmParams
from tests.test_serving_engine import _GreedyRef
from tests.test_spec_decode import _RunStream, _Stream

# bf16 logits of about 1-3: one unit in the last place is 2**-7 at 2; a
# product of 64 rows may accumulate in another order than one of all T
_BF16_TOL = 2e-2


def _Registered(name, dtype, depth=None):
  mp = model_registry.GetParams(name, "Train")
  tp = mp.task
  tp.input = mp.input
  if depth is not None:
    tp.num_layers = depth
    tp.layer_kinds = phi4flash.LayerKinds(depth)
  tp.fprop_dtype = dtype
  task = tp.Instantiate()
  task.FinalizePaths()
  return task, task.InstantiateVariables(jax.random.PRNGKey(7))


_FAMILIES = {
    "dense": lambda dtype: InstantiateLm(TinyLmParams(fprop_dtype=dtype)),
    "smallthinker": lambda dtype: _Registered(
        "lm.smallthinker.SmallThinkerTiny", dtype),
    "phi4flash": lambda dtype: _Registered(
        "lm.phi4flash.Phi4MiniFlashTiny", dtype, depth=8),
    "nemotron_h": lambda dtype: _Registered(
        "lm.nemotron_h.Nemotron3NanoTiny", dtype),
    "brumby": lambda dtype: _Registered("lm.brumby.BrumbyTiny", dtype),
}
# families newer than the tables that other test files keep a family
# (tests/test_step_trace.py, tests/test_ragged_step.py: the parent's counts)
_NEWER_FAMILIES = {
    "mistral4": lambda dtype: _Registered(
        "lm.mistral4.MistralSmall4Tiny", dtype),
    "trinity": lambda dtype: _Registered("lm.trinity.TrinityTiny", dtype),
    "lfm2": lambda dtype: _Registered("lm.lfm2.Lfm2Tiny", dtype),
}


class _Calls:
  """Every "ragged" call of an engine, kept: its arguments and its draws."""

  def __init__(self, engine):
    self.calls = []
    self._inner = engine._compile_log.Call
    engine._compile_log.Call = self._Call

  def _Call(self, name, fn, *args):
    out = self._inner(name, fn, *args)
    if name == "ragged":
      self.calls.append((args, out))
    return out


def _MixedStepEngine(task, theta, **kw):
  """An engine driven until one step holds a mid-prompt chunk (slot 2), a
  finishing prompt (slot 1), a decode row (slot 0) and an empty slot (3).
  Returns (engine, calls, that step's batch)."""
  eng = engine_lib.ServingLoop(
      task, theta, page_size=8, num_pages=48, max_batch=4, max_seq_len=128,
      prefill_token_budget=8, **kw)
  calls = _Calls(eng)
  eng.Submit([5, 9, 2], 6, eos_id=None, seed=11)
  eng.StepOnce()                       # slot 0 prefills, then decodes
  eng.Submit([7, 1, 4], 6, eos_id=None, seed=12)
  eng.Submit(list(range(1, 31)), 6, eos_id=None, seed=13)
  eng.StepOnce()
  batch = eng._in_flight[-1][0]
  row_len = np.asarray(batch.rows_desc.row_len)
  assert row_len.tolist() == [1, 3, 8, 0], row_len
  assert batch.out_col[2] == -1 and batch.out_col[3] == -1
  assert batch.out_col[0] == 0         # the column an empty slot points at
  return eng, calls, batch


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("family", list(_FAMILIES) + list(_NEWER_FAMILIES))
def test_head_cols_are_columns_of_the_full_logits(family, dtype):
  task, theta = {**_FAMILIES, **_NEWER_FAMILIES}[family](
      jnp.float32 if dtype == "f32" else jnp.bfloat16)
  _, calls, _ = _MixedStepEngine(task, theta)
  theta_, states, tok_ids, rows, tables = calls.calls[-1][0][:5]
  t, v = tok_ids.shape[0], task.p.vocab_size
  # out of order, one column twice, the chunk's last column
  cols = jnp.asarray([3, 0, 7, 3, t - 1], jnp.int32)

  full, full_states = jax.jit(
      lambda th, st: task.RaggedStep(th, tok_ids[None], st, tables, rows))(
          theta_, states)
  some, some_states = jax.jit(
      lambda th, st, c: task.RaggedStep(th, tok_ids[None], st, tables, rows,
                                        head_cols=c))(theta_, states, cols)
  assert full.shape == (1, t, v) and some.shape == (1, cols.shape[0], v)
  want = np.asarray(full[0].astype(jnp.float32))[np.asarray(cols)]
  got = np.asarray(some[0].astype(jnp.float32))
  if dtype == "f32":
    np.testing.assert_array_equal(got, want)
  else:
    np.testing.assert_allclose(got, want, atol=_BF16_TOL, rtol=0)
  # the stack ran over every token either way
  for a, b in zip(jax.tree_util.tree_leaves(full_states),
                  jax.tree_util.tree_leaves(some_states)):
    np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                  np.asarray(b.astype(jnp.float32)))


def _FullWidthDraws(task, eng, args):
  """What the step program drew before: a token for every packed column,
  each from its row's (seed, output position) stream."""
  theta, states, tok_ids, rows, tables, seeds, pos = args[:7]
  logits, new_states = task.RaggedStep(theta, tok_ids[None], states, tables,
                                       rows)
  row = jnp.clip(rows.row_of, 0, eng.max_batch - 1)
  sampled = sampling.SampleFromLogits(
      logits[0], jax.random.PRNGKey(eng.sample_seed),
      temperature=eng.temperature, top_k=eng.top_k, row_seeds=seeds[row],
      positions=pos[row])
  return sampled, new_states


_SAMPLING = {"greedy": {}, "temp0.8_top8": dict(temperature=0.8, top_k=8,
                                                sample_seed=7)}


@pytest.mark.parametrize("how", list(_SAMPLING))
def test_each_rows_draw_lands_at_its_out_col(tiny_lm, how):
  task, theta = tiny_lm
  eng, calls, batch = _MixedStepEngine(task, theta, **_SAMPLING[how])
  args, out = calls.calls[-1]
  sampled = np.asarray(out[0])
  assert sampled.shape == (eng._ragged_t,) and sampled.dtype == np.int32
  full = np.asarray(_FullWidthDraws(task, eng, args)[0])
  # a live row's last column holds what that column drew when every column
  # did (the chunk's too, which nothing reads: out_col -1); the rest is 0
  desc = batch.rows_desc
  last = [int(desc.row_cols[i, n - 1]) for i, n in enumerate(desc.row_len)
          if n > 0]
  assert last == [0, 3, 11] and list(batch.out_col) == [0, 3, -1, -1]
  want = np.zeros_like(sampled)
  want[last] = full[last]
  np.testing.assert_array_equal(sampled, want)


def test_an_empty_slot_writes_nothing_to_column_0(tiny_lm):
  """Empty slots before and after the live row whose draw column is 0: their
  `row_cols` point there too (core/ragged.py), and whichever of them a
  scatter took last, the column would hold another stream's token (at a
  temperature that flattens the tied head's liking for the input token)."""
  task, theta = tiny_lm
  eng = engine_lib.ServingLoop(
      task, theta, page_size=8, num_pages=48, max_batch=5, max_seq_len=128,
      prefill_token_budget=8, temperature=4.0, sample_seed=7)
  rows = ragged_lib.BuildRaggedRows(
      [0, 1, 3, 7, 0], [0, 5, 0, 8, 0], eng._ragged_t, eng._ragged_wmax)
  assert rows.row_cols[:, 0].tolist() == [0, 0, 1, 4, 0]
  args = (eng._theta, eng._states,
          jnp.arange(1, eng._ragged_t + 1, dtype=jnp.int32),
          ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows)),
          jnp.asarray(eng.sched.block_tables),
          jnp.asarray([31, 22, 23, 24, 45], jnp.int32),
          jnp.asarray([4, 3, 2, 1, 0], jnp.int32))
  sampled = np.asarray(eng._ragged_fn(*args)[0])
  full = np.asarray(_FullWidthDraws(task, eng, args)[0])
  want = np.zeros_like(sampled)
  want[[0, 3, 10]] = full[[0, 3, 10]]
  np.testing.assert_array_equal(sampled, want)
  # the streams differ, so the test can tell: slot 0's and slot 4's draws
  # from column 0's logits are not slot 1's
  logits = task.RaggedStep(args[0], args[2][None], args[1], args[4],
                           args[3])[0][0]
  others = {int(sampling.SampleFromLogits(
      logits[:1], jax.random.PRNGKey(eng.sample_seed),
      temperature=eng.temperature, top_k=eng.top_k,
      row_seeds=args[5][i:i + 1], positions=args[6][i:i + 1])[0])
            for i in (0, 4)}
  assert int(sampled[0]) not in others


@pytest.mark.parametrize("how", list(_SAMPLING))
def test_streams_are_the_full_width_programs(tiny_lm, how):
  task, theta = tiny_lm
  reqs = _Stream(12, seed=21) + [(list(range(1, 20)), 5)]

  def _Run(full_width):
    eng = engine_lib.ServingLoop(
        task, theta, page_size=4, num_pages=32, max_batch=3, max_seq_len=32,
        prefill_chunk=4, default_max_new=8, **_SAMPLING[how])
    if full_width:
      twin = jax.jit(lambda *args: _FullWidthDraws(task, eng, args))
      inner = eng._compile_log.Call
      eng._compile_log.Call = lambda name, fn, *args: (
          twin(*args) if name == "ragged" else inner(name, fn, *args))
    handles = [eng.Submit(p, m, eos_id=None, seed=40 + i)
               for i, (p, m) in enumerate(reqs)]
    while eng.sched.HasWork():
      eng.StepOnce()
    return eng, [h.Result(timeout=0) for h in handles]

  eng, got = _Run(False)
  _, want = _Run(True)
  assert got == want
  stats = eng.Stats()
  assert stats["steps_overlapped"] > 0.9 * stats["steps"]   # two in flight
  assert stats["compile"]["feed"]["calls"] > 0
  assert stats["compile"][observe_schema.COMPILE_CENSUS_KEY] == 1


@pytest.mark.parametrize("spec,budget,head_rows,packed", [
    # a slot's draw and its k + 1 (w * k + 1) verify columns
    pytest.param(lambda: spec_decode.SelfDraft(k=3, num_layers=1), 4,
                 3 * 5, 3 * 4 + 4, id="chain_narrow"),
    pytest.param(lambda: spec_decode.SelfDraft(k=2, w=2, num_layers=1), 4,
                 3 * 6, 3 * 5 + 4, id="tree_narrow"),
    # no fewer columns than the pack holds: the full head, gathered after
    pytest.param(lambda: spec_decode.SelfDraft(k=3, num_layers=1), 2,
                 3 * 4 + 2, 3 * 4 + 2, id="chain_full_head"),
    pytest.param(lambda: spec_decode.SelfDraft(k=2, w=2, num_layers=1), 3,
                 3 * 5 + 3, 3 * 5 + 3, id="tree_full_head"),
])
def test_draft_engines_head_rows_and_greedy_streams(tiny_lm, spec, budget,
                                                    head_rows, packed):
  task, theta = tiny_lm
  eng = engine_lib.ServingLoop(
      task, theta, page_size=4, num_pages=24, max_batch=3, max_seq_len=32,
      prefill_chunk=budget, default_max_new=8, spec=spec())
  assert (eng.head_rows, eng._ragged_t) == (head_rows, packed)
  reqs = _Stream(8, seed=31)
  for (prompt, max_new), out in zip(reqs, _RunStream(eng, reqs)):
    assert out == _GreedyRef(task, theta, prompt, max_new)
  stats = eng.Stats()
  assert stats["spec_cycles"] > 0 and stats["head_rows"] == head_rows
  assert stats["compile"][observe_schema.COMPILE_CENSUS_KEY] == 1


def test_plain_step_program_holds_no_packed_logits():
  # a vocabulary of 96 is no other width of the tiny model (32, 64)
  task, theta = InstantiateLm(TinyLmParams(vocab_size=96))
  eng, calls, _ = _MixedStepEngine(task, theta)
  t, v, b = eng._ragged_t, 96, eng.max_batch
  assert (t, b) == (12, 4)
  args = calls.calls[-1][0]
  lowered = eng._ragged_fn.lower(*args).as_text()
  assert f"{t}x{v}x" not in lowered
  assert f"tensor<1x{b}x{v}x" in lowered or f"tensor<{b}x{v}x" in lowered
  # the search finds the array where it is: the task's own default call
  theta_, states, tok_ids, rows, tables = args[:5]
  full = jax.jit(lambda th, st: task.RaggedStep(
      th, tok_ids[None], st, tables, rows)).lower(theta_, states).as_text()
  assert f"tensor<1x{t}x{v}x" in full
  stats = observe_schema.ValidateEngineStats(eng.Stats())
  assert stats["head_rows"] == b
  # a constant of the step program: a gauge, not a column of every record
  assert eng.metrics.Snapshot()["serving/head_rows"] == b
  records = eng.trace.Steps()
  # (a step that compiled carries its programs' names there, nothing else)
  assert records and all(
      set(r.counters or ()) <= {"compile_fun_names"} for r in records)
  assert all((r.counters is None) == (r.compile_s == 0.0) for r in records)


def test_a_stand_in_for_one_step_hands_back_every_columns_argmax(tiny_lm):
  """`benchmarks/harness/serve_cell.LogitProbe`'s contract (the rehearsal
  in tests/benchmark/ drives the probe itself): one "ragged" call answered
  by another program from its first five arguments, `sampled` over all T
  columns first and the states last; the next step's feed and the commit
  read their columns of it and the streams are the greedy reference's."""
  task, theta = tiny_lm
  eng = engine_lib.ServingLoop(
      task, theta, page_size=4, num_pages=32, max_batch=3, max_seq_len=32,
      prefill_chunk=4, default_max_new=8)

  def _Step(th, states, tok_ids, rows, tables):
    logits, new_states = task.RaggedStep(th, tok_ids[None], states, tables,
                                         rows)
    return jnp.argmax(logits[0], -1).astype(jnp.int32), new_states

  stood_in = []
  inner = eng._compile_log.Call

  def _Call(name, fn, *args):
    if name != "ragged" or eng.Stats()["steps"] != 5:
      return inner(name, fn, *args)
    stood_in.append(args[2].shape)
    return jax.jit(_Step)(*args[:5])

  eng._compile_log.Call = _Call
  reqs = _Stream(9, seed=41)
  outs = _RunStream(eng, reqs)
  assert stood_in == [(eng._ragged_t,)]
  for (prompt, max_new), out in zip(reqs, outs):
    assert out == _GreedyRef(task, theta, prompt, max_new)
  assert eng.sched.finished == len(reqs)
  assert all(s is None or s.state is scheduler_lib.SeqState.FINISHED
             for s in eng.sched.slots)
