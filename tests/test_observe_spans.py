"""Step spans and scope names (docs/observability.md, "Step spans").

- a step record's segments and `loop` add up to the step's period, over a
  short run of a tiny engine;
- the step deque is bounded and of its own: token events never evict a step;
- `ChromeTrace()` draws the "engine loop" row with matched B/E pairs and
  carries `perStep`; `tools/trace_report.py` prints the phase table;
- `observe.trace.Live()` forgets a collected recorder, and keeps the one that
  recorded the newest step;
- engine steps and train loops under `jax.profiler.trace` leave their
  `lingvo/` spans, with their arguments, on the host plane of the trace;
- a compiled train step and ragged serving step carry every scope name in
  their HLO metadata, and `host_overhead_s` is a perf_counter duration;
- the one declared tree (`observe.schema.DEVICE_SCOPES`): every scope is on
  some op of a compiled tiny program (dense train, dense / SmallThinker /
  Phi-4-flash ragged steps, the Pallas lowerings), an undeclared name raises,
  `observe.Scope` is the program's one way into `jax.named_scope`, the docs'
  table is the tree, and each program's lowered text is byte for byte the
  same with every scope a null context.
"""

import gc
import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu import observe
from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.observe import trace as trace_lib

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import trace_report  # noqa: E402

from tests.test_observe import _CheckChromeTrace, _FakeClock, _TinyLmParams
from tests.test_serving_engine import _GreedyRef

TRAIN_MODEL = "lm.synthetic_packed_input.DenseLmTiny"
# the blocks of the tiny dense train and ragged steps, and what
# MultiHeadedAttention names inside `atten` in both: read from the one tree
_DENSE = ("atten", "qkv_proj", "rope", "out_proj", "ffn", "norm", "embed")
TRAIN_SCOPES = tuple(s for s in observe_schema.DEVICE_SCOPES if s in _DENSE + (
    "head_loss", "optimizer_update"))
SERVE_SCOPES = tuple(s for s in observe_schema.DEVICE_SCOPES if s in _DENSE + (
    "ragged_attend", "kv_write", "head_sample"))
SERVE_SPANS = ("lingvo/serve/step",) + tuple(
    "lingvo/serve/" + p for p in trace_lib.STEP_PHASES if p != "draft")
TRAIN_SPANS = ("lingvo/train/loop", "lingvo/train/infeed_get",
               "lingvo/train/dispatch", "lingvo/train/accumulate",
               "lingvo/train/backpressure",
               "lingvo/train/finalize", "lingvo/train/device_wait",
               "lingvo/train/summaries", "lingvo/infeed/produce",
               "lingvo/infeed/place")


@pytest.fixture(scope="module")
def tiny_lm():
  task = _TinyLmParams().Instantiate()
  task.FinalizePaths()
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  return task, theta


def _Engine(tiny_lm, **kw):
  from lingvo_tpu.serving import engine as engine_lib
  task, theta = tiny_lm
  return engine_lib.ServingLoop(
      task, theta, page_size=4, num_pages=32, max_batch=3, max_seq_len=32,
      prefill_chunk=4, default_max_new=4, **kw)


def _Segments(**named):
  """A segments tuple with the given seconds in the first segment of each
  name, zero elsewhere."""
  out = [0.0] * len(trace_lib.STEP_SEGMENTS)
  for name, s in named.items():
    out[trace_lib.STEP_SEGMENTS.index(name)] = s
  return out


# -- the step record ----------------------------------------------------------


class TestStepRecord:

  def test_phases_add_the_two_lock_waits(self):
    seg = [0.001 * (i + 1) for i in range(len(trace_lib.STEP_SEGMENTS))]
    st = trace_lib.StepTrace(7, 10.0, 0.5, tuple(seg), 5, 3, 2)
    ph = st.Phases()
    assert tuple(ph) == trace_lib.STEP_PHASES
    assert ph["lock_wait"] == pytest.approx(0.001 + 0.008)
    assert sum(ph.values()) == pytest.approx(st.span_s)
    assert st.end_ts == pytest.approx(10.0 + sum(seg))
    m = st.Metrics()
    assert m["step"] == 7 and m["valid_tokens"] == 5 and m["rows"] == 2

  def test_step_deque_is_bounded_oldest_first(self):
    rec = trace_lib.TraceRecorder(clock=_FakeClock(), step_capacity=4)
    for i in range(10):
      rec.StepDone(i, float(i), 0.0, _Segments(dispatch=0.5))
    assert [s.step for s in rec.Steps()] == [6, 7, 8, 9]
    st = rec.Stats()
    assert st["steps_recorded"] == 10 and st["steps_buffered"] == 4

  def test_token_events_never_evict_a_step(self):
    rec = trace_lib.TraceRecorder(capacity=8, completed_capacity=2,
                                  clock=_FakeClock())
    rec.StepDone(1, 0.0, 0.0, _Segments(commit=0.1))
    for rid in range(50):                 # wraps both request-side stores
      rec.Submit(rid, 3, 2)
      rec.Token(rid)
      rec.Retire(rid, "length")
    assert rec.Stats()["events_dropped"] > 0
    assert [s.step for s in rec.Steps()] == [1]

  def test_wrong_segment_count_is_refused(self):
    rec = trace_lib.TraceRecorder(clock=_FakeClock())
    with pytest.raises(AssertionError):
      rec.StepDone(1, 0.0, 0.0, [0.1, 0.2])


# -- a live tiny engine -------------------------------------------------------


@pytest.fixture(scope="module")
def stepped_engine(tiny_lm):
  """A tiny engine driven inline for a dozen steps."""
  eng = _Engine(tiny_lm)
  prompts = np.arange(1, 13, dtype=np.int32).reshape(2, 6)
  eng.RunBatch(prompts, np.array([6, 5]), max_new_tokens=5)
  eng.RunBatch(prompts, np.array([3, 6]), max_new_tokens=4)
  return eng


class TestEngineStepRecords:

  def test_one_record_per_step_numbered_by_the_counter(self, stepped_engine):
    steps = stepped_engine.trace.Steps()
    assert len(steps) == stepped_engine.Stats()["steps"] >= 8
    assert [s.step for s in steps] == list(range(1, len(steps) + 1))

  def test_segments_and_loop_add_up_to_the_period(self, stepped_engine):
    steps = stepped_engine.trace.Steps()
    for prev, st in zip(steps, steps[1:]):
      period = st.end_ts - prev.end_ts
      parts = st.loop_s + sum(st.Phases().values())
      assert parts == pytest.approx(period, rel=0.02), (st.step, st.Metrics())
      assert st.loop_s > 0 and st.start_ts >= prev.end_ts

  def test_every_phase_but_draft_took_time(self, stepped_engine):
    # the loop keeps one step in flight: an iteration's device_wait and
    # commit are the step before's, so the iteration that fills the
    # pipeline (each RunBatch's first) has neither, nor the lock before them
    steps = stepped_engine.trace.Steps()
    fills = [st for st in steps if st.Phases()["device_wait"] == 0.0]
    assert len(fills) == 2 and fills[0] is steps[0]
    for st in steps:
      ph = st.Phases()
      assert ph.pop("draft") == 0.0
      if st in fills:
        assert ph.pop("commit") == 0.0 and ph.pop("device_wait") == 0.0
        assert st.segments_s[7] == 0.0      # the second lock_wait
      assert all(v > 0 for v in ph.values()), ph

  def test_tokens_and_rows_of_a_step(self, stepped_engine):
    steps = stepped_engine.trace.Steps()
    stats = stepped_engine.Stats()
    assert sum(s.prefill_tokens for s in steps) == stats["prompt_tokens"]
    assert all(1 <= s.rows <= 3 and s.valid_tokens >= s.rows for s in steps)
    assert all(s.valid_tokens >= s.prefill_tokens for s in steps)

  def test_an_iteration_with_no_work_leaves_no_record(self, tiny_lm):
    eng = _Engine(tiny_lm)
    assert eng.StepOnce() == 0
    assert eng.trace.Steps() == []

  def test_trace_off_still_steps(self, tiny_lm):
    eng = _Engine(tiny_lm, trace=False)
    out = eng.RunBatch(np.ones((1, 3), np.int32), np.array([3]), 2)
    assert out.shape == (1, 2) and eng.trace is None

  def test_spec_engine_records_the_draft_phase(self, tiny_lm):
    from lingvo_tpu.serving import spec_decode
    eng = _Engine(tiny_lm, spec=spec_decode.SelfDraft(num_layers=1, k=2))
    eng.RunBatch(np.ones((2, 5), np.int32), np.array([5, 4]), 6)
    steps = eng.trace.Steps()
    assert len(steps) == eng.Stats()["steps"]
    assert any(s.Phases()["draft"] > 0 for s in steps)


# -- export -------------------------------------------------------------------


class TestEngineLoopRow:

  def test_chrome_trace_holds_the_engine_loop_row(self, stepped_engine):
    trace = _CheckChromeTrace(stepped_engine.trace.ChromeTrace())
    names = {e["args"]["name"]: e["tid"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "engine loop" in names
    row = [e for e in trace["traceEvents"]
           if e.get("tid") == names["engine loop"] and e["ph"] in "BE"]
    n = len(stepped_engine.trace.Steps())
    assert sum(e["ph"] == "B" and e["name"].startswith("step ")
               for e in row) == n
    begun = {e["name"] for e in row if e["ph"] == "B"}
    assert {"admit", "build", "h2d", "dispatch", "device_wait",
            "commit"} <= begun

  def test_per_step_beside_per_request(self, stepped_engine):
    trace = stepped_engine.trace.ChromeTrace()
    assert len(trace["perRequest"]) == 4
    per_step = trace["perStep"]
    assert len(per_step) == len(stepped_engine.trace.Steps())
    assert set(per_step[0]["phases_s"]) == set(trace_lib.STEP_PHASES)
    json.dumps(per_step)

  def test_no_steps_no_row(self):
    rec = trace_lib.TraceRecorder(clock=_FakeClock())
    rec.Submit(1, 2, 2)
    trace = rec.ChromeTrace()
    assert trace["perStep"] == []
    assert not any(e.get("args", {}).get("name") == "engine loop"
                   for e in trace["traceEvents"])

  def test_trace_report_prints_the_phase_table(self, stepped_engine,
                                               tmp_path):
    path = str(tmp_path / "t.json")
    stepped_engine.trace.Export(path)
    text = trace_report.Report(trace_report.LoadTrace(path))
    assert "engine steps" in text
    for phase in ("loop",) + trace_lib.STEP_PHASES:
      assert phase in text
    summary = trace_report.StepSummary(trace_report.LoadTrace(path))
    assert summary["steps"] == len(stepped_engine.trace.Steps())
    assert summary["host_ms"]["p50"] > 0

  def test_trace_report_without_steps_prints_no_phase_table(self, tmp_path):
    rec = trace_lib.TraceRecorder(clock=_FakeClock())
    rec.Submit(1, 2, 2)
    path = str(tmp_path / "t.json")
    rec.Export(path)
    assert "engine steps" not in trace_report.Report(
        trace_report.LoadTrace(path))


# -- Live() -------------------------------------------------------------------


class TestLive:

  def test_live_lists_a_recorder_and_forgets_it_once_collected(self):
    rec = trace_lib.TraceRecorder()
    assert any(r is rec for r in trace_lib.Live())
    ident = id(rec)
    del rec
    gc.collect()
    assert all(id(r) != ident for r in trace_lib.Live())

  def test_the_newest_stepper_outlives_its_owner(self):
    a = trace_lib.TraceRecorder()
    a.StepDone(1, 0.0, 0.0, _Segments(commit=0.1))
    ident = id(a)
    del a
    gc.collect()
    kept = [r for r in trace_lib.Live() if id(r) == ident]
    assert len(kept) == 1 and kept[0].Steps()[0].step == 1
    del kept
    b = trace_lib.TraceRecorder()
    b.StepDone(1, 0.0, 0.0, _Segments(commit=0.1))   # replaces the one held
    gc.collect()
    assert all(id(r) != ident for r in trace_lib.Live())
    assert any(r is b for r in trace_lib.Live())

  def test_an_engines_recorder_is_live(self, stepped_engine):
    assert any(r is stepped_engine.trace for r in trace_lib.Live())


# -- spans on the profiler's clock --------------------------------------------


def _HostSpans(logdir):
  """{name: [(thread line index, stats dict), ...]} of the lingvo/ events
  on the host plane of the newest trace under logdir."""
  paths = sorted(glob.glob(os.path.join(
      logdir, "plugins", "profile", "*", "*.xplane.pb")))
  assert paths, f"no trace under {logdir}"
  pd = jax.profiler.ProfileData.from_file(paths[-1])
  out = {}
  for plane in pd.planes:
    if plane.name != "/host:CPU":
      continue
    for i, line in enumerate(plane.lines):
      for ev in line.events:
        if ev.name.startswith("lingvo/"):
          out.setdefault(ev.name, []).append(
              (i, dict(ev.stats), ev.start_ns, ev.duration_ns))
  return out


def _TrainProgram(logdir, **kw):
  from lingvo_tpu import model_registry
  from lingvo_tpu.runners import program as program_lib
  import lingvo_tpu.models.all_params  # noqa: F401
  mp = model_registry.GetParams(TRAIN_MODEL, "Train")
  mp.input.Set(batch_size=2, seq_len=32)
  mp.task.input = mp.input
  task = mp.task.Instantiate()
  task.FinalizePaths()
  train_p = program_lib.TrainProgram.Params().Set(
      task=mp.task, logdir=logdir, steps_per_loop=2,
      write_tensorboard=False, **kw)
  return task, program_lib.TrainProgram(train_p, task=task)


@pytest.fixture(scope="module")
def profiled(tiny_lm, tmp_path_factory):
  """Three engine steps and two train loops under one profiler trace."""
  logdir = str(tmp_path_factory.mktemp("spans"))
  eng = _Engine(tiny_lm)
  eng.RunBatch(np.ones((1, 3), np.int32), np.array([3]), 2)     # compiles
  task, prog = _TrainProgram(os.path.join(logdir, "train"))
  state = task.CreateTrainState(jax.random.PRNGKey(0))
  state, _ = prog.Run(state)                                     # compiles
  prog.Flush()
  first_step = eng.Stats()["steps"] + 1
  jax.profiler.start_trace(logdir)
  try:
    eng.RunBatch(np.ones((1, 3), np.int32), np.array([3]), 3)
    for _ in range(2):
      state, _ = prog.Run(state)
    prog.Flush()
  finally:
    jax.profiler.stop_trace()
  prog.Shutdown()
  return _HostSpans(logdir), first_step, eng


class TestSpansInAProfilerTrace:

  @pytest.mark.parametrize("name", SERVE_SPANS)
  def test_engine_spans_land_on_the_host_plane(self, profiled, name):
    spans, _, eng = profiled
    assert name in spans, sorted(spans)
    steps = eng.Stats()["steps"]
    assert len(spans["lingvo/serve/step"]) >= 3
    assert len(spans[name]) >= 3 and steps >= 3

  def test_step_span_carries_its_arguments(self, profiled):
    spans, first_step, _ = profiled
    # an iteration that launched nothing (here the one that retires the
    # last step in flight) has a step span with no arguments
    stats = [s for _, s, _, _ in spans["lingvo/serve/step"] if "step" in s]
    assert len(stats) == len(spans["lingvo/serve/step"]) - 1
    assert [s["step"] for s in stats][:3] == [
        first_step, first_step + 1, first_step + 2]
    for s in stats:
      assert {"valid_tokens", "prefill_tokens", "rows"} <= set(s)
    assert stats[0]["prefill_tokens"] == 3 and stats[0]["rows"] == 1

  def test_segments_lie_inside_their_step(self, profiled):
    spans, _, _ = profiled
    steps = [(t, s, s + d) for t, _, s, d in spans["lingvo/serve/step"]]
    for name in SERVE_SPANS[1:]:
      for t, _, s, d in spans[name]:
        assert any(t == st and a <= s and s + d <= b + 1
                   for st, a, b in steps), name

  @pytest.mark.parametrize("name", TRAIN_SPANS)
  def test_train_spans_land_on_the_host_plane(self, profiled, name):
    spans, _, _ = profiled
    assert name in spans, sorted(spans)

  def test_train_loop_span_carries_its_number(self, profiled):
    spans, _, _ = profiled
    loops = [s["loop"] for _, s, _, _ in spans["lingvo/train/loop"]]
    assert loops == [2, 3]

  def test_three_threads_on_the_train_side(self, profiled):
    spans, _, _ = profiled
    main = {t for t, *_ in spans["lingvo/train/loop"]}
    worker = {t for t, *_ in spans["lingvo/train/finalize"]}
    producer = {t for t, *_ in spans["lingvo/infeed/produce"]}
    assert len(main) == len(worker) == len(producer) == 1
    assert len(main | worker | producer) == 3


class TestTrainHostOverhead:

  @pytest.mark.parametrize("kw", [dict(async_infeed=False),
                                  dict(async_infeed=True)],
                           ids=["sync", "async"])
  def test_host_overhead_is_a_short_duration(self, tmp_path, kw):
    task, prog = _TrainProgram(str(tmp_path), **kw)
    state = task.CreateTrainState(jax.random.PRNGKey(0))
    results = []
    for _ in range(3):
      state, res = prog.Run(state)
      results.append(res)
    prog.Shutdown()
    for r in results:
      assert 0.0 <= r["infeed_wait_s"] <= r["host_overhead_s"] < 60.0


# -- scope names on device ops ------------------------------------------------


@pytest.fixture(scope="module")
def train_hlo(tmp_path_factory):
  task, prog = _TrainProgram(str(tmp_path_factory.mktemp("hlo")))
  state = task.CreateTrainState(jax.random.PRNGKey(0))
  batch = prog._PutBatch(prog.input_generator.GetPreprocessedInputBatch())
  return prog._GetStepFn(state).lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def ragged_hlo(tiny_lm):
  eng = _Engine(tiny_lm)
  eng.RunBatch(np.ones((1, 3), np.int32), np.array([3]), 2)
  compiled = eng._compile_log._programs["ragged"][0]
  assert compiled is not None, eng.Stats()["compile"]
  return compiled.as_text()


def _OpNames(hlo_text):
  import re
  return re.findall(r'op_name="([^"]+)"', hlo_text)


class TestScopeNames:

  @pytest.mark.parametrize("scope", TRAIN_SCOPES)
  def test_train_step_ops_carry_the_scope(self, train_hlo, scope):
    names = _OpNames(train_hlo)
    assert any(f"/{scope}/" in n or f"({scope})" in n for n in names), (
        scope, names[:20])

  @pytest.mark.parametrize("scope", SERVE_SCOPES)
  def test_ragged_step_ops_carry_the_scope(self, ragged_hlo, scope):
    names = _OpNames(ragged_hlo)
    assert any(f"/{scope}/" in n for n in names), (scope, names[:20])

  def test_most_train_ops_lie_under_some_scope(self, train_hlo):
    names = _OpNames(train_hlo)
    scoped = [n for n in names
              if any(f"/{s}/" in n or f"({s})" in n for s in TRAIN_SCOPES)]
    assert len(scoped) > 0.6 * len(names), (len(scoped), len(names))

  def test_scopes_change_no_number(self, tiny_lm):
    """Metadata only: the scoped step emits the greedy tokens of the dense
    per-request rollout, which runs under no scope."""
    task, theta = tiny_lm
    out = _Engine(tiny_lm).RunBatch(np.ones((2, 4), np.int32),
                                    np.array([4, 3]), 4)
    for row, n in zip(out, (4, 3)):
      assert list(row) == _GreedyRef(task, theta, [1] * n, 4)


# -- the one declared tree (observe.schema.DEVICE_SCOPES) ---------------------

_PROGRAMS = ("dense_train", "dense", "smallthinker", "phi4flash",
             "nemotron_h", "brumby", "mistral4", "trinity", "lfm2",
             "pallas_attend")


def _Avals(tree):
  """Shapes for `lower`: a dispatched step's states were donated."""
  return jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
      if hasattr(x, "shape") else x, tree)


def _Lowered(program, tmp):
  """The program's jitted step, lowered on what it is really called with:
  the tiny dense train step, the ragged step of a tiny engine of each family
  (tests/test_head_cols), and the Pallas lowerings of the ragged attend, the
  differential attend and the page write in interpret mode (a CPU engine
  takes their XLA twins)."""
  if program == "dense_train":
    task, prog = _TrainProgram(tmp)
    state = task.CreateTrainState(jax.random.PRNGKey(0))
    batch = prog._PutBatch(prog.input_generator.GetPreprocessedInputBatch())
    return prog._GetStepFn(state).lower(state, batch)
  if program == "pallas_attend":
    from lingvo_tpu.core import ragged as ragged_lib
    from lingvo_tpu.ops import diff_attend
    from lingvo_tpu.ops import ragged_block_attend
    q = jnp.zeros((8, 1, 8), jnp.float32)
    pool = jnp.zeros((7, 8, 1, 8), jnp.float32)
    tables = jnp.arange(6, dtype=jnp.int32).reshape(3, 2)
    row_of = jnp.asarray([0, 1, 1, 1, 2, 2, 2, 0], jnp.int32)
    q_end = jnp.asarray([9, 5, 6, 7, 12, 13, 14, 0], jnp.int32)
    rows = jax.tree_util.tree_map(
        jnp.asarray, ragged_lib.BuildRaggedRows([1, 3, 3], [8, 4, 11], 8, 4))
    wide = jnp.zeros((7, 8, 2, 64), jnp.float32)
    new = jnp.zeros((8, 2, 64), jnp.float32)

    dq = jnp.zeros((8, 8, 8), jnp.float32)
    dpool = jnp.zeros((7, 8, 4, 8), jnp.float32)

    def _All(q, pool, wide, new, dq, dpool):
      with observe.Scope("atten"):
        with observe.Scope("ragged_attend"):
          ctx = ragged_block_attend.RaggedAttend(
              q, pool, pool, tables, row_of, q_end, page_size=8,
              lowering="pallas", interpret=True)
        with observe.Scope("diff_attend"):
          diff = diff_attend.DiffAttend(
              dq, dpool, dpool, tables, row_of, q_end, 0.3, page_size=8,
              lowering="pallas", interpret=True)
        with observe.Scope("kv_write"):
          return ctx, diff, diff_attend.WritePages(
              wide, wide, new, new, tables, rows, lowering="pallas",
              interpret=True)

    return jax.jit(_All).lower(q, pool, wide, new, dq, dpool)
  from lingvo_tpu.serving import engine as engine_lib
  from tests.test_head_cols import _FAMILIES, _NEWER_FAMILIES
  task, theta = {**_FAMILIES, **_NEWER_FAMILIES}[program](jnp.float32)
  eng = engine_lib.ServingLoop(
      task, theta, page_size=8, num_pages=48, max_batch=4, max_seq_len=128,
      prefill_token_budget=8)
  seen = []
  inner = eng._compile_log.Call

  def _Call(name, fn, *args):
    if name == "ragged":
      seen.append((fn, _Avals(args)))
    return inner(name, fn, *args)

  eng._compile_log.Call = _Call
  eng.Submit([5, 9, 2], 6, eos_id=None, seed=11)
  eng.StepOnce()
  fn, args = seen[-1]
  return fn.lower(*args)


@pytest.fixture(scope="module")
def scoped_programs(tmp_path_factory):
  """{program: (lowered text, op_names of the compiled HLO)}, scopes on."""
  out = {}
  for program in _PROGRAMS:
    lowered = _Lowered(program, str(tmp_path_factory.mktemp(program)))
    out[program] = (lowered.as_text(), _OpNames(lowered.compile().as_text()))
  return out


class TestDeclaredTree:

  @pytest.mark.parametrize("scope", list(observe_schema.DEVICE_SCOPES))
  def test_every_declared_scope_is_on_some_compiled_op(self, scoped_programs,
                                                       scope):
    names = [n for _, ns in scoped_programs.values() for n in ns]
    assert any(f"/{scope}/" in n or f"({scope})" in n for n in names), scope

  @pytest.mark.parametrize("scope", list(observe_schema.DEVICE_SCOPES))
  def test_a_scopes_parent_is_declared(self, scope):
    parent, holds = observe_schema.DEVICE_SCOPES[scope]
    assert parent is None or parent in observe_schema.DEVICE_SCOPES
    assert holds and parent != scope

  @pytest.mark.parametrize("scope", list(observe_schema.DEVICE_SCOPES))
  def test_the_docs_table_is_the_tree(self, scope):
    """docs/observability.md, "Device scopes": one row a scope, with the
    tree's parent and a reader."""
    docs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "observability.md")
    with open(docs) as f:
      rows = [ln.split("|") for ln in f if ln.startswith(f"| `{scope}` ")]
    assert len(rows) == 1, scope
    parent = observe_schema.DEVICE_SCOPES[scope][0]
    assert rows[0][2].strip() == (f"`{parent}`" if parent else "")
    assert rows[0][3].strip() and rows[0][5].strip()

  def test_an_undeclared_scope_raises(self):
    with pytest.raises(KeyError, match="nope"):
      observe.Scope("nope")
    with observe.Scope("atten"):
      pass

  def test_the_program_enters_scopes_through_the_tree_alone(self):
    """No op can carry a scope of the program's that is not declared: the
    one `jax.named_scope` call under lingvo_tpu/ is observe.Scope's."""
    root = os.path.dirname(os.path.abspath(observe.__file__))
    pkg = os.path.dirname(root)
    hits = []
    for d, _, files in os.walk(pkg):
      for f in files:
        if f.endswith(".py"):
          path = os.path.join(d, f)
          with open(path) as fh:
            if "named_scope(" in fh.read():
              hits.append(os.path.relpath(path, pkg))
    assert hits == [os.path.join("observe", "schema.py")], hits

  @pytest.mark.parametrize("program", _PROGRAMS)
  def test_scopes_are_metadata_only(self, scoped_programs, program,
                                    monkeypatch, tmp_path):
    """The lowered program without its metadata (`as_text()` prints no
    locations) is byte for byte what it is with every scope a null context:
    no shape, number, operand or instruction depends on a scope."""
    import contextlib
    monkeypatch.setattr(observe, "Scope",
                        lambda name: contextlib.nullcontext())
    bare = _Lowered(program, str(tmp_path))
    assert not any(
        s in n for n in _OpNames(bare.as_text(debug_info=True))
        for s in ("qkv_proj", "kv_layout", "ssm_conv", "moe_dispatch"))
    assert bare.as_text() == scoped_programs[program][0]
