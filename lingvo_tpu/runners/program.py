"""Programs: jit-compiled train/eval/decode units + their schedule.

Re-designs `lingvo/core/program.py` (2.9k LoC). The reference builds TF graphs
with on-device `steps_per_loop` repeats, infeed/outfeed queues and
`tpu.split_compile_and_shard`; here each program owns a jit'd step function
(optionally pjit over a mesh), a host loop that feeds device_put batches, and
weighted metric accumulators (ref `TpuEvalMetrics`). `SimpleProgramSchedule`
(ref `program.py:2329`) time-slices train/eval/decode on the same chips.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import json
import os
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from lingvo_tpu import observe
from lingvo_tpu.observe import goodput as goodput_lib
from lingvo_tpu.observe import profile as profile_lib
from lingvo_tpu.core import base_layer
from lingvo_tpu.core import hyperparams
from lingvo_tpu.core import metrics as metrics_lib
from lingvo_tpu.core import py_utils
from lingvo_tpu.core.nested_map import NestedMap


# Set-up under the program's own names (observe.profile's start-up record,
# each phase a `lingvo/setup/<phase>` span too): `build`, a program's
# construction (and `infeed`, the producer's start, where it runs);
# `compile_step`, Compile(), which holds the named programs `step` / `loop`;
# and, in the record only, `first_steps`: from Compile()'s return (the first
# Run's start where none came first) to the first loop's completion.
_STARTUP = profile_lib.Startup()


def _StateDonation() -> tuple:
  """donate_argnums for the train-state argument: donation only buys the
  in-place update on accelerators, and the CPU backend warns 'Some donated
  buffers were not usable' for every non-aliasable leaf (same gating as
  gshard_decode's decode-state donation)."""
  return (0,) if jax.default_backend() != "cpu" else ()


def _ScalarSummaryPairs(train_out: NestedMap) -> dict:
  """In-loop `tpu_summary.scalar` values as accumulable (value, 1.0) pairs.

  Scalars recorded inside FProp (ref tpu_summary.py) ride the same
  fixed-shape metric accumulators as stats. Non-scalar tensor summaries are
  skipped: in on_device_loop mode they never leave the scan; in per-step
  mode a host can read the last step's from train_out.summaries.
  """
  out = {}
  for k, v in train_out.get("summaries", NestedMap()).FlattenItems():
    if getattr(v, "ndim", None) == 0:
      out[f"summary_{k}"] = (v, 1.0)
  return out


class BaseProgram:
  """Shared program machinery (ref BaseProgram, program.py:75)."""

  @classmethod
  def Params(cls):
    p = hyperparams.InstantiableParams(cls)
    p.Define("name", "", "Program name (logdir subdir).")
    p.Define("task", None, "Task params.")
    p.Define("logdir", "", "Run log directory.")
    p.Define("steps_per_loop", 100, "Steps per Run() invocation.")
    p.Define("dataset_name", "Train", "Which dataset this program consumes.")
    p.Define("mesh", None, "Optional jax Mesh for sharded execution.")
    p.Define("input_sharding", None, "PartitionSpec for input batches.")
    p.Define("state_sharding_fn", None,
             "fn(state_template)->sharding pytree (pjit).")
    p.Define("write_tensorboard", True,
             "Write TensorBoard event files next to the JSONL summaries.")
    p.Define("profiler_capture_every_n_runs", 0,
             "If >0, wrap every Nth Run() in a jax.profiler trace written "
             "to <program_dir>/plugins/profile (SURVEY §5: profiling is "
             "first-class; view in XProf/TensorBoard).")
    p.Define("async_infeed", True,
             "Overlap host batch prep (+ H2D placement) with device compute "
             "via a background producer thread (runners/infeed.py), and — "
             "for TrainProgram — defer the post-loop metric fetch + summary "
             "writes to a background worker. False restores the exact "
             "fully-synchronous legacy control flow (kill switch).")
    p.Define("infeed_depth", 2,
             "Bounded infeed queue depth: stacked loop batches for "
             "on_device_loop, single batches otherwise.")
    p.Define("infeed_place_on_device", None,
             "Where H2D placement happens under async_infeed: True = on the "
             "producer thread (transfer overlaps compute too), False = "
             "numpy in the thread, placement on the consumer, None = auto "
             "(True single-process; multi-process, producer-side iff the "
             "one-shot off-main-thread safety probe of "
             "make_array_from_process_local_data passes — "
             "infeed.ProbeProducerPlacement — else the numpy+consumer "
             "fallback).")
    return p

  @profile_lib.InPhase("build")
  def __init__(self, params, task=None, input_generator=None):
    self.p = params.Copy()
    self._task = task if task is not None else params.task.Instantiate()
    self._input = input_generator
    self._program_dir = os.path.join(self.p.logdir,
                                     self.p.name or type(self).__name__)
    os.makedirs(self._program_dir, exist_ok=True)
    self._step_fn = None
    self._loop_fn = None
    self._run_count = 0
    self._loops_run = 0   # the `loop` argument of the lingvo/train/loop span
    self._profiling_run = False
    # async-infeed machinery (runners/infeed.py), created lazily on the
    # first async Run so Compile() can pull warm-up batches without racing
    # the producer thread for the input stream
    self._infeed = None
    self._telemetry = None
    self._pending_telemetry = None
    self._pending_consumed = True  # was the pending result already returned?
    # k-deep dispatch window (pipeline_depth >= 1): unresolved telemetry
    # futures, oldest first. The legacy lag-1 fields above stay the
    # pipeline_depth=0 kill-switch path, byte-for-byte.
    self._pending: collections.deque = collections.deque()
    self._last_result: dict | None = None
    self._last_result_consumed = True
    # completed-but-unpolled results for the executor's telemetry-driven
    # cadence decisions (NaN-stop etc.); every result that resolves through
    # the window lands here exactly once until PollCompletedResults drains it
    self._completed_unpolled: list = []
    # executor hook fired when one dispatched loop's device work +
    # telemetry completes (watchdog heartbeat); may run on the worker thread
    self._loop_done_cb: Callable[[], None] | None = None
    # host-side step tracking: after a successful loop the step is
    # deterministic (start + loops x steps_per_loop); None = unseeded (the
    # first pipelined Run seeds it from the concrete restored state, a
    # fence that already exists)
    self._host_step: int | None = None
    # pipelined goodput attribution marks (completion-interval based;
    # see _AttributePipelinedLoop): None = not yet in a pipelined window
    self._pipe_t_mark: float | None = None
    self._pipe_wait_mark = 0.0
    self._pipe_compile_mark = 0.0
    self._pipe_thread: int | None = None   # the thread that dispatches
    # the start-up record's `first_steps` phase (TrainProgram): None until
    # Compile() returns or the first Run starts, open until the first
    # loop's completion, False from then on
    self._first_steps = None
    self._run_unit = None   # the Run open on the dispatching thread
    self._named_unbuilt: set = set()   # _Unbuilt's labels, each given once
    from lingvo_tpu.core import summary_utils
    self._tb = summary_utils.SummaryWriter(
        self._program_dir, enabled=self.p.write_tensorboard)
    # train-side observability publishes to the process-global registry
    # (one trainer per process; serving engines use per-instance ones)
    self.metrics = observe.Default()
    # all programs feed ONE process-global goodput tracker, so the
    # buckets partition a single wall clock (observe/goodput.py)
    self._goodput = goodput_lib.Get()
    self._rate_tracker = summary_utils.StepRateTracker(
        registry=self.metrics, name=self.p.name or "train")
    # {program_name: compile record} — wall time + XLA memory plan of each
    # AOT Compile() (observe.CompileInfo); also published as train gauges
    self.compile_records: dict = {}
    # live generator-side counters (SequenceBatcher stats, prefetch depth);
    # lazy via self._input so the snapshot never instantiates the generator
    self.metrics.SectionFn(
        f"infeed/{self.p.name or type(self).__name__}_input",
        lambda: (self._InputStatsOf(self._input)
                 if self._input is not None else {}))

  @property
  def task(self):
    return self._task

  @property
  def input_generator(self):
    if self._input is None:
      ip = self.p.task.input
      if ip is None:
        raise ValueError(f"Program {self.p.name}: no input params")
      from lingvo_tpu.core import input_policy
      self._input = input_policy.Instantiate(ip)
    return self._input

  @staticmethod
  def _PlaceLocalShard(x, sharding, batch_dim: int = 0):
    """One leaf of a host-local batch -> device array under `sharding`.

    Multi-process: this HOST's rows (ref InfeedContextScope per-host
    sharding) concatenate with the other processes' along `batch_dim`
    into one global array.
    """
    if jax.process_count() > 1:
      x = np.asarray(x)
      gshape = list(x.shape)
      gshape[batch_dim] *= jax.process_count()
      return jax.make_array_from_process_local_data(
          sharding, x, tuple(gshape))
    return jax.device_put(jnp.asarray(x), sharding)

  def _PutBatch(self, batch: NestedMap) -> NestedMap:
    """Host batch -> device array(s), honoring the input sharding."""
    if self.p.mesh is not None and self.p.input_sharding is not None:
      sharding = jax.sharding.NamedSharding(self.p.mesh,
                                            self.p.input_sharding)
      return batch.Transform(
          lambda x: self._PlaceLocalShard(x, sharding))
    return batch.Transform(jnp.asarray)

  def _MeshScope(self):
    """Ambient-mesh context so sharding hints inside FProps apply."""
    import contextlib
    if self.p.mesh is not None:
      from lingvo_tpu.parallel import mesh as mesh_lib
      return mesh_lib.MeshContext(self.p.mesh)
    return contextlib.nullcontext()

  @profile_lib.InPhase("compile_step")
  def Compile(self, state: NestedMap) -> None:
    """Ahead-of-time compile with a real batch (ref Compile:355)."""
    batch = self._PutBatch(self.input_generator.GetPreprocessedInputBatch())
    fn = self._GetStepFn(state)
    if hasattr(fn, "lower"):
      with self._MeshScope():
        self._RecordCompile("step", fn, state, batch)

  def _RecordCompile(self, name: str, fn, *args) -> None:
    """AOT-compiles `fn(*args)` once, recording wall time + the XLA memory
    plan into `self.compile_records[name]` and the registry (ISSUE 12
    pillar 3: per-compiled-program records for train/eval programs).
    Dispatch behavior is unchanged: like the previous Compile(), the
    executable is discarded and Run keeps calling the jit wrapper."""
    # the record is the named program's row in the start-up record, which
    # stamps `compile_wall_s` and what it was made of. Exclude the
    # listener-attributed compile seconds so the AOT window's remainder
    # (lowering glue) is all that lands here extra
    rec = {"name": name}
    with self._goodput.TrackExcludingCompile("compile"), \
        _STARTUP.Program(self._ProgramLabel(name), rec):
      compiled = fn.lower(*args).compile()
    rec.update(observe.CompileInfo(compiled))
    from lingvo_tpu.core import computation_cost
    try:
      flops = float(computation_cost.CostAnalysisOf(compiled).get(
          "flops", 0.0))
    except Exception:  # noqa: BLE001 - cost analysis is backend-optional
      flops = 0.0
    if flops > 0:
      rec["flops"] = flops
    self.compile_records[name] = rec
    ns = self.p.name or type(self).__name__
    self.metrics.Gauge(
        f"{ns}/compile/{name}_wall_s").Set(rec["compile_wall_s"])
    if "temp_bytes" in rec:
      self.metrics.Gauge(
          f"{ns}/compile/{name}_temp_bytes").Set(rec["temp_bytes"])
    self._OnCompileRecord(name, rec)

  def _OnCompileRecord(self, name: str, rec: dict) -> None:
    """Subclass hook after every AOT compile record (TrainProgram uses it
    to derive flops/step and publish `train/mfu`)."""

  def _ProgramLabel(self, name: str, what: str = "compile") -> str:
    """The named program `name` ('step', 'loop') as the start-up record
    labels it: `<program>/compile/<name>`, as the registry's gauges
    (`<program>/flops/<name>`: TrainProgram._Unbuilt)."""
    return f"{self.p.name or type(self).__name__}/{what}/{name}"

  def _GetStepFn(self, state: NestedMap | None = None):
    raise NotImplementedError

  def Run(self, state: NestedMap) -> tuple[NestedMap, dict[str, float]]:
    raise NotImplementedError

  def SaveProgramState(self) -> dict:
    return {}

  def LoadProgramState(self, blob: dict) -> None:
    pass

  def WriteSummaries(self, step: int, values: dict[str, float]) -> None:
    self._PublishRunMetrics(values)  # every process: registry is local
    if jax.process_index() != 0:
      return  # one writer per logdir (ref cluster.add_summary job gating)
    path = os.path.join(self._program_dir, "summaries.jsonl")
    with open(path, "a") as f:
      f.write(json.dumps({"step": step, **values}) + "\n")
    self._tb.Scalars(values, step)
    self._tb.Flush()

  def _PublishRunMetrics(self, values: dict) -> None:
    """Mirrors a Run's result dict into the process registry as gauges.

    WriteSummaries is the single result sink for every program kind, so
    hooking here covers train/eval/decode/input-benchmark uniformly.
    Namespacing: input-pipeline keys (`input_*`, `infeed_*`) land under
    `infeed/*` (the schema's pipeline namespace); everything else under
    `<program name>/*`. Non-numeric values are skipped — they belong to
    the JSONL record, not the metric surface."""
    ns = self.p.name or type(self).__name__
    for k, v in values.items():
      if isinstance(v, bool) or not isinstance(v, (int, float)):
        continue
      if k.startswith("input_"):
        name = f"infeed/{k}"
      elif k.startswith("infeed_"):
        name = f"infeed/{ns}_{k[len('infeed_'):]}"
      else:
        name = f"{ns}/{k}"
      self.metrics.Gauge(name).Set(v)

  def _ProfilerScope(self):
    """jax.profiler trace around every Nth Run (program option), via
    observe.ProfileWindow — same `<program_dir>/plugins/profile/<ts>`
    layout jax.profiler.trace wrote, but degrades to a no-op instead of
    raising on backends without profiler support."""
    import contextlib
    n = self.p.profiler_capture_every_n_runs
    self._run_count += 1
    self._profiling_run = n > 0 and self._run_count % n == 0
    if self._profiling_run:
      return observe.ProfileWindow(self._program_dir)
    return contextlib.nullcontext()

  # -- async infeed / deferred telemetry lifecycle ---------------------------

  def _PlaceInProducer(self) -> bool:
    """Auto policy for where H2D placement runs (see infeed_place_on_device):
    single-process always places on the producer; multi-process asks the
    one-shot `make_array_from_process_local_data` safety probe and falls
    back to numpy-in-thread + consumer placement when it fails."""
    if self.p.infeed_place_on_device is not None:
      return bool(self.p.infeed_place_on_device)
    if jax.process_count() == 1:
      return True
    from lingvo_tpu.runners import infeed as infeed_lib
    return infeed_lib.ProbeProducerPlacement()

  @staticmethod
  def _InputStatsOf(gen) -> dict:
    """Generator-side counters (SequenceBatcher stats, prefetch depth) for
    the train summaries; {} when the generator doesn't expose them."""
    fn = getattr(gen, "InputStats", None)
    if not callable(fn):
      return {}
    try:
      return dict(fn())
    except Exception:  # noqa: BLE001 - stats must never kill a train loop
      return {}

  def SetLoopDoneCallback(self, cb: Callable[[], None] | None) -> None:
    """Executor hook: `cb` fires each time one dispatched loop's device
    work + telemetry completes (on the telemetry worker thread for deferred
    loops, inline otherwise). The executor wires the stall watchdog's
    Beat() here, so liveness tracks device COMPLETION, not host dispatch —
    a hung device behind a free-running pipelined host stops beating."""
    self._loop_done_cb = cb

  def _NotifyLoopDone(self) -> None:
    cb = self._loop_done_cb
    if cb is not None:
      try:
        cb()
      except BaseException:  # noqa: BLE001 - liveness must not kill the loop
        pass

  def SyncHostStep(self, step: int) -> None:
    """Seeds host-side step tracking at a device fence (restore, recovery).
    Between fences the pipelined paths advance the step arithmetically
    instead of fetching `state.step` from the device."""
    self._host_step = int(step)

  def _PopPending(self) -> dict:
    """Resolves the OLDEST pending loop (blocking); its result becomes the
    newest completed result and joins the unpolled cadence stream."""
    res = self._pending.popleft().result()[1]
    self._last_result = res
    self._last_result_consumed = False
    self._completed_unpolled.append(res)
    return res

  def PollCompletedResults(self) -> list:
    """Drains (without blocking) every result that completed since the last
    poll — the executor's telemetry-driven cadence stream. Each result
    appears exactly once; staleness is bounded by the dispatch window
    (<= pipeline_depth unresolved loops at any Run exit)."""
    while self._pending and self._pending[0].done():
      self._PopPending()
    out, self._completed_unpolled = self._completed_unpolled, []
    return out

  def PendingLoops(self) -> int:
    """Unresolved dispatched loops (k-deep window + the legacy lag-1 slot)."""
    return len(self._pending) + (1 if self._pending_telemetry is not None
                                 else 0)

  def Flush(self):
    """Waits for ALL deferred telemetry and flushes the TB writer; returns
    the newest completed result if no Run handed it out yet, else None.
    Called by schedules at program boundaries and by the executor at
    decision boundaries (eval, save, stop) and before the final checkpoint,
    so summaries land in order and the lagged tail result still reaches
    NaN-stop/metrics. No-op for fully-synchronous programs."""
    out = None
    if self._pending_telemetry is not None:   # legacy lag-1 window
      res = self._pending_telemetry.result()[1]
      if not self._pending_consumed:
        out = res
      self._pending_telemetry = None
      self._pending_consumed = True
    while self._pending:                      # k-deep window
      self._PopPending()
    if not self._last_result_consumed:
      out = self._last_result
      self._last_result_consumed = True
    self._tb.Flush()
    return out

  def RecoverFromFailure(self) -> None:
    """Executor retry hook: drain pending telemetry (swallowing the error
    already being handled upstream) and restart an errored infeed producer
    so the retried Run pulls fresh batches."""
    fut, self._pending_telemetry = self._pending_telemetry, None
    self._pending_consumed = True
    if fut is not None:
      try:
        fut.result()
      except BaseException:  # noqa: BLE001
        pass
    while self._pending:
      try:
        self._pending.popleft().result()
      except BaseException:  # noqa: BLE001
        pass
    # results straddling the failure are unreliable; the restore that
    # follows re-seeds the host step and the goodput interval marks
    self._last_result = None
    self._last_result_consumed = True
    self._completed_unpolled = []
    self._host_step = None
    self._pipe_t_mark = None
    if self._infeed is not None and not self._infeed.healthy:
      self._infeed.Reset()

  def Shutdown(self) -> None:
    """Clean teardown between programs / at executor exit: best-effort
    telemetry flush, then stop the producer thread and the worker. The
    program stays usable — the next Run lazily restarts both (note any
    prefetched-but-unconsumed batches are discarded at Stop)."""
    try:
      self.Flush()
    except BaseException:  # noqa: BLE001 - already surfaced via Run/Flush
      pass
    if self._infeed is not None:
      self._infeed.Stop()
      self._infeed = None
    if self._telemetry is not None:
      self._telemetry.Shutdown()
      self._telemetry = None


class TrainProgram(BaseProgram):
  """steps_per_loop training steps per Run (ref TrainProgram:441).

  The jit'd unit is a single TrainStep; the host loop feeds batches and
  donates the state buffers so theta/opt-state update in place on device.
  """

  # flops per optimizer step, from the step executable's XLA cost
  # analysis; set once (AOT compile record or lazy first-Run lower())
  _flops_per_step: float | None = None

  @classmethod
  def Params(cls):
    p = super().Params()
    p.name = "train"
    p.Define("base_step_seed", 1234, "Base PRNG seed for step seeds.")
    p.Define("on_device_loop", False,
             "Run all steps_per_loop inside ONE jit call (lax.scan over a "
             "stacked batch) — one host round-trip per loop instead of per "
             "step (ref tpu_training_loop.repeat, program.py:601-609). The "
             "host prefetches steps_per_loop batches and stacks them.")
    p.Define("defer_telemetry", True,
             "Under async_infeed, run the post-loop metric device_get + "
             "summary writes on a background worker; Run returns the most "
             "recent COMPLETED loop's result (lags dispatch by <= 1 loop). "
             "False fetches synchronously after dispatch (infeed overlap "
             "only). Ignored when async_infeed is False.")
    p.Define("pipeline_depth", 2,
             "k-deep dispatch window under async_infeed + defer_telemetry: "
             "Run may leave up to this many loops' telemetry unresolved, "
             "so loop k+1 dispatches before loop k's metrics land and the "
             "returned result is stale by at most this many loops. Also "
             "switches to host-side step tracking (no device_get of "
             "state.step between fences). 0 = the exact legacy lag-1 "
             "behavior (kill switch; docs/pipelined_executor.md).")
    return p

  def _GetStepFn(self, state: NestedMap | None = None):
    if self._step_fn is None:
      key = jax.random.PRNGKey(self.p.base_step_seed)
      state_shardings = None
      if (self.p.mesh is not None and self.p.state_sharding_fn is not None and
          state is not None):
        state_shardings = self.p.state_sharding_fn(state)

      def _Step(state, batch):
        if state_shardings is not None:
          state = jax.lax.with_sharding_constraint(state, state_shardings)
        new_state, out = self._task.TrainStep(state, batch, key)
        if state_shardings is not None:
          new_state = jax.lax.with_sharding_constraint(new_state,
                                                       state_shardings)
        return new_state, out

      self._step_fn = jax.jit(_Step, donate_argnums=_StateDonation())
    return self._step_fn

  def Compile(self, state: NestedMap) -> None:
    try:
      if self.p.on_device_loop:
        self._CompileLoop(state)
      else:
        super().Compile(state)
    finally:
      self._OpenFirstSteps()

  @profile_lib.InPhase("compile_step")
  def _CompileLoop(self, state: NestedMap) -> None:
    # shapes only: tile ONE batch rather than consuming steps_per_loop
    # real batches from a possibly-finite stream
    batch = self.input_generator.GetPreprocessedInputBatch()
    stacked = batch.Transform(
        lambda x: jnp.broadcast_to(
            jnp.asarray(x)[None], (self.p.steps_per_loop,) + np.shape(x)))
    with self._MeshScope():
      self._RecordCompile("loop", self._GetLoopFn(state), state, stacked)

  def _GetLoopFn(self, state: NestedMap | None = None):
    """steps_per_loop TrainSteps as ONE jitted lax.scan over stacked batches
    (the reference's on-device training loop, program.py:601-609)."""
    if self._loop_fn is None:

      state_shardings = None
      if (self.p.mesh is not None and self.p.state_sharding_fn is not None
          and state is not None):
        state_shardings = self.p.state_sharding_fn(state)

      def _Loop(state, stacked_batches):
        key = jax.random.PRNGKey(self.p.base_step_seed)

        def _Body(carry, batch):
          state, acc, stats_acc = carry
          if state_shardings is not None:
            state = jax.lax.with_sharding_constraint(state, state_shardings)
          state, out = self._task.TrainStep(state, batch, key)
          if state_shardings is not None:
            state = jax.lax.with_sharding_constraint(state, state_shardings)
          acc = metrics_lib.AccumulateMetrics(acc, out.metrics)
          stats = NestedMap(
              {k: (v, 1.0) for k, v in out.stats.FlattenItems()})
          stats.update(_ScalarSummaryPairs(out))
          stats_acc = metrics_lib.AccumulateMetrics(stats_acc, stats)
          return (state, acc, stats_acc), ()

        # fixed-structure zero accumulators (scan carries can't grow)
        _, out_shape = jax.eval_shape(
            lambda s, b: self._task.TrainStep(s, b, key), state,
            jax.tree_util.tree_map(lambda x: x[0], stacked_batches))
        zeros = lambda m: NestedMap(
            {k: jnp.zeros((2,), jnp.float32) for k in m.keys()})
        acc0 = zeros(out_shape.metrics)
        stats0 = NestedMap({k: jnp.zeros((2,), jnp.float32)
                            for k, _ in out_shape.stats.FlattenItems()})
        stats0.update({k: jnp.zeros((2,), jnp.float32)
                       for k in _ScalarSummaryPairs(out_shape)})
        (state, acc, stats_acc), _ = jax.lax.scan(
            _Body, (state, acc0, stats0), stacked_batches)
        return state, acc, stats_acc

      self._loop_fn = jax.jit(_Loop, donate_argnums=_StateDonation())
    return self._loop_fn

  def _PutStackedBatch(self, stacked: NestedMap) -> NestedMap:
    """[steps_per_loop, ...]-stacked host batches -> device arrays. The
    stacked leading dim is the STEPS axis: keep it unsharded and shift the
    per-step batch spec right by one."""
    if self.p.mesh is not None and self.p.input_sharding is not None:
      spec = jax.sharding.PartitionSpec(None, *self.p.input_sharding)
      sharding = jax.sharding.NamedSharding(self.p.mesh, spec)
      return stacked.Transform(
          lambda x: self._PlaceLocalShard(x, sharding, batch_dim=1))
    return stacked.Transform(jnp.asarray)

  def _MakeTrainIter(self):
    """Host batch units in exactly the order the sync path consumes them:
    stacked loop batches for on_device_loop, single batches otherwise.
    Runs on the infeed producer thread (the only generator caller once
    async Run starts)."""
    p = self.p
    gen = self.input_generator
    if p.on_device_loop:
      while True:
        batches = []
        try:
          for _ in range(p.steps_per_loop):
            batches.append(gen.GetPreprocessedInputBatch())
        except StopIteration:
          return  # partial loop at stream end: dropped (sync path raises
                  # StopIteration mid-stack and loses the same batches)
        yield jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
    else:
      while True:
        try:
          batch = gen.GetPreprocessedInputBatch()
        except StopIteration:
          return
        yield batch

  def _GetInfeed(self):
    if self._infeed is None:
      from lingvo_tpu.runners import infeed as infeed_lib
      p = self.p
      place = self._PutStackedBatch if p.on_device_loop else self._PutBatch
      with _STARTUP.Phase("infeed"):
        self._infeed = infeed_lib.DeviceInfeed(
            self._MakeTrainIter, place_fn=place, depth=p.infeed_depth,
            place_in_producer=self._PlaceInProducer(),
            name=f"{p.name or 'train'}-infeed",
            stream_key=id(self.input_generator), registry=self.metrics)
    return self._infeed

  def _GetTelemetry(self):
    if self._telemetry is None:
      from lingvo_tpu.runners import infeed as infeed_lib
      self._telemetry = infeed_lib.DeferredTelemetry(
          name=f"{self.p.name or 'train'}-telemetry")
    return self._telemetry

  def _OnCompileRecord(self, name: str, rec: dict) -> None:
    """Derives flops/step from the AOT compile's cost analysis and wires
    the `train/mfu` lazy gauge ("loop" compiles cover steps_per_loop
    optimizer steps in one executable)."""
    flops = rec.get("flops", 0.0)
    if flops <= 0:
      return
    steps = self.p.steps_per_loop if name == "loop" else 1
    self._SetFlopsPerStep(flops / max(steps, 1))

  def _SetFlopsPerStep(self, flops_per_step: float) -> None:
    self._flops_per_step = flops_per_step
    goodput_lib.PublishMfu(
        self.metrics, flops_per_step,
        rate_gauge=f"train/{self.p.name or 'train'}_steps_per_second")

  def _MaybePublishMfu(self, fn, *args, steps: int = 1) -> None:
    """Lazy flops/step for runs without an AOT Compile(): one abstract
    `.lower().cost_analysis()` on the first Run — tracing only, never a
    second XLA compilation (jax >= 0.4.30 analyzes the lowered HLO)."""
    if self._flops_per_step is not None or not hasattr(fn, "lower"):
      return
    try:
      with self._Unbuilt("flops"):
        cost = fn.lower(*args).cost_analysis()
      if isinstance(cost, (list, tuple)):
        cost = cost[0]
      flops = float((cost or {}).get("flops", 0.0))
    except Exception:  # noqa: BLE001 - cost analysis is backend-optional
      flops = 0.0
    if flops > 0:
      self._SetFlopsPerStep(flops / max(steps, 1))
    else:
      self._flops_per_step = 0.0   # don't re-trace every Run

  def _MarkRunStart(self) -> None:
    self._run_compile_mark = self._goodput.CompileSeconds()

  def _AttributeRunWall(self, t_start: float, infeed_wait_s: float) -> None:
    """Goodput attribution of one Run's wall: input wait is badput, the
    rest is the productive device loop minus any lazy jit compiles the
    jax.monitoring listener attributed inside the window (first Run
    without AOT precompile — that wall is compile badput, not step). In
    the async/deferred pipeline the Run blocks on the PREVIOUS loop's
    telemetry, so in steady state its wall still spans ~one device loop."""
    wall = max(time.time() - t_start, 0.0)
    compiled = max(
        self._goodput.CompileSeconds()
        - getattr(self, "_run_compile_mark", 0.0), 0.0)
    self._goodput.Add("infeed_wait", min(max(infeed_wait_s, 0.0), wall))
    self._goodput.Add("step", max(wall - infeed_wait_s - compiled, 0.0))

  def _RefreshHostSchedules(self) -> None:
    """Host-driven schedules (DevBasedSchedule anneal-on-plateau) may change
    between runs; their values are trace-time constants, so a change must
    drop the cached jitted functions (rare — a few decays per run)."""
    key = []
    for lrn in getattr(self._task, "learners", []):
      sched = getattr(lrn, "lr_sched", None)
      if sched is None:
        continue
      if hasattr(sched, "UpdateFromHistory"):
        sched.UpdateFromHistory()
      if hasattr(sched, "HostStateKey"):
        key.append(sched.HostStateKey())
    key = tuple(key)
    if key != getattr(self, "_host_sched_key", None):
      if getattr(self, "_host_sched_key", None) is not None:
        self._loop_fn = None
        self._step_fn = None
      self._host_sched_key = key

  def _Unbuilt(self, what: str):
    """Round the first piece of work on a program that no Compile() built:
    `compile`, the first dispatch of the step or loop function, which
    traces, lowers and compiles (or fetches) it there; `flops`, the lowering
    _MaybePublishMfu counts its operations from. Its compile events fall
    under `<program>/<what>/<step|loop>` in the start-up record, and no
    other program's do (batch placement, the infeed's jits, eager ops)."""
    name = "loop" if self.p.on_device_loop else "step"
    label = self._ProgramLabel(name, what)
    if name in self.compile_records or label in self._named_unbuilt:
      return contextlib.nullcontext()
    self._named_unbuilt.add(label)
    return _STARTUP.Program(label)

  def _OpenFirstSteps(self) -> None:
    if self._first_steps is None:
      self._first_steps = _STARTUP.OpenPhase("first_steps")

  def _NoteLoopDone(self, unit, result: dict) -> None:
    """A loop's completion: what compiled on the dispatching thread while
    its Run was open goes into its result beside `host_overhead_s` (0.0 for
    a loop that found its programs built; the programs' names where not),
    and the loop into the start-up record, whose `first_steps` the first
    completion ends."""
    unit.done = time.perf_counter()
    result["compile_s"] = round(unit.compile_s, 6)
    if unit.compile_s:
      result["compile_fun_names"] = list(unit.fun_names)
    _STARTUP.LoopDone(unit)
    if self._first_steps:
      self._first_steps.Close(unit.done)
      self._first_steps = False

  def Run(self, state: NestedMap) -> tuple[NestedMap, dict[str, float]]:
    self._RefreshHostSchedules()
    self._loops_run += 1
    self._OpenFirstSteps()
    unit = self._run_unit = _STARTUP.OpenUnit("loop")
    # spans: jax.profiler.TraceAnnotation, on the host plane of a profiler
    # trace beside the device ops; a flag test when no trace is running
    try:
      with jax.profiler.TraceAnnotation("lingvo/train/loop",
                                        loop=self._loops_run):
        if not self.p.async_infeed:
          return self._RunSync(state)
        return self._RunAsync(state)
    finally:
      _STARTUP.CloseUnit(unit)

  def _RunSync(self, state: NestedMap) -> tuple[NestedMap, dict[str, float]]:
    """The legacy fully-synchronous loop (p.async_infeed = False): host
    batch prep, device loop, metric fetch and summary writes all serialize
    on this thread. Kept bit-exact as the kill-switch reference behavior;
    only the infeed_wait_s / host_overhead_s timers are new."""
    p = self.p
    t0 = time.time()
    self._MarkRunStart()
    if p.on_device_loop:
      # host: prefetch + stack steps_per_loop batches; device: one program
      t_in = time.perf_counter()
      with jax.profiler.TraceAnnotation("lingvo/train/infeed_get"):
        batches = [self.input_generator.GetPreprocessedInputBatch()
                   for _ in range(p.steps_per_loop)]
        stacked = jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *batches)
      with jax.profiler.TraceAnnotation("lingvo/train/put_batch"):
        stacked = self._PutStackedBatch(stacked)
      infeed_wait_s = time.perf_counter() - t_in
      fn = self._GetLoopFn(state)
      self._MaybePublishMfu(fn, state, stacked, steps=p.steps_per_loop)
      with self._MeshScope(), self._ProfilerScope():
        with jax.profiler.TraceAnnotation("lingvo/train/dispatch"), \
            self._Unbuilt("compile"):
          state, acc, stats_acc = fn(state, stacked)
        with jax.profiler.TraceAnnotation("lingvo/train/backpressure"):
          jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
    else:
      fn = self._GetStepFn(state)
      acc = None
      stats_acc = None
      infeed_wait_s = 0.0
      with self._MeshScope(), self._ProfilerScope():
        for _ in range(p.steps_per_loop):
          t_in = time.perf_counter()
          with jax.profiler.TraceAnnotation("lingvo/train/infeed_get"):
            batch = self.input_generator.GetPreprocessedInputBatch()
          with jax.profiler.TraceAnnotation("lingvo/train/put_batch"):
            batch = self._PutBatch(batch)
          infeed_wait_s += time.perf_counter() - t_in
          self._MaybePublishMfu(fn, state, batch)
          with jax.profiler.TraceAnnotation("lingvo/train/dispatch"), \
              self._Unbuilt("compile"):
            state, out = fn(state, batch)
          with jax.profiler.TraceAnnotation("lingvo/train/accumulate"):
            acc = metrics_lib.AccumulateMetrics(acc, out.metrics)
            stats_pairs = NestedMap(
                {k: (v, 1.0) for k, v in out.stats.FlattenItems()})
            stats_pairs.update(_ScalarSummaryPairs(out))
            stats_acc = metrics_lib.AccumulateMetrics(stats_acc, stats_pairs)
        # One host sync per loop (ref: one session.run per steps_per_loop);
        # inside the profiler scope so traces capture the device work.
        with jax.profiler.TraceAnnotation("lingvo/train/backpressure"):
          jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
    wall = time.time() - t0
    self._AttributeRunWall(t0, infeed_wait_s)
    t_tel = time.perf_counter()
    with jax.profiler.TraceAnnotation("lingvo/train/finalize"):
      with jax.profiler.TraceAnnotation("lingvo/train/device_wait"):
        result = metrics_lib.FinalizeMetrics(acc) if acc else {}
        if stats_acc:
          result.update(metrics_lib.FinalizeMetrics(stats_acc))
      result["steps_per_second"] = p.steps_per_loop / wall
      result["examples_per_second"] = (
          p.steps_per_loop * self.input_generator.GlobalBatchSize() / wall)
      step = int(jax.device_get(state.step))
      # loop wall attribution (satellite of the async-infeed PR): input wait
      # vs host-side telemetry fetch — on this path both sit on the critical
      # path between device loops
      result["infeed_wait_s"] = round(infeed_wait_s, 6)
      result["host_overhead_s"] = round(
          infeed_wait_s + (time.perf_counter() - t_tel), 6)
      self._NoteLoopDone(self._run_unit, result)
      for k, v in self._InputStatsOf(self.input_generator).items():
        result[f"input_{k}"] = v
      # smoothed cross-Run rate incl. eval gaps (ref StepRateTracker:393)
      result["global_steps_per_second"] = self._rate_tracker.Update(
          step, self.input_generator.GlobalBatchSize())
      with jax.profiler.TraceAnnotation("lingvo/train/summaries"):
        self.WriteSummaries(step, result)
    self._NotifyLoopDone()
    return state, result

  def _RunAsync(self, state: NestedMap) -> tuple[NestedMap, dict[str, float]]:
    """Async pipeline: batches come pre-prepared (and, single-process,
    pre-placed) from the infeed producer; the post-loop metric fetch +
    summary write run on the telemetry worker. Batch order is bit-identical
    to _RunSync; the returned result is the most recent COMPLETED loop's
    (<= pipeline_depth loops stale — <= 1 for the legacy pipeline_depth=0
    window; the first Run blocks for its own)."""
    p = self.p
    t0 = time.time()
    t_host0 = time.perf_counter()
    self._MarkRunStart()
    infeed = self._GetInfeed()
    wait0 = infeed.wait_s
    pipelined = p.defer_telemetry and int(p.pipeline_depth or 0) >= 1
    if pipelined:
      if self._host_step is None:
        # the ONLY steady-path device fetch: seed host-side step tracking
        # from the concrete restored/initial state, before this Run's
        # dispatch makes `state.step` an in-flight value
        self._host_step = int(jax.device_get(state.step))
      if self._pipe_t_mark is None:
        self._pipe_t_mark = t0
        self._pipe_wait_mark = wait0
        self._pipe_thread = threading.get_ident()
        self._pipe_compile_mark = self._goodput.CompileSeconds()
    if p.on_device_loop:
      with jax.profiler.TraceAnnotation("lingvo/train/infeed_get"):
        stacked = infeed.Get()
      if stacked is None:
        raise StopIteration("train input exhausted")
      if not infeed.places_batches:
        with jax.profiler.TraceAnnotation("lingvo/train/put_batch"):
          stacked = self._PutStackedBatch(stacked)
      fn = self._GetLoopFn(state)
      self._MaybePublishMfu(fn, state, stacked, steps=p.steps_per_loop)
      with self._MeshScope(), self._ProfilerScope():
        with jax.profiler.TraceAnnotation("lingvo/train/dispatch"), \
            self._Unbuilt("compile"):
          state, acc, stats_acc = fn(state, stacked)
        if self._profiling_run:
          # opt-in diagnostics: keep the device work inside the trace
          jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
    else:
      fn = self._GetStepFn(state)
      acc = None
      stats_acc = None
      with self._MeshScope(), self._ProfilerScope():
        for _ in range(p.steps_per_loop):
          with jax.profiler.TraceAnnotation("lingvo/train/infeed_get"):
            batch = infeed.Get()
          if batch is None:
            raise StopIteration("train input exhausted")
          if not infeed.places_batches:
            with jax.profiler.TraceAnnotation("lingvo/train/put_batch"):
              batch = self._PutBatch(batch)
          self._MaybePublishMfu(fn, state, batch)
          with jax.profiler.TraceAnnotation("lingvo/train/dispatch"), \
              self._Unbuilt("compile"):
            state, out = fn(state, batch)
          with jax.profiler.TraceAnnotation("lingvo/train/accumulate"):
            acc = metrics_lib.AccumulateMetrics(acc, out.metrics)
            stats_pairs = NestedMap(
                {k: (v, 1.0) for k, v in out.stats.FlattenItems()})
            stats_pairs.update(_ScalarSummaryPairs(out))
            stats_acc = metrics_lib.AccumulateMetrics(stats_acc, stats_pairs)
        if self._profiling_run:
          jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
    # host-side cost of this Run (input wait + placement + dispatch);
    # everything below the dispatch is off the critical path
    host_overhead_s = time.perf_counter() - t_host0
    infeed_wait_s = infeed.wait_s - wait0
    queue_depth = infeed.QueueDepth()
    input_stats = self._InputStatsOf(self.input_generator)
    if pipelined:
      # host-side step tracking: the loop just dispatched WILL end at this
      # step (or fail, in which case recovery re-seeds from the device)
      self._host_step += p.steps_per_loop
      step_val: Any = self._host_step
    else:
      step_val = state.step
      if _StateDonation():
        # the NEXT Run's dispatch donates `state` (incl. .step) on
        # accelerator backends; hand the worker an independent derived array
        # so its deferred device_get can't hit a deleted buffer
        step_val = step_val + 0
    job = functools.partial(
        self._FinalizeLoop, step_val, acc, stats_acc, t0,
        host_overhead_s, infeed_wait_s, queue_depth, input_stats,
        pipelined=pipelined, unit=self._run_unit)
    if not p.defer_telemetry:
      result = job()[1]
      self._AttributeRunWall(t0, infeed_wait_s)
      return state, result
    fut = self._GetTelemetry().Submit(job)
    if not pipelined:
      # pipeline_depth=0 kill switch: the exact PR 5 lag-1 window
      prev, self._pending_telemetry = self._pending_telemetry, fut
      # steady state: return loop k-1's result (its fetch overlapped this
      # loop's dispatch); first Run after a Flush blocks for its own — and
      # marks it consumed so Flush won't report it a second time
      self._pending_consumed = prev is None
      with jax.profiler.TraceAnnotation("lingvo/train/backpressure"):
        result = (prev if prev is not None else fut).result()[1]
      self._AttributeRunWall(t0, infeed_wait_s)
      return state, result
    # k-deep dispatch window: sweep already-completed loops (free), then
    # apply backpressure so at most pipeline_depth loops stay unresolved.
    # Goodput attribution happens at loop completion
    # (_AttributePipelinedLoop), not here: this Run's wall is near zero in
    # steady state and says nothing about device time.
    self._pending.append(fut)
    while self._pending and self._pending[0].done():
      self._PopPending()
    with jax.profiler.TraceAnnotation("lingvo/train/backpressure"):
      while len(self._pending) > int(p.pipeline_depth):
        self._PopPending()
      if self._last_result is None:
        self._PopPending()   # very first loop (or first after recovery)
    self._last_result_consumed = True
    return state, self._last_result

  def _AttributePipelinedLoop(self) -> float:
    """Pipelined goodput attribution, run on the telemetry worker at loop
    COMPLETION: loops execute serially on device however far ahead the
    host dispatches, so completion-to-completion intervals partition the
    wall into per-loop spans. Each span minus the infeed wait and the
    lazy-compile seconds the DISPATCHING thread spent inside it is
    productive step time (a compile on any other thread — this worker,
    the checkpoint writer — runs beside the device loop and delays
    nothing).
    Replaces _AttributeRunWall on this path — with a k-deep window the
    Run wall is near zero and measures nothing. Returns the interval (the
    per-loop wall basis for rate metrics)."""
    now = time.time()
    prev_t = self._pipe_t_mark if self._pipe_t_mark is not None else now
    self._pipe_t_mark = now
    wait_now = self._infeed.wait_s if self._infeed is not None else 0.0
    wait_d = max(wait_now - self._pipe_wait_mark, 0.0)
    self._pipe_wait_mark = wait_now
    comp_now = self._goodput.CompileSeconds(self._pipe_thread)
    comp_d = max(comp_now - self._pipe_compile_mark, 0.0)
    self._pipe_compile_mark = comp_now
    interval = max(now - prev_t, 1e-9)
    self._goodput.Add("infeed_wait", min(wait_d, interval))
    self._goodput.Add("step", max(interval - wait_d - comp_d, 0.0))
    return interval

  def _FinalizeLoop(self, step_val, acc, stats_acc, t_start,
                    host_overhead_s, infeed_wait_s, queue_depth,
                    input_stats, pipelined: bool = False, unit=None,
                    ) -> tuple[int, dict[str, float]]:
    """Telemetry-worker job: device_get of one loop's metrics + summary
    write. The np.asarray inside FinalizeMetrics synchronizes on the loop's
    completion, so `wall` covers dispatch through device completion.
    step_val is a host int under host-side step tracking (pipelined), else
    the loop's device step counter."""
    p = self.p
    with jax.profiler.TraceAnnotation("lingvo/train/finalize"):
      with jax.profiler.TraceAnnotation("lingvo/train/device_wait"):
        result = metrics_lib.FinalizeMetrics(acc) if acc else {}
        if stats_acc:
          result.update(metrics_lib.FinalizeMetrics(stats_acc))
      wall = max(time.time() - t_start, 1e-9)
      if pipelined:
        # dispatch->completion spans queue time behind earlier in-flight
        # loops; the completion-to-completion interval is the honest
        # per-loop wall (and feeds the goodput step bucket)
        wall = self._AttributePipelinedLoop()
      result["steps_per_second"] = p.steps_per_loop / wall
      result["examples_per_second"] = (
          p.steps_per_loop * self.input_generator.GlobalBatchSize() / wall)
      result["infeed_wait_s"] = round(infeed_wait_s, 6)
      result["host_overhead_s"] = round(host_overhead_s, 6)
      if unit is not None:
        self._NoteLoopDone(unit, result)
      result["infeed_queue_depth"] = queue_depth
      for k, v in input_stats.items():
        result[f"input_{k}"] = v
      step = (int(step_val) if isinstance(step_val, int)
              else int(jax.device_get(step_val)))
      result["global_steps_per_second"] = self._rate_tracker.Update(
          step, self.input_generator.GlobalBatchSize())
      with jax.profiler.TraceAnnotation("lingvo/train/summaries"):
        self.WriteSummaries(step, result)
    # stamped AFTER the summary write (the jsonl rows are keyed by step
    # already): lets executor metrics rows disambiguate the bounded lag
    result["at_step"] = step
    self._NotifyLoopDone()
    return step, result


class EvalProgram(BaseProgram):
  """Whole-dataset eval with fixed-shape metric accumulation
  (ref EvalProgram:995)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.name = "eval"
    p.dataset_name = "Test"
    p.Define("use_ema", True, "Eval with EMA weights when available.")
    return p

  def _GetStepFn(self, state: NestedMap | None = None):
    if self._step_fn is None:

      def _Step(theta, batch, step):
        metrics, _ = self._task.EvalStep(theta, batch, step=step)
        return metrics

      self._step_fn = jax.jit(_Step)
    return self._step_fn

  def _EvalTheta(self, state: NestedMap) -> NestedMap:
    if self.p.use_ema and "ema_theta" in state:
      return state.ema_theta
    return state.theta

  def _MaxEvalBatches(self) -> int:
    """Eval budget: task's eval.samples_per_summary wins over steps_per_loop
    (ref base_model.py eval params; 0 = unlimited for finite datasets)."""
    sps = getattr(self._task.p.eval, "samples_per_summary", 0)
    if sps:
      # each coordinated step consumes a GLOBAL batch (all hosts' shards)
      bs = max(1, self.input_generator.InfeedBatchSize()
               * jax.process_count())
      return max(1, -(-sps // bs))
    return self.p.steps_per_loop

  def Run(self, state: NestedMap) -> tuple[NestedMap, dict[str, float]]:
    with self._goodput.TrackExcludingCompile("eval"):   # badput, minus compiles
      return self._RunEval(state)

  def _RunEval(self, state: NestedMap) -> tuple[NestedMap, dict[str, float]]:
    fn = self._GetStepFn(state)
    theta = self._EvalTheta(state)
    acc = None
    gen = self.input_generator
    max_batches = self._MaxEvalBatches()
    raw = (gen.EpochBatches() if hasattr(gen, "EpochBatches")
           else _TakeN(gen, max_batches))
    # Async infeed: prefetch (and, single-process, pre-place) eval batches
    # on a producer thread so host batch prep overlaps the device eval
    # steps. The multi-host batch-availability barrier stays on THIS thread
    # (its process_allgather must not run concurrently with the eval step's
    # collectives). One throwaway infeed per Run: eval streams are finite
    # and the generator is Reset between cycles.
    infeed = None
    if self.p.async_infeed:
      from lingvo_tpu.runners import infeed as infeed_lib
      infeed = infeed_lib.DeviceInfeed(
          lambda: raw, place_fn=self._PutBatch, depth=self.p.infeed_depth,
          place_in_producer=self._PlaceInProducer(),
          name=f"{self.p.name or 'eval'}-infeed", stream_key=id(gen),
          registry=self.metrics)
    batches = _CoordinateFiniteStream(
        infeed.Iter() if infeed is not None else raw)
    n = 0
    infeed_wait_s = 0.0
    try:
      with self._MeshScope(), self._ProfilerScope():
        for batch in batches:
          if infeed is None or not infeed.places_batches:
            batch = self._PutBatch(batch)
          out = fn(theta, batch, state.step)
          acc = metrics_lib.AccumulateMetrics(acc, out)
          n += 1
          if n >= max_batches:
            break
    finally:
      if infeed is not None:
        infeed_wait_s = infeed.wait_s
        infeed.Stop()
    result = metrics_lib.FinalizeMetrics(acc) if acc else {}
    if infeed is not None:
      result["infeed_wait_s"] = round(infeed_wait_s, 6)
    _MaybeResetFiniteStream(gen)
    step = int(jax.device_get(state.step))
    self.WriteSummaries(step, result)
    self._NotifyLoopDone()
    return state, result


class DecodeProgram(BaseProgram):
  """Device decode + host postprocess into decoder metrics
  (ref DecodeProgram:1229)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.name = "decode"
    p.dataset_name = "Test"
    p.Define("use_ema", True, "Decode with EMA weights when available.")
    return p

  def _GetStepFn(self, state: NestedMap | None = None):
    if self._step_fn is None:

      def _Step(theta, batch):
        with py_utils.EvalContext():
          return self._task.Decode(theta, batch)

      self._step_fn = jax.jit(_Step)
    return self._step_fn

  def Run(self, state: NestedMap) -> tuple[NestedMap, dict[str, float]]:
    with self._goodput.TrackExcludingCompile("eval"):   # decode rides eval badput
      return self._RunDecode(state)

  def _RunDecode(self, state: NestedMap) -> tuple[NestedMap, dict[str, float]]:
    fn = self._GetStepFn(state)
    theta = (state.ema_theta
             if self.p.use_ema and "ema_theta" in state else state.theta)
    dec_metrics = self._task.CreateDecoderMetrics()
    gen = self.input_generator
    batches = _CoordinateFiniteStream(
        gen.EpochBatches() if hasattr(gen, "EpochBatches")
        else _TakeN(gen, self.p.steps_per_loop))
    n = 0
    # async host postprocess (ref DecodeProgram:1487-1529): the device
    # decodes batch k+1 while ONE worker thread postprocesses batch k.
    # One outstanding future max: bounded memory (host_out trees are big)
    # and exceptions surface within one batch, while keeping the k/k+1
    # overlap. Single worker => decoder metrics mutate without locks.
    from concurrent.futures import ThreadPoolExecutor
    pending = None
    with self._MeshScope(), self._ProfilerScope(), \
         ThreadPoolExecutor(max_workers=1) as pool:
      for batch in batches:
        out = fn(theta, self._PutBatch(batch))
        if jax.process_count() > 1:
          # batch-sharded outputs are not host-addressable: gather the
          # global tree so postprocess sees every example (every process
          # computes identical metrics; only process 0 writes). Global
          # fully-replicated leaves (scalar counters, reduced statistics a
          # task adds to its Decode output) skip the collective — every
          # process already holds the value; everything else (global
          # batch-sharded arrays, host-local or numpy leaves that differ
          # per process) goes through process_allgather as before.
          from jax.experimental import multihost_utils

          def _GatherLeaf(leaf):
            if (isinstance(leaf, jax.Array)
                and not leaf.is_fully_addressable
                and leaf.is_fully_replicated):
              return np.asarray(leaf.addressable_shards[0].data)
            return multihost_utils.process_allgather(leaf, tiled=True)

          out = jax.tree_util.tree_map(_GatherLeaf, out)
        host_out = jax.tree_util.tree_map(np.asarray, out)
        if n == 0 and isinstance(host_out, NestedMap) and (
            jax.process_index() == 0):
          probs = host_out.Get("atten_probs")
          if probs is not None:
            from lingvo_tpu.core import summary_utils
            summary_utils.AddAttentionSummary(
                self._tb, f"{self.p.name}/atten", probs,
                int(jax.device_get(state.step)))
        if pending is not None:
          pending.result()  # backpressure + surface exceptions promptly
        pending = pool.submit(self._task.PostProcessDecodeOut, host_out,
                              dec_metrics)
        n += 1
        if n >= self.p.steps_per_loop:
          break
      if pending is not None:
        pending.result()
    result = self._task.DecodeFinalize(dec_metrics)
    _MaybeResetFiniteStream(gen)
    step = int(jax.device_get(state.step))
    self.WriteSummaries(step, result)
    self._NotifyLoopDone()
    return state, result


class InputBenchmarkProgram(BaseProgram):
  """Measures input-pipeline throughput without touching the model (ref
  `InputBenchmark:2249`): drains steps_per_loop batches from the generator
  and reports batches/sec + examples/sec."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.name = "input_benchmark"
    p.Define("warmup_batches", 2, "Batches drawn before timing starts.")
    return p

  def Run(self, state: NestedMap) -> tuple[NestedMap, dict[str, float]]:
    gen = self.input_generator
    for _ in range(self.p.warmup_batches):
      gen.GetPreprocessedInputBatch()
    t0 = time.time()
    n = examples = 0
    for _ in range(self.p.steps_per_loop):
      batch = gen.GetPreprocessedInputBatch()
      batched = [l for l in batch.Flatten() if np.ndim(l) >= 1]
      examples += int(batched[0].shape[0]) if batched else 0
      n += 1
    wall = max(time.time() - t0, 1e-9)
    result = {
        "batches_per_second": n / wall,
        "examples_per_second": examples / wall,
    }
    step = int(jax.device_get(state.step)) if hasattr(state, "step") else 0
    self.WriteSummaries(step, result)
    return state, result


def PlaceStateForPrograms(programs, state):
  """Places (or, for an abstract template, annotates) a train state onto
  the mesh shardings of whichever program declares them.

  Multi-host REQUIRES this before any collective orbax restore/save or
  mesh-spanning jit: host-local SingleDeviceSharding state is rejected.
  Works for any schedule shape — scans the given programs rather than
  assuming a single train program.
  """
  shardings = None
  for prog in programs:
    pp = prog.p if hasattr(prog, "p") else prog
    try:
      mesh_ = pp.mesh
      fn = pp.state_sharding_fn
    except (AttributeError, KeyError):
      continue  # program stub without mesh params (tests, custom runners)
    if mesh_ is not None and fn is not None:
      shardings = fn(state)
      break
  if shardings is None:
    return state
  leaves = jax.tree_util.tree_leaves(state)
  if leaves and isinstance(leaves[0], jax.ShapeDtypeStruct):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, shardings)
  return jax.device_put(state, shardings)


def _MaybeResetFiniteStream(gen):
  """Finite (max_epochs-bounded) file streams must be re-read from the start
  on the next eval round (ref EvalProgram infeed-until-OutOfRange re-setup,
  `program.py:995`); infinite streams keep their position."""
  if getattr(getattr(gen, "p", None), "max_epochs", 0):
    gen.Reset()


def _TakeN(gen, n):
  it = iter(gen)
  for _ in range(n):
    try:
      yield next(it)
    except StopIteration:
      return


def _CoordinateFiniteStream(batches):
  """Multi-host barrier on batch availability: hosts with disjoint finite
  input shards can yield UNEQUAL batch counts; since every program step is
  a cross-process collective, a host iterating one batch more than another
  deadlocks. Stops ALL hosts as soon as ANY host runs dry (the tail
  examples on longer shards are skipped — the price of collective eval;
  ref the infeed-until-OutOfRange coordination in program.py:1386)."""
  if jax.process_count() <= 1:
    yield from batches
    return
  from jax.experimental import multihost_utils
  it = iter(batches)
  while True:
    try:
      batch = next(it)
      have = True
    except StopIteration:
      batch = None
      have = False
    counts = multihost_utils.process_allgather(
        np.asarray([1 if have else 0]))
    if not bool(np.all(counts)):
      return
    yield batch


class SimpleProgramSchedule:
  """Train K loops, then run eval/decode programs
  (ref SimpleProgramSchedule:2329)."""

  @classmethod
  def Params(cls):
    p = hyperparams.InstantiableParams(cls)
    p.Define("name", "schedule", "Name.")
    p.Define("train_program", None, "TrainProgram params (or None).")
    p.Define("eval_programs", [], "List of eval/decode program params.")
    p.Define("train_executions_per_eval", 1,
             "Train Run() calls between eval rounds.")
    return p

  def __init__(self, params, task=None, input_generators=None):
    self.p = params.Copy()
    input_generators = input_generators or {}
    self.train_program = None
    if self.p.train_program is not None:
      self.train_program = self.p.train_program.cls(
          self.p.train_program, task=task,
          input_generator=input_generators.get(
              self.p.train_program.dataset_name))
    self.eval_programs = [
        ep.cls(ep, task=task,
               input_generator=input_generators.get(ep.dataset_name))
        for ep in self.p.eval_programs
    ]

  @property
  def programs(self):
    out = []
    if self.train_program:
      out.append(self.train_program)
    return out + list(self.eval_programs)

  def StepsPerCycle(self) -> int:
    """Optimizer steps one Run() advances the train state by — the
    executor's host-side step arithmetic (pipelined main loop) relies on
    this being deterministic. 0 = no train program (the executor falls
    back to device-step fetching). Schedules without this method (e.g.
    MultiTaskProgramSchedule, whose per-cycle step count depends on the
    sampled task) are never pipelined."""
    if self.train_program is None:
      return 0
    return (max(1, self.p.train_executions_per_eval)
            * int(self.train_program.p.steps_per_loop))

  def Run(self, state: NestedMap) -> tuple[NestedMap, dict[str, Any]]:
    results: dict[str, Any] = {}
    if self.train_program is not None:
      train_result = None
      for _ in range(max(1, self.p.train_executions_per_eval)):
        state, train_result = self.train_program.Run(state)
      results["train"] = train_result
      if self.eval_programs:
        # program boundary: land the deferred telemetry of the last train
        # loop before eval starts (summary ordering), and report the
        # CURRENT loop's result to the executor instead of the lagged one
        flushed = self.train_program.Flush()
        if flushed is not None:
          results["train"] = flushed
    for ep in self.eval_programs:
      state, r = ep.Run(state)
      results[ep.p.name] = r
    return state, results


class MultiTaskProgramSchedule:
  """Per-task train programs driven by a sampling TaskScheduler.

  The executor-side expansion of a MultiTaskModel (ref
  `executor.py:67-153` GetExecutorParams + the per-cycle
  `task_scheduler.Sample` at `executor.py:573`, and `SampleTask` in
  `base_model.py:1480`): each cycle samples one task name and runs that
  task's TrainProgram for its steps_per_loop. The combined train state is
  NestedMap(tasks={name: per-task state}, step=total steps) so a single
  checkpointer handles save/restore for the whole model.
  """

  @classmethod
  def Params(cls):
    p = hyperparams.InstantiableParams(cls)
    p.Define("name", "multitask_schedule", "Name.")
    p.Define("task_schedule", None, "TaskScheduler params.")
    p.Define("train_programs", None,
             "Params holding one TrainProgram params per task name.")
    p.Define("eval_programs", [], "Eval/decode program params (any task).")
    p.Define("train_executions_per_eval", 1,
             "Train cycles between eval rounds (ref "
             "SimpleProgramSchedule.train_executions_per_eval).")
    p.Define("variable_renaming_rules", [],
             "[(regex, replacement)] over dotted theta paths; tasks whose renamed "
             "paths collide share those variables (ref multitask_model.py "
             "RegExSharedVariableModel). Shared values are unified at init "
             "and propagated from the sampled task after each train cycle.")
    return p

  def __init__(self, params, tasks: dict | None = None,
               input_generators: dict | None = None, task=None):
    """tasks: {task_name: task instance} (instantiated from each train
    program's task params when omitted — the trainer CLI path);
    input_generators: {(task_name, dataset_name): generator}, or
    {dataset_name: generator} applied to every task. `task` is accepted for
    SimpleProgramSchedule constructor compatibility and ignored when `tasks`
    is given."""
    del task  # the multi-task schedule owns its task set
    self.p = params.Copy()
    input_generators = input_generators or {}
    if tasks is None:
      tasks = {}
      for name, tp in self.p.train_programs.IterParams():
        tasks[name] = tp.task.Instantiate()
        tasks[name].FinalizePaths()
    self._tasks = dict(tasks)
    self._scheduler = self.p.task_schedule.Instantiate()
    self._runs_since_eval = 0
    self._shared_rules = None
    if self.p.variable_renaming_rules:
      from lingvo_tpu.core import multitask_model
      self._shared_rules = multitask_model.SharedVariableRules(
          self.p.variable_renaming_rules)

    def _GenFor(name, dataset):
      if (name, dataset) in input_generators:
        return input_generators[(name, dataset)]
      return input_generators.get(dataset)

    self.train_programs = {}
    for name, tp in self.p.train_programs.IterParams():
      self.train_programs[name] = tp.cls(
          tp, task=tasks[name],
          input_generator=_GenFor(name, tp.dataset_name))
    self.eval_programs = []
    for ep in self.p.eval_programs:
      task_name = getattr(ep, "task_name", None) or next(iter(tasks))
      self.eval_programs.append(
          ep.cls(ep, task=tasks[task_name],
                 input_generator=_GenFor(task_name, ep.dataset_name)))

  @property
  def programs(self):
    return list(self.train_programs.values()) + list(self.eval_programs)

  @property
  def tasks(self):
    return dict(self._tasks)

  def CreateTrainState(self, key) -> NestedMap:
    import jax
    states = NestedMap()
    keys = jax.random.split(key, len(self._tasks))
    for k, name in zip(keys, sorted(self._tasks)):
      states.Set(name, self._tasks[name].CreateTrainState(k))
    if self._shared_rules is not None:
      states = self._shared_rules.UnifyStates(states)
    return NestedMap(tasks=states, step=jnp.zeros((), jnp.int32))

  def Run(self, state: NestedMap) -> tuple[NestedMap, dict[str, Any]]:
    import jax
    total_step = int(jax.device_get(state.step))
    name = self._scheduler.Sample(total_step)
    task_state = state.tasks.GetItem(name)
    task_state, result = self.train_programs[name].Run(task_state)
    state.tasks.Set(name, task_state)
    if self._shared_rules is not None:
      state.tasks = self._shared_rules.Propagate(state.tasks, name)
    state.step = jnp.asarray(
        sum(int(jax.device_get(state.tasks.GetItem(n).step))
            for n in sorted(self._tasks)), jnp.int32)
    results = {f"train_{name}": result, "sampled_task": name}
    self._runs_since_eval += 1
    if self._runs_since_eval >= max(1, self.p.train_executions_per_eval):
      self._runs_since_eval = 0
      if self.eval_programs:
        # program boundary: see SimpleProgramSchedule.Run
        flushed = self.train_programs[name].Flush()
        if flushed is not None:
          results[f"train_{name}"] = flushed
      for ep in self.eval_programs:
        task_name = (getattr(ep.p, "task_name", None)
                     or next(iter(self._tasks)))
        st, r = ep.Run(state.tasks.GetItem(task_name))
        state.tasks.Set(task_name, st)
        results[ep.p.name] = r
    return state, results
