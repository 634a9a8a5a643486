"""ExecutorTpu: the training driver loop.

Re-designs `lingvo/executor.py` (`ExecutorTpu:161`): owns the train state,
checkpointer, and program schedule; the main loop interleaves
checkpoint-save/restore with program-schedule runs and exports metrics. TPU
system init / device assignment collapses to jax device discovery; program
compilation is jit's AOT lower+compile.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any

import jax

from lingvo_tpu import observe
from lingvo_tpu.observe import goodput as goodput_lib
from lingvo_tpu.observe import profile as profile_lib
from lingvo_tpu.core import checkpointer as checkpointer_lib
from lingvo_tpu.core import py_utils
from lingvo_tpu.core.nested_map import NestedMap


class ExecutorTpu:

  @profile_lib.InPhase("build")
  def __init__(self, model_params, logdir: str, schedule=None, task=None,
               init_seed: int = 1234, precompile: bool = False,
               max_train_retries: int = 3, mlperf_benchmark: str = "",
               trial=None, serve_port=None, watchdog=None):
    """model_params: SingleTaskModel-style params (task + input attached).

    If `task` is given (e.g. the instance shared with the program schedule),
    it is used directly instead of instantiating a duplicate model. For a
    multi-task schedule (one exposing CreateTrainState/tasks) `task` may be
    None. `max_train_retries`: consecutive transient failures tolerated
    before giving up (each retry restores the last checkpoint — ref
    `base_runner._RunLoop:399-528` taxonomy).

    serve_port: when not None, a StatusServer over the process-global
    registry serves /metrics, /statusz, /traces, /healthz for this
    trainer (0 = ephemeral port; read `self.status_server.port`). It is
    stopped when the main loop exits. watchdog: None auto-creates a
    StallWatchdog when serve_port is set; True forces one; False
    disables; or pass a configured StallWatchdog. The watchdog beats
    once per COMPLETED program loop (telemetry-side, not dispatch-side),
    so /healthz flips when the device stalls even while a pipelined host
    keeps dispatching.
    """
    self._logdir = logdir
    os.makedirs(logdir, exist_ok=True)
    self._max_train_retries = max_train_retries
    if task is not None:
      # task built by the caller: the caller must apply
      # trial.OverrideModelParams before constructing it
      self._task = task
    elif schedule is not None and hasattr(schedule, "tasks"):
      self._task = None  # multi-task: schedule owns the task set
    else:
      if trial is not None:
        model_params = trial.OverrideModelParams(model_params)
      self._model = model_params.Instantiate()
      self._task = self._model.GetTask()
    if self._task is not None:
      self._task.FinalizePaths()
    else:
      for t in schedule.tasks.values():
        t.FinalizePaths()
    # Serialize the full experiment config for reproducibility
    # (ref executor.py:233-237 trainer_params.txt). One writer per logdir
    # under multi-host.
    if model_params is not None and jax.process_index() == 0:
      with open(os.path.join(logdir, "trainer_params.txt"), "w") as f:
        f.write(model_params.ToText())
    self._schedule = schedule
    if jax.process_index() == 0:
      self._WriteModelAnalysis()

    ref_task = (self._task if self._task is not None
                else next(iter(schedule.tasks.values())))
    tp = ref_task.p.train
    self._checkpointer = checkpointer_lib.Checkpointer(
        os.path.join(logdir, "train"),
        save_interval_steps=tp.save_interval_steps,
        max_to_keep=tp.save_max_to_keep)
    self._init_seed = init_seed
    self._pruning_schedule = None
    self._pruning_masks = None
    # MLPerf-compliance logging (ref ml_perf_log.py:80 + executor hooks)
    # hyperparameter-tuning service hook (ref base_trial.Trial + the
    # executor's trial consultation; NoOpTrial when absent)
    if trial is None:
      from lingvo_tpu.core import base_trial
      trial = base_trial.NoOpTrial()
    self._trial = trial
    self._trial_done = False
    self._mlperf = None
    from lingvo_tpu.core import ml_perf_log
    self._mllog = ml_perf_log
    if mlperf_benchmark and jax.process_index() == 0:  # single log writer
      self._mlperf = ml_perf_log.MlPerfLogger(
          os.path.join(logdir, "mlperf_log.txt"),
          benchmark=mlperf_benchmark)
      self._mlperf.Print(ml_perf_log.INIT_START)
    self._last_prune_step = -1
    self._precompile = precompile
    self._max_steps = tp.max_steps
    # early stop on eval plateau (ref base_runner._ShouldStop + EarlyStop)
    self._early_stop = None
    if getattr(tp, "early_stop_window", 0) > 0:
      from lingvo_tpu.core import early_stop as early_stop_lib
      self._metric_history = early_stop_lib.MetricHistory(
          logdir, "eval", tp.early_stop_metric)
      self._early_stop = early_stop_lib.EarlyStop(
          early_stop_lib.EarlyStop.Params().Set(
              window=tp.early_stop_window,
              tolerance=tp.early_stop_tolerance,
              metric_history=self._metric_history))
    # fleet-facing telemetry (observe/): checkpoint/recovery wall time
    # feeds the process-global goodput tracker; serve_port opens the
    # status endpoints; the watchdog beats once per completed loop
    self._goodput = goodput_lib.Get()
    self.watchdog = None
    if isinstance(watchdog, observe.StallWatchdog):
      self.watchdog = watchdog
    elif watchdog or (watchdog is None and serve_port is not None):
      self.watchdog = observe.StallWatchdog(observe.Default())
    if self.watchdog is not None:
      # liveness follows loop COMPLETION (the telemetry worker fires the
      # callback), not schedule-Run dispatch: a pipelined host dispatches
      # freely while the device hangs, so dispatch-side beats would keep
      # /healthz green through a real stall
      for prog in self._SchedulePrograms():
        set_cb = getattr(prog, "SetLoopDoneCallback", None)
        if callable(set_cb):
          set_cb(self.watchdog.Beat)
    self.status_server = None
    if serve_port is not None:
      self.status_server = observe.StatusServer(
          serve_port, registry=observe.Default(), name="executor",
          statusz_fn=self._StatuszStats,
          watchdog=self.watchdog).Start()

  def _StatuszStats(self) -> dict:
    """Structured /statusz `stats`: loop facts + every program's AOT
    compile records (wall time, XLA memory plan, flops)."""
    out = {"max_steps": self._max_steps, "compile": {}}
    for prog in self._SchedulePrograms():
      name = (getattr(getattr(prog, "p", None), "name", "")
              or type(prog).__name__)
      recs = getattr(prog, "compile_records", None)
      if recs:
        out["compile"][name] = dict(recs)
    return out

  @property
  def task(self):
    return self._task

  @property
  def checkpointer(self):
    return self._checkpointer

  def _WriteModelAnalysis(self):
    """Param-count report (ref summary_utils.ModelAnalysis:432)."""
    import numpy as np
    tasks = ({"": self._task} if self._task is not None
             else self._schedule.tasks)
    lines = []
    total = 0
    for tname, task in sorted(tasks.items()):
      prefix = f"{tname}." if tname else ""
      for path, wp in task.VariableSpecs().FlattenItems():
        n = int(np.prod(wp.shape)) if wp.shape else 1
        total += n
        lines.append(f"{prefix}{path:<60} {str(tuple(wp.shape)):<20} {n}")
    lines.append(f"{'TOTAL':<60} {'':<20} {total}")
    with open(os.path.join(self._logdir, "model_analysis.txt"), "w") as f:
      f.write("\n".join(lines) + "\n")

  def _MaybePrune(self, state: NestedMap, step: int) -> NestedMap:
    """Magnitude pruning between program runs (ref _GetMaskUpdateOp):
    masks recomputed at the schedule cadence, re-applied every loop so
    pruned weights cannot regrow."""
    tp = self._task.p.train if self._task is not None else None
    if tp is None or getattr(tp, "pruning", None) is None:
      return state
    from lingvo_tpu.core import pruning as pruning_lib
    if self._pruning_schedule is None:
      self._pruning_schedule = tp.pruning.Instantiate()
    sched = self._pruning_schedule
    if self._pruning_masks is None or sched.ShouldUpdate(
        step, self._last_prune_step):
      self._pruning_masks = pruning_lib.ComputeMasks(state.theta, sched,
                                                     step)
      self._last_prune_step = step
    state.theta = pruning_lib.ApplyMasks(state.theta, self._pruning_masks)
    if "ema_theta" in state:
      # eval/decode/export read EMA weights — they must be pruned too
      state.ema_theta = pruning_lib.ApplyMasks(state.ema_theta,
                                               self._pruning_masks)
    return state

  def _CreateTrainState(self) -> NestedMap:
    key = jax.random.PRNGKey(self._init_seed)
    if self._task is None or hasattr(self._schedule, "CreateTrainState"):
      return self._schedule.CreateTrainState(key)
    return self._task.CreateTrainState(key)

  def _PlaceState(self, state: NestedMap) -> NestedMap:
    """Places the (host-local, every-process-identical) initial state onto
    the schedule's mesh shardings (any program that declares them).
    Required under multi-host: the collective orbax save and the spanning
    jit both need global arrays, not SingleDeviceSharding host copies.
    """
    if self._schedule is None:
      return state
    from lingvo_tpu.runners import program as program_lib
    return program_lib.PlaceStateForPrograms(self._schedule.programs, state)

  def Start(self) -> NestedMap:
    """Runs the main loop until max_steps; returns the final state.

    Failure taxonomy (ref `base_runner._RunLoop:399-528`): a transient
    infrastructure error (Unavailable/Aborted/deadline — a preempted chip or
    lost host connection) restores the last checkpoint and continues, up to
    `max_train_retries` consecutive failures; anything else (compile errors,
    OOM, shape bugs) is fatal immediately.
    """
    # the start-up record's `build` goes on to the programs' Compile():
    # the train state (`train_state`), then the restore and the warm start
    with profile_lib.Startup().Phase("build"):
      state, start_step = self._BuildState()
    if self._precompile and self._schedule is not None:
      for prog in self._schedule.programs:
        prog.Compile(state)

    if self._mlperf is not None:
      self._mlperf.Print(self._mllog.INIT_STOP)
      self._mlperf.Print(self._mllog.RUN_START)
    try:
      return self._MainLoop(state, start_step)
    except BaseException:
      if self._mlperf is not None:
        self._mlperf.Print(self._mllog.RUN_STOP,
                           metadata={"status": "aborted"})
        self._mlperf.Close()
      raise

  def _BuildState(self) -> tuple[NestedMap, int]:
    """The train state as the main loop starts from it: created and placed,
    restored from the newest checkpoint, or warm-started on a fresh run."""
    with profile_lib.Startup().Phase("train_state"):
      state = self._PlaceState(self._CreateTrainState())
    # 'no checkpoint at all' (fresh run) is distinct from 'restored the
    # step-0 checkpoint' — warm start must apply only to the former
    fresh_run = self._checkpointer.LatestStep() is None
    with self._goodput.Track("checkpoint_restore"):
      state, start_step = self._checkpointer.Restore(state)
    if fresh_run and self._task is not None:
      rules = getattr(self._task.p.train, "init_from_checkpoint_rules", None)
      if rules:
        # fresh run: warm-start matching vars from other checkpoints
        # (ref checkpointer.py:214); resumed runs skip this.
        state = checkpointer_lib.ApplyInitFromCheckpointRules(state, rules)
      npz = getattr(self._task.p.train, "init_from_npz", "")
      if npz:
        state = checkpointer_lib.ImportNpzCheckpoint(
            state, npz,
            getattr(self._task.p.train, "init_from_npz_rules", None))
    return state, start_step

  def _SchedulePrograms(self):
    return list(getattr(self._schedule, "programs", None) or [])

  def _FlushPrograms(self) -> dict:
    """Lands every program's deferred telemetry (summaries, metric fetch)
    — called before the final checkpoint so nothing is lost at exit. A
    telemetry error propagates: it is a real failed summary write/fetch.
    Returns {program name: result} for results no Run handed out yet (the
    lag-1 tail), so the caller can NaN-check and export them."""
    out = {}
    for prog in self._SchedulePrograms():
      flush = getattr(prog, "Flush", None)
      if callable(flush):
        r = flush()
        if isinstance(r, dict):
          out[getattr(getattr(prog, "p", None), "name", "") or "train"] = r
    return out

  def _RecoverPrograms(self):
    """Transient-retry hook: drain pending telemetry (the failure is
    already being handled) and restart errored infeed producers."""
    for prog in self._SchedulePrograms():
      rec = getattr(prog, "RecoverFromFailure", None)
      if callable(rec):
        try:
          rec()
        except BaseException:  # noqa: BLE001
          pass

  def _ShutdownPrograms(self):
    """Stops infeed producer threads + telemetry workers (programs stay
    restartable). Best-effort: teardown must not mask the real error."""
    for prog in self._SchedulePrograms():
      sd = getattr(prog, "Shutdown", None)
      if callable(sd):
        try:
          sd()
        except BaseException:  # noqa: BLE001
          pass

  def _MainLoop(self, state, start_step):
    try:
      return self._MainLoopBody(state, start_step)
    finally:
      try:
        # a fatal exit must not abandon an in-flight background write
        # (non-daemon worker); its own error is secondary here
        self._checkpointer.WaitForPendingSave()
      except BaseException:  # noqa: BLE001
        pass
      self._ShutdownPrograms()
      if self.status_server is not None:
        self.status_server.Stop()
        self.status_server = None
      if self.watchdog is not None:
        self.watchdog.Close()   # drop any still-armed flight recorder

  def _PipelineDepth(self) -> int:
    """The train schedule's dispatch-window depth, or 0 when the schedule
    can't be pipelined: no deterministic StepsPerCycle (multi-task
    sampling), no train program, or the program runs synchronously /
    with pipeline_depth=0 (the kill switch)."""
    sched = self._schedule
    spc = getattr(sched, "StepsPerCycle", None)
    if not callable(spc) or spc() <= 0:
      return 0
    tp = getattr(sched, "train_program", None)
    if tp is None:
      return 0
    p = tp.p
    if not (p.async_infeed and getattr(p, "defer_telemetry", False)):
      return 0
    return max(int(getattr(p, "pipeline_depth", 0) or 0), 0)

  def _SyncHostSteps(self, step: int) -> None:
    """Seeds every program's host-side step tracking at a device fence
    (start, restore, recovery)."""
    for prog in self._SchedulePrograms():
      fn = getattr(prog, "SyncHostStep", None)
      if callable(fn):
        fn(step)

  def _MainLoopBody(self, state, start_step):
    if self._PipelineDepth() >= 1:
      return self._PipelinedMainLoopBody(state, start_step)
    return self._LegacyMainLoopBody(state, start_step)

  def _LegacyMainLoopBody(self, state, start_step):
    """The pre-pipelining main loop (PR 5 shape), kept as the exact path
    for pipeline_depth=0 / sync programs / multi-task schedules: one
    blocking `device_get(state.step)` per cycle, lag-<=1 results."""
    from lingvo_tpu.core import retry as retry_lib
    step = start_step
    consecutive_failures = 0
    while step < self._max_steps:
      # Save applies the cadence policy itself; checking ShouldSave here
      # too would run its multi-host broadcast twice per cycle. (Goodput
      # attribution lives inside Save, gated on an actual write.)
      self._checkpointer.Save(step, state)
      if self._mlperf is not None:
        self._mlperf.Print(self._mllog.BLOCK_START,
                           metadata={"step": step})
      try:
        state, results = self._schedule.Run(state)
        consecutive_failures = 0
      except BaseException as e:  # noqa: BLE001
        if self._mlperf is not None:
          # keep intervals balanced: close the block before retrying/raising
          self._mlperf.Print(self._mllog.BLOCK_STOP,
                             metadata={"step": step, "status": "error"})
        if (not retry_lib.IsTransient(e) or
            consecutive_failures >= self._max_train_retries):
          raise
        consecutive_failures += 1
        delay = min(2.0 ** consecutive_failures, 30.0)
        print(f"[executor] transient failure ({type(e).__name__}: {e}); "
              f"restoring last checkpoint and retrying "
              f"({consecutive_failures}/{self._max_train_retries}) "
              f"in {delay:.0f}s", flush=True)
        with self._goodput.Track("recovery"):
          time.sleep(delay)
          # rebuild device state from the last checkpoint (ref: cleanup +
          # rebuild session + resume from checkpoint); restart any errored
          # infeed producers so the retried Run pulls fresh batches
          self._RecoverPrograms()
        with self._goodput.Track("checkpoint_restore"):
          state, step = self._checkpointer.Restore(
              self._PlaceState(self._CreateTrainState()))
        continue
      step = int(jax.device_get(state.step))
      state = self._MaybePrune(state, step)
      self._ExportMetrics(step, results)
      # trial reporting: eval AND decode program metrics; NaN train loss ->
      # report infeasible and stop (ref _RunLoop NaN-under-Vizier handling).
      # Multi-task schedules key results 'train_<task>', so scan them all.
      import math as _math
      nan_loss = any(
          isinstance(r, dict) and "loss" in r
          and not _math.isfinite(r["loss"])
          for name, r in results.items() if name.startswith("train"))
      if nan_loss:
        self._trial.ReportDone(infeasible=True, reason="nan_loss")
        self._trial_done = True
        if self._mlperf is not None:
          self._mlperf.Print(self._mllog.RUN_STOP,
                             metadata={"status": "aborted",
                                       "reason": "nan_loss"})
          self._mlperf.Close()
          self._mlperf = None
        print("[executor] NaN/Inf train loss: reporting trial infeasible "
              "and stopping", flush=True)
        break
      stop_requested = False
      for name, r in results.items():
        if isinstance(r, dict) and name.startswith(("eval", "decode")):
          stop_requested |= bool(
              self._trial.ReportEvalMeasure(step, r))
      if stop_requested or self._trial.ShouldStop():
        print(f"[executor] trial requested early stop at step {step}",
              flush=True)
        break
      if self._mlperf is not None:
        self._mlperf.Print(self._mllog.BLOCK_STOP,
                           metadata={"step": step})
        for name, r in results.items():
          if not (isinstance(r, dict) and name.startswith("eval")):
            continue
          if "accuracy" in r:  # eval_accuracy is higher-is-better ONLY
            self._mlperf.Print(self._mllog.EVAL_ACCURACY, r["accuracy"],
                               metadata={"step": step, "program": name})
          if "loss" in r:
            self._mlperf.Print("eval_loss", r["loss"],
                               metadata={"step": step, "program": name})
      if self._early_stop is not None and self._task is not None:
        tp = self._task.p.train
        # one designated eval program feeds the plateau detector — mixing
        # datasets would compare non-comparable losses
        r = results.get(tp.early_stop_program)
        if r is not None and tp.early_stop_metric in r and (
            jax.process_index() == 0):  # single writer per history file
          self._metric_history.ConditionalAppend(step,
                                                 r[tp.early_stop_metric])
        # process 0 decides (it owns the history file; a read-write race
        # could diverge the loop and deadlock the collectives), all follow
        should_stop = (bool(self._early_stop.Stop(step))
                       if jax.process_index() == 0 else False)
        if jax.process_count() > 1:
          import numpy as _np
          from jax.experimental import multihost_utils
          should_stop = bool(multihost_utils.broadcast_one_to_all(
              _np.asarray(should_stop)))
        if should_stop:
          print(f"[executor] early stop at step {step} "
                f"(no {tp.early_stop_metric} improvement in "
                f"{tp.early_stop_window} steps)", flush=True)
          break
    # land deferred telemetry (lagging <= 1 loop) before the final save so
    # summaries/metrics are complete when FINISHED appears; the tail
    # result the lag-1 return path never surfaced still gets its metrics
    # row and NaN check here
    flushed = self._FlushPrograms()
    if flushed:
      self._ExportMetrics(step, flushed)
      import math as _math
      tail_nan = any(
          isinstance(r, dict) and "loss" in r
          and not _math.isfinite(r["loss"])
          for name, r in flushed.items() if name.startswith("train"))
      if tail_nan and not self._trial_done:
        self._trial.ReportDone(infeasible=True, reason="nan_loss")
        self._trial_done = True
        print("[executor] NaN/Inf train loss in final deferred loop: "
              "reporting trial infeasible", flush=True)
    if self._mlperf is not None:
      self._mlperf.Print(self._mllog.RUN_STOP,
                         metadata={"status": "success", "step": step})
      self._mlperf.Close()
    if not self._trial_done:
      self._trial.ReportDone()
    self._checkpointer.Save(step, state, force=True)
    self._checkpointer.Close()
    # marker for follower jobs (evaler/decoder pollers): training is over —
    # process the final checkpoint and exit instead of idling to timeout
    if jax.process_index() == 0:
      with open(os.path.join(self._checkpointer.train_dir, "FINISHED"),
                "w") as f:
        f.write(str(step))
    return state

  def _PipelinedMainLoopBody(self, state, start_step):
    """The fully pipelined main loop (pipeline_depth >= 1): infeed,
    compute, checkpointing, and cadence decisions run as independent
    pipelines.

    - Host-side step tracking: after a successful cycle the step is
      `start + cycles x StepsPerCycle()` — no `device_get(state.step)`
      on the steady-state path; the device counter is re-read only at
      the fences that already exist (restore, recovery).
    - The dispatch window lives in TrainProgram ($pipeline_depth loops'
      telemetry may be unresolved at Run exit); this loop never blocks
      on Run's stale return value.
    - Checkpoint saves snapshot on this thread and write on a background
      worker (Checkpointer.SaveAsync); restore/final-save/recovery cross
      the WaitForPendingSave barrier.
    - Cadence decisions (NaN-stop, early-stop, trial, mlperf markers)
      consume the completed-loop stream via PollCompletedResults, so they
      fire within <= pipeline_depth loops of the offending step; eval
      results are fresh (the schedule flushes the train window at eval
      boundaries) and the exit path flushes + re-runs the decisions on
      the tail (docs/pipelined_executor.md).
    """
    from lingvo_tpu.core import retry as retry_lib
    sched = self._schedule
    steps_per_cycle = int(sched.StepsPerCycle())
    step = start_step
    self._SyncHostSteps(step)
    consecutive_failures = 0
    while step < self._max_steps:
      # cadence save: ShouldSave runs inside (once — it may broadcast
      # multi-host); the orbax write overlaps the cycles dispatched below.
      # The save decision needs no telemetry, only the state reference,
      # which is consistent by construction (in-flight but ordered).
      self._checkpointer.SaveAsync(step, state)
      if self._mlperf is not None:
        self._mlperf.Print(self._mllog.BLOCK_START,
                           metadata={"step": step})
      try:
        state, run_results = self._schedule.Run(state)
        consecutive_failures = 0
      except BaseException as e:  # noqa: BLE001
        if self._mlperf is not None:
          self._mlperf.Print(self._mllog.BLOCK_STOP,
                             metadata={"step": step, "status": "error"})
        if (not retry_lib.IsTransient(e) or
            consecutive_failures >= self._max_train_retries):
          raise
        consecutive_failures += 1
        delay = min(2.0 ** consecutive_failures, 30.0)
        print(f"[executor] transient failure ({type(e).__name__}: {e}); "
              f"restoring last checkpoint and retrying "
              f"({consecutive_failures}/{self._max_train_retries}) "
              f"in {delay:.0f}s", flush=True)
        with self._goodput.Track("recovery"):
          time.sleep(delay)
          # drain the dispatch window (results straddling the failure are
          # unreliable) and restart errored infeed producers
          self._RecoverPrograms()
        with self._goodput.Track("checkpoint_restore"):
          # Restore crosses WaitForPendingSave: never read around an
          # in-flight background write
          state, step = self._checkpointer.Restore(
              self._PlaceState(self._CreateTrainState()))
        self._SyncHostSteps(step)  # fence: host arithmetic re-seeds here
        continue
      step += steps_per_cycle
      state = self._MaybePrune(state, step)
      # telemetry-driven cadence: decisions run over loops that COMPLETED
      # (each exactly once, <= pipeline_depth stale), plus this cycle's
      # inline eval/decode results (fresh — the schedule flushed the train
      # window before running them). Run's returned train result is the
      # same stream lagged, so it is deliberately ignored here.
      completed = []
      for name, r in (run_results or {}).items():
        if isinstance(r, dict) and not name.startswith("train"):
          completed.append((name, r))
      for prog in self._SchedulePrograms():
        poll = getattr(prog, "PollCompletedResults", None)
        if not callable(poll):
          continue
        name = getattr(getattr(prog, "p", None), "name", "") or "train"
        for r in poll():
          completed.append((name, r))
      if self._CadenceDecisions(step, completed):
        break
      if self._mlperf is not None:
        self._mlperf.Print(self._mllog.BLOCK_STOP,
                           metadata={"step": step})
    # exit: land every in-flight loop, then run the SAME cadence pass over
    # the tail so the final metrics/NaN/trial state is complete before the
    # force save (the staleness contract's "complete final flush")
    self._FlushPrograms()
    tail = []
    for prog in self._SchedulePrograms():
      poll = getattr(prog, "PollCompletedResults", None)
      if not callable(poll):
        continue
      name = getattr(getattr(prog, "p", None), "name", "") or "train"
      for r in poll():
        tail.append((name, r))
    if tail:
      self._CadenceDecisions(step, tail)
    if self._mlperf is not None:
      self._mlperf.Print(self._mllog.RUN_STOP,
                         metadata={"status": "success", "step": step})
      self._mlperf.Close()
    if not self._trial_done:
      self._trial.ReportDone()
    # synchronous force save (barriers on any pending async write first)
    self._checkpointer.Save(step, state, force=True)
    self._checkpointer.Close()
    if jax.process_index() == 0:
      with open(os.path.join(self._checkpointer.train_dir, "FINISHED"),
                "w") as f:
        f.write(str(step))
    return state

  def _CadenceDecisions(self, step: int, completed: list) -> bool:
    """One telemetry-driven cadence pass (pipelined loop): exports metric
    rows, then NaN-stop, trial reporting, mlperf eval markers, early stop.
    `completed` is [(program name, result dict)] — train rows carry their
    own `at_step` (host-tracked), eval rows belong to the current `step`.
    Returns True when the main loop must stop."""
    import math as _math
    rows: dict[int, dict] = {}
    for name, r in completed:
      at = (int(r["at_step"]) if isinstance(r, dict) and "at_step" in r
            else step)
      rows.setdefault(at, {})[name] = r
    for at in sorted(rows):
      self._ExportMetrics(at, rows[at])
    nan_loss = any(
        isinstance(r, dict) and "loss" in r
        and not _math.isfinite(r["loss"])
        for name, r in completed if name.startswith("train"))
    if nan_loss:
      if not self._trial_done:
        self._trial.ReportDone(infeasible=True, reason="nan_loss")
        self._trial_done = True
      if self._mlperf is not None:
        self._mlperf.Print(self._mllog.RUN_STOP,
                           metadata={"status": "aborted",
                                     "reason": "nan_loss"})
        self._mlperf.Close()
        self._mlperf = None
      print("[executor] NaN/Inf train loss: reporting trial infeasible "
            "and stopping", flush=True)
      return True
    stop_requested = False
    for name, r in completed:
      if isinstance(r, dict) and name.startswith(("eval", "decode")):
        stop_requested |= bool(self._trial.ReportEvalMeasure(step, r))
    if stop_requested or self._trial.ShouldStop():
      print(f"[executor] trial requested early stop at step {step}",
            flush=True)
      return True
    if self._mlperf is not None:
      for name, r in completed:
        if not (isinstance(r, dict) and name.startswith("eval")):
          continue
        if "accuracy" in r:  # eval_accuracy is higher-is-better ONLY
          self._mlperf.Print(self._mllog.EVAL_ACCURACY, r["accuracy"],
                             metadata={"step": step, "program": name})
        if "loss" in r:
          self._mlperf.Print("eval_loss", r["loss"],
                             metadata={"step": step, "program": name})
    if self._early_stop is not None and self._task is not None:
      tp = self._task.p.train
      for name, r in completed:
        if name != tp.early_stop_program:
          continue
        if (isinstance(r, dict) and tp.early_stop_metric in r
            and jax.process_index() == 0):  # single history writer
          self._metric_history.ConditionalAppend(step,
                                                 r[tp.early_stop_metric])
      should_stop = (bool(self._early_stop.Stop(step))
                     if jax.process_index() == 0 else False)
      if jax.process_count() > 1:
        import numpy as _np
        from jax.experimental import multihost_utils
        should_stop = bool(multihost_utils.broadcast_one_to_all(
            _np.asarray(should_stop)))
      if should_stop:
        print(f"[executor] early stop at step {step} "
              f"(no {tp.early_stop_metric} improvement in "
              f"{tp.early_stop_window} steps)", flush=True)
        return True
    return False

  def _ExportMetrics(self, step: int, results: dict[str, Any]):
    if jax.process_index() != 0:
      return
    path = os.path.join(self._logdir, "metrics.jsonl")
    with open(path, "a") as f:
      f.write(json.dumps({"step": step, **results}, default=float) + "\n")
    summary = {k: v.get("loss", v.get("steps_per_second"))
               for k, v in results.items() if isinstance(v, dict)}
    print(f"[executor] step={step} " +
          " ".join(f"{k}={v:.4g}" for k, v in summary.items()
                   if v is not None), flush=True)
