"""Executor failure recovery + multi-task training + checkpoint-polling jobs
(VERDICT r1 item 4; ref base_runner._RunLoop retry taxonomy, executor
GetExecutorParams multi-task expansion, _FindNewCheckpoint polling)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu.core import base_model
from lingvo_tpu.core import layers
from lingvo_tpu.core import learner as learner_lib
from lingvo_tpu.core import optimizer as opt_lib
from lingvo_tpu.core import retry as retry_lib
from lingvo_tpu.core import task_scheduler
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.runners import base_runner
from lingvo_tpu.runners import executor as executor_lib
from lingvo_tpu.runners import program as program_lib


class _RegressionTask(base_model.BaseTask):
  """y = 2x regression on synthetic data (ref trainer_test_utils)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("dim", 4, "")
    return p

  def __init__(self, params):
    super().__init__(params)
    self.CreateChild(
        "proj",
        layers.ProjectionLayer.Params().Set(
            input_dim=self.p.dim, output_dim=self.p.dim))

  def ComputePredictions(self, theta, input_batch):
    return self.proj.FProp(theta.proj, input_batch.x)

  def ComputeLoss(self, theta, predictions, input_batch):
    err = jnp.mean(jnp.square(predictions - input_batch.y))
    b = input_batch.x.shape[0]
    return NestedMap(loss=(err, float(b))), NestedMap()


class _RegressionInput:
  """Minimal generator protocol for TrainProgram."""

  def __init__(self, dim=4, batch=16, seed=0):
    self._rng = np.random.RandomState(seed)
    self._dim, self._batch = dim, batch

  def GetPreprocessedInputBatch(self):
    x = self._rng.randn(self._batch, self._dim).astype("float32")
    return NestedMap(x=x, y=2.0 * x)

  def GlobalBatchSize(self):
    return self._batch

  def InfeedBatchSize(self):
    return self._batch

  def __iter__(self):
    while True:
      yield self.GetPreprocessedInputBatch()


def _TaskParams(name="reg", lr=0.05, max_steps=30, steps_per_loop=5,
                save_interval=10):
  p = _RegressionTask.Params().Set(name=name, dim=4)
  p.train.learner = learner_lib.Learner.Params().Set(
      learning_rate=lr, optimizer=opt_lib.Adam.Params())
  p.train.max_steps = max_steps
  p.train.tpu_steps_per_loop = steps_per_loop
  p.train.save_interval_steps = save_interval
  return p


def _MakeScheduleAndTask(logdir, **kw):
  task_p = _TaskParams(**kw)
  task = task_p.Instantiate()
  task.FinalizePaths()
  train_p = program_lib.TrainProgram.Params().Set(
      task=task_p, logdir=logdir,
      steps_per_loop=task_p.train.tpu_steps_per_loop)
  sched_p = program_lib.SimpleProgramSchedule.Params().Set(
      train_program=train_p)
  sched = program_lib.SimpleProgramSchedule(
      sched_p, task=task, input_generators={"Train": _RegressionInput()})
  return sched, task, task_p


class TestRetryTaxonomy:

  def test_is_transient(self):
    assert retry_lib.IsTransient(RuntimeError("UNAVAILABLE: socket closed"))
    assert retry_lib.IsTransient(RuntimeError("DEADLINE_EXCEEDED"))
    assert not retry_lib.IsTransient(RuntimeError("Compilation failure: x"))
    assert not retry_lib.IsTransient(ValueError("shapes mismatch"))
    # fatal patterns win even when transient text co-occurs
    assert not retry_lib.IsTransient(
        RuntimeError("UNAVAILABLE while RESOURCE_EXHAUSTED"))

  def test_retry_decorator(self):
    calls = []

    @retry_lib.Retry(initial_delay_sec=0.01, max_retries=3)
    def flaky():
      calls.append(1)
      if len(calls) < 3:
        raise RuntimeError("UNAVAILABLE: try again")
      return "ok"

    assert flaky() == "ok"
    assert len(calls) == 3

    @retry_lib.Retry(initial_delay_sec=0.01, max_retries=3)
    def fatal():
      raise ValueError("nope")

    with pytest.raises(ValueError):
      fatal()


class TestExecutorRecovery:

  def test_transient_failure_restores_and_completes(self, tmp_path):
    """A backend death mid-run must resume from the last checkpoint."""
    logdir = str(tmp_path)
    sched, task, _ = _MakeScheduleAndTask(logdir, max_steps=30)

    real_run = sched.Run
    fail_state = {"armed": True}

    def _FlakyRun(state):
      step = int(jax.device_get(state.step))
      if fail_state["armed"] and step >= 10:
        fail_state["armed"] = False
        raise RuntimeError("UNAVAILABLE: TPU backend connection dropped")
      return real_run(state)

    sched.Run = _FlakyRun
    ex = executor_lib.ExecutorTpu(_TaskParams(), logdir, schedule=sched,
                                  task=task)
    state = ex.Start()
    assert int(jax.device_get(state.step)) == 30
    assert not fail_state["armed"]  # the failure did fire

  def test_fatal_failure_raises(self, tmp_path):
    logdir = str(tmp_path)
    sched, task, _ = _MakeScheduleAndTask(logdir)

    def _CompileError(state):
      raise RuntimeError("Compilation failure: rank mismatch")

    sched.Run = _CompileError
    ex = executor_lib.ExecutorTpu(_TaskParams(), logdir, schedule=sched,
                                  task=task)
    with pytest.raises(RuntimeError, match="Compilation failure"):
      ex.Start()

  def test_retries_exhausted_raises(self, tmp_path):
    logdir = str(tmp_path)
    sched, task, _ = _MakeScheduleAndTask(logdir)

    def _AlwaysDown(state):
      raise RuntimeError("UNAVAILABLE: connection lost")

    sched.Run = _AlwaysDown
    ex = executor_lib.ExecutorTpu(_TaskParams(), logdir, schedule=sched,
                                  task=task, max_train_retries=2)
    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
      ex.Start()


class TestMultiTaskExecutor:

  def test_two_tasks_train_with_sampled_schedule(self, tmp_path):
    logdir = str(tmp_path)
    import lingvo_tpu.core.hyperparams as hp
    task_ps = {"a": _TaskParams("a"), "b": _TaskParams("b")}
    tasks = {}
    train_programs = hp.Params()
    gens = {}
    for name, tp_ in task_ps.items():
      tasks[name] = tp_.Instantiate()
      tasks[name].FinalizePaths()
      train_programs.Define(
          name,
          program_lib.TrainProgram.Params().Set(
              task=tp_, logdir=logdir, name=f"train_{name}",
              steps_per_loop=5), "")
      gens[(name, "Train")] = _RegressionInput(seed=hash(name) % 100)
    sched_p = program_lib.MultiTaskProgramSchedule.Params().Set(
        task_schedule=task_scheduler.ConstantScheduler.Params().Set(
            task_probs=[("a", 0.5), ("b", 0.5)], seed=3),
        train_programs=train_programs)
    sched = program_lib.MultiTaskProgramSchedule(sched_p, tasks=tasks,
                                                 input_generators=gens)
    ex = executor_lib.ExecutorTpu(None, logdir, schedule=sched)
    state = ex.Start()
    steps = {n: int(jax.device_get(state.tasks.GetItem(n).step))
             for n in ("a", "b")}
    assert sum(steps.values()) >= 30
    assert steps["a"] > 0 and steps["b"] > 0  # both tasks actually sampled
    # checkpoint round-trips the combined state
    template = sched.CreateTrainState(jax.random.PRNGKey(0))
    restored, step = ex.checkpointer.Restore(template)
    assert step == sum(steps.values())


class TestCheckpointPoller:

  def test_poller_sees_new_checkpoints_and_stops(self, tmp_path):
    logdir = str(tmp_path)
    # produce a training run with checkpoints at 10/20/30
    sched, task, task_p = _MakeScheduleAndTask(logdir, max_steps=30,
                                               save_interval=10)
    ex = executor_lib.ExecutorTpu(task_p, logdir, schedule=sched, task=task)
    ex.Start()

    class _EvalProg:
      def __init__(self):
        self.p = NestedMap(name="eval_test")
        self.seen = []

      def Run(self, state):
        self.seen.append(int(jax.device_get(state.step)))
        return state, {"loss": 0.0}

    prog = _EvalProg()
    poller = base_runner.CheckpointPollingRunner(
        task, [prog], os.path.join(logdir, "train"),
        poll_interval_secs=0.1, timeout_secs=5.0)
    poller.Run()
    # the final checkpoint (step 30) must be scored; poller then exits
    assert prog.seen and prog.seen[-1] == 30


class TestTrialWiring:

  def test_trial_reports_and_stops(self, tmp_path):
    """The executor consults the Trial each cycle (ref executor trial hooks
    + base_trial.Trial): eval measures reported, early stop honored."""
    from lingvo_tpu.core import base_trial

    class CountingTrial(base_trial.NoOpTrial):
      def __init__(self):
        self.reports = []
        self.done = None

      def ReportEvalMeasure(self, step, metrics, checkpoint_path=""):
        self.reports.append((step, dict(metrics)))
        return len(self.reports) >= 2

      def ReportDone(self, infeasible=False, reason=""):
        self.done = (infeasible, reason)

    logdir = str(tmp_path)
    task_p = _TaskParams(max_steps=100, steps_per_loop=5)
    task = task_p.Instantiate()
    task.FinalizePaths()
    train_p = program_lib.TrainProgram.Params().Set(
        task=task_p, logdir=logdir, steps_per_loop=5)
    eval_p = program_lib.EvalProgram.Params().Set(
        task=task_p, logdir=logdir, name="eval_test", steps_per_loop=2)
    sched = program_lib.SimpleProgramSchedule(
        program_lib.SimpleProgramSchedule.Params().Set(
            train_program=train_p, eval_programs=[eval_p]),
        task=task,
        input_generators={"Train": _RegressionInput(),
                          "Test": _RegressionInput(seed=9)})
    trial = CountingTrial()
    ex = executor_lib.ExecutorTpu(task_p, logdir, schedule=sched, task=task,
                                  trial=trial)
    state = ex.Start()
    assert int(jax.device_get(state.step)) == 10  # stopped early, not 100
    assert len(trial.reports) == 2
    assert "loss" in trial.reports[0][1]


class TestInputBenchmark:

  def test_reports_throughput(self, tmp_path):
    task_p = _TaskParams()
    task = task_p.Instantiate()
    task.FinalizePaths()
    p = program_lib.InputBenchmarkProgram.Params().Set(
        task=task_p, logdir=str(tmp_path), steps_per_loop=10)
    prog = program_lib.InputBenchmarkProgram(
        p, task=task, input_generator=_RegressionInput())
    state = task.CreateTrainState(jax.random.PRNGKey(0))
    _, result = prog.Run(state)
    assert result["batches_per_second"] > 0
    assert result["examples_per_second"] >= result["batches_per_second"]
