"""Trainer CLI: the operator entry point.

Re-designs `lingvo/trainer.py`: `--model` selects a registered experiment,
`--mode` picks train/eval/decode/inspect, `--logdir` receives config +
analysis + summaries. The runner/job-thread machinery of the reference
collapses into the executor (single-program SPMD: every chip runs the same
program; multi-host launches run this same binary per host).

Usage:
  python -m lingvo_tpu.trainer --model=image.mnist.LeNet5 \
      --logdir=/tmp/mnist --mode=train
  python -m lingvo_tpu.trainer --model=... --mode=inspect_model
  python -m lingvo_tpu.trainer --list_models
"""

from __future__ import annotations

import argparse
import os
import sys

from lingvo_tpu import model_registry


def _MultiHostMesh(task):
  """Default multi-host layout: data parallelism over all devices with
  ZeRO/FSDP state sharding over the same axis (model-parallel multi-host
  layouts come from experiment-provided ProgramSchedules). Returns
  (mesh, input_sharding, state_sharding_fn)."""
  import jax
  from jax.sharding import PartitionSpec
  from lingvo_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.MakeMesh({"data": jax.device_count()})
  return (mesh, PartitionSpec("data"),
          lambda state: mesh_lib.TrainStateShardings(
              mesh, task, state, fsdp_axis="data"))


def _BuildSchedule(model_params, args):
  import jax
  from lingvo_tpu.runners import program as program_lib
  task_p = model_params.task
  if task_p.input is None and model_params.input is not None:
    task_p.input = model_params.input
  cls = model_registry.GetClass(args.model)
  inst = cls()
  # Experiment-provided schedule takes precedence (ref GetProgramSchedule).
  ps = inst.ProgramSchedule()
  input_generators = {}
  train_p = program_lib.TrainProgram.Params().Set(
      task=task_p, logdir=args.logdir,
      steps_per_loop=task_p.train.tpu_steps_per_loop)
  from lingvo_tpu.core import base_model as base_model_lib
  from lingvo_tpu.core import base_model_params as bmp
  eval_programs = []
  has_decode = task_p.cls.Decode is not base_model_lib.BaseTask.Decode
  for ds in ("Test", "Dev"):
    try:
      ds_params = inst.GetDatasetParams(ds)
    except bmp.DatasetError:
      continue  # dataset genuinely not defined; real errors propagate
    ep = program_lib.EvalProgram.Params().Set(
        task=task_p, logdir=args.logdir, dataset_name=ds,
        name=f"eval_{ds.lower()}")
    from lingvo_tpu.core import input_policy
    input_generators[ds] = input_policy.Instantiate(ds_params)
    eval_programs.append(ep)
    if has_decode and ds == "Test":
      eval_programs.append(program_lib.DecodeProgram.Params().Set(
          task=task_p, logdir=args.logdir, dataset_name=ds,
          name=f"decode_{ds.lower()}"))
  if ps is None:
    ps = program_lib.SimpleProgramSchedule.Params().Set(
        train_program=train_p, eval_programs=eval_programs,
        train_executions_per_eval=args.train_executions_per_eval)
  task = None  # schedule instantiates from params
  sched_cls = ps.cls
  # Single task instance shared by all programs.
  task = task_p.Instantiate()
  task.FinalizePaths()
  if jax.process_count() > 1:
    # multi-host default: data-parallel mesh over every device, FSDP-style
    # state shardings, per-host input shards joined into global batches
    mesh, input_sharding, sharding_fn = _MultiHostMesh(task)
    for prog_p in [train_p] + eval_programs:
      prog_p.mesh = mesh
      prog_p.input_sharding = input_sharding
      prog_p.state_sharding_fn = sharding_fn
  return sched_cls(ps, task=task, input_generators=input_generators), task


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("--model", default="", help="Registered model name.")
  parser.add_argument("--logdir", default="/tmp/lingvo_tpu",
                      help="Output directory.")
  parser.add_argument("--mode", default="train",
                      choices=["train", "eval", "decode", "inspect_model",
                               "inspect_params", "export", "shell"],
                      help="What to run. 'export' writes the serving bundle "
                      "(ref --mode=write_inference_graph); 'shell' drops "
                      "into an interactive prompt with the model loaded "
                      "(ref --mode=shell ipython_kernel).")
  parser.add_argument("--export_dir", default="",
                      help="'export' output dir (default <logdir>/export).")
  parser.add_argument("--allow_fresh_init", action="store_true",
                      help="let 'export' serialize randomly initialized "
                      "weights when the logdir has no checkpoint "
                      "(default: hard error).")
  parser.add_argument("--export_int8", action="store_true",
                      help="'export' freezes matmul weights to the "
                      "per-channel int8 grid and bundles the int8+scale "
                      "artifact (theta_int8) for integer-math serving.")
  parser.add_argument("--job", default="executor_tpu",
                      help="executor_tpu (train), or evaler/decoder "
                           "(checkpoint-polling follower jobs).")
  parser.add_argument("--poll_interval_secs", type=float, default=10.0)
  parser.add_argument("--poll_timeout_secs", type=float, default=3600.0,
                      help="Follower jobs exit after this long without a "
                           "new checkpoint (also exit early when the "
                           "trainer's FINISHED marker appears).")
  # multi-host control plane (ref trainer.py:210-278 cluster_spec flags)
  parser.add_argument("--coordinator_address", default=None,
                      help="host:port of process 0 (jax.distributed).")
  parser.add_argument("--num_processes", type=int, default=None)
  parser.add_argument("--process_id", type=int, default=None)
  parser.add_argument("--mlperf_benchmark", default="",
                      help="If set, write MLPerf :::MLLOG compliance events "
                           "to <logdir>/mlperf_log.txt.")
  parser.add_argument("--max_steps", type=int, default=None,
                      help="Override task max_steps.")
  parser.add_argument("--train_executions_per_eval", type=int, default=1)
  parser.add_argument("--list_models", action="store_true")
  args = parser.parse_args(argv)

  if args.list_models:
    import lingvo_tpu.models.all_params  # noqa: F401  (populate registry)
    from lingvo_tpu import datasets as datasets_lib
    for name in sorted(model_registry.GetRegisteredModels()):
      try:
        ds = datasets_lib.GetDatasets(model_registry.GetClass(name))
      except Exception:  # noqa: BLE001 - listing must never crash
        ds = []
      print(f"{name}  [{', '.join(ds)}]" if ds else name)
    return 0

  if not args.model:
    parser.error("--model is required")

  if args.coordinator_address or args.num_processes:
    from lingvo_tpu.core import cluster
    cluster.InitDistributed(
        coordinator_address=args.coordinator_address,
        num_processes=args.num_processes, process_id=args.process_id)
  else:
    # One process only: two processes that share a cache directory load each
    # other's executables, and on the CPU's gloo collectives a program built
    # by the other rank aborts with a size mismatch (tests/test_multiprocess).
    from lingvo_tpu.core import compile_cache
    compile_cache.Configure()

  model_params = model_registry.GetParams(args.model, "Train")
  if args.max_steps is not None:
    model_params.task.train.max_steps = args.max_steps

  if args.mode == "inspect_params":
    print(model_params.ToText())
    return 0

  if args.mode == "inspect_model":
    task = model_params.task.Instantiate()
    task.FinalizePaths()
    import numpy as np
    total = 0
    for path, wp in task.VariableSpecs().FlattenItems():
      n = int(np.prod(wp.shape)) if wp.shape else 1
      total += n
      print(f"{path:<60} {str(tuple(wp.shape)):<20} {n}")
    print(f"{'TOTAL':<60} {'':<20} {total}")
    return 0

  if args.mode in ("export", "shell"):
    import jax
    from lingvo_tpu.core import checkpointer as checkpointer_lib
    task = model_params.task.Instantiate()
    task.FinalizePaths()
    state = task.CreateTrainState(jax.random.PRNGKey(1234))
    ckpt = checkpointer_lib.Checkpointer(os.path.join(args.logdir, "train"))
    step = None
    if ckpt.LatestStep() is not None:
      state, step = ckpt.Restore(state)
    ckpt.Close()
    if args.mode == "export":
      if step is None and not args.allow_fresh_init:
        print(f"no checkpoint in {args.logdir}/train — refusing to export "
              "random weights (pass --allow_fresh_init to override)",
              file=sys.stderr)
        return 1
      from lingvo_tpu.serving import export as export_lib
      out_dir = args.export_dir or os.path.join(args.logdir, "export")
      # serve what eval/decode blessed: EMA weights when the task keeps them
      theta = state.ema_theta if "ema_theta" in state else state.theta
      export_lib.InferenceGraphExporter.Export(
          task, theta, out_dir, quantize_int8=args.export_int8)
      which = "ema_theta" if "ema_theta" in state else "theta"
      print(f"exported inference bundle ({which}, ckpt step {step}) -> "
            f"{out_dir}")
      return 0
    banner = (f"lingvo_tpu shell: `task` ({type(task).__name__}), `state` "
              f"(step {step}), `model_params`, jax/jnp/np loaded")
    ns = dict(task=task, state=state, model_params=model_params, jax=jax)
    import jax.numpy as jnp
    import numpy as np
    ns.update(jnp=jnp, np=np)
    try:
      import IPython
      IPython.start_ipython(argv=[], user_ns=ns, display_banner=False)
    except ImportError:
      import code
      code.interact(banner=banner, local=ns)
    return 0

  schedule, task = _BuildSchedule(model_params, args)
  if args.mode == "train":
    from lingvo_tpu.runners import executor as executor_lib
    execu = executor_lib.ExecutorTpu(model_params, args.logdir,
                                     schedule=schedule, task=task,
                                     mlperf_benchmark=args.mlperf_benchmark)
    execu.Start()
    return 0
  if args.mode in ("eval", "decode"):
    # follower jobs never construct an executor: the trainer owns
    # trainer_params.txt / model_analysis.txt and the save-side manager
    progs = [pr for pr in schedule.programs
             if (args.mode == "eval" and "eval" in pr.p.name) or
             (args.mode == "decode" and "decode" in pr.p.name)]
    from lingvo_tpu.core import checkpointer as checkpointer_lib
    from lingvo_tpu.runners import base_runner
    if args.job in ("evaler", "decoder"):
      # checkpoint-following job (ref base_runner.py:224-298): keep polling
      # the trainer's dir and score every new checkpoint until training ends
      poller = base_runner.CheckpointPollingRunner(
          task, progs, os.path.join(args.logdir, "train"),
          poll_interval_secs=args.poll_interval_secs,
          timeout_secs=args.poll_timeout_secs)
      poller.Run()
      return 0
    import jax
    from lingvo_tpu.runners import program as program_lib
    ckpt = checkpointer_lib.Checkpointer(os.path.join(args.logdir, "train"))
    state = program_lib.PlaceStateForPrograms(
        progs, task.CreateTrainState(jax.random.PRNGKey(1234)))
    state, step = ckpt.Restore(state)
    for prog in progs:
      _, results = prog.Run(state)
      print(f"[{prog.p.name}] step={step} {results}")
    ckpt.Close()
    return 0
  return 1


if __name__ == "__main__":
  sys.exit(main())
