"""A train cell: the trainer's program loop (TrainProgram with async infeed
and the pipelined dispatch window, as ExecutorTpu drives it), timed as a whole
number of loops, each loop's completion one reading. train_tok_s is all the
window's tokens over all its time; the median of the loop readings and their
scatter are per-layer metrics."""

from __future__ import annotations

import importlib
import math
import os
import statistics
import time

from benchmarks.harness import device
from benchmarks.harness import flops as flops_lib
from benchmarks.harness import model as model_lib
from benchmarks.harness import readings


_WARMUP_MIN_LOOPS = 3
_WARMUP_MAX_LOOPS = 12
_WARMUP_AGREE = 0.01
_MIN_READINGS = 10
_TRACE_TAIL_S = 6.0       # a traced run traces the window's last seconds


def _Mesh(sizes: dict, chips: int):
  if not sizes.get("mesh"):
    return None
  import jax
  from lingvo_tpu.parallel import mesh as mesh_lib
  shape = dict(sizes["mesh"])
  if math.prod(shape.values()) != chips:
    raise ValueError(f"mesh {shape} does not span the cell's {chips} chips")
  return mesh_lib.MakeMesh(shape, devices=jax.devices()[:chips])


def Run(ctx) -> dict:
  import jax
  from jax.sharding import PartitionSpec
  from lingvo_tpu.parallel import mesh as mesh_lib
  from lingvo_tpu.runners import program as program_lib

  cell, traffic = ctx.cell, ctx.cell["traffic"]
  sizes = model_lib.Sizes(cell["config"], ctx.rehearse)
  chips = cell["chips"]
  layers = sizes["train_num_layers"]
  mp = model_lib.ModelParams(
      sizes, num_layers=layers, flash=traffic["flash_attention"],
      remat_policy=traffic["remat_policy"], input_seed=ctx.seed)
  task = model_lib.Instantiate(mp.task)
  mesh = _Mesh(sizes, chips)
  steps_per_loop = int(traffic["steps_per_loop"])
  tokens_per_loop = steps_per_loop * sizes["batch_size"] * sizes["seq_len"]

  logdir = os.path.join(ctx.out_dir, "train_logdir")
  os.makedirs(logdir, exist_ok=True)
  train_p = program_lib.TrainProgram.Params().Set(
      task=mp.task, logdir=logdir, steps_per_loop=steps_per_loop,
      write_tensorboard=False)
  shard_fn = None
  if mesh is not None:
    fsdp = sizes.get("fsdp_axis")
    shard_fn = lambda st: mesh_lib.TrainStateShardings(  # noqa: E731
        mesh, task, st, fsdp_axis=fsdp)
    train_p.Set(mesh=mesh, input_sharding=PartitionSpec("data"),
                state_sharding_fn=shard_fn)
  prog = program_lib.TrainProgram(train_p, task=task)

  reference = importlib.import_module(
      "benchmarks.references." + cell["config"]["reference"])

  def _Create(key):
    state = task.CreateTrainState(key)
    state.theta = reference.SeededWeights(state.theta,
                                          **cell["config"]["weights"])
    return state

  # weights and optimizer state on the device from --seed, one program
  key = jax.random.PRNGKey(ctx.seed % (2**31))
  if shard_fn is None:
    state = jax.jit(_Create)(key)
  else:
    shardings = shard_fn(jax.eval_shape(_Create, key))
    state = jax.jit(_Create, out_shardings=shardings)(key)
  device.Fence(state)
  ctx.Note("state_ready_s", time.perf_counter() - ctx.t_process)

  completions: list[float] = []
  prog.SetLoopDoneCallback(lambda: completions.append(time.perf_counter()))
  results: list[dict] = []
  dispatched = 0

  def _OneLoop():
    nonlocal state, dispatched
    state, _ = prog.Run(state)
    dispatched += 1
    results.extend(prog.PollCompletedResults())

  try:
    # warm-up: the first loop compiles; then at least three more, until two
    # consecutive loop intervals agree to 1%, up to a cap
    while True:
      _OneLoop()
      warm = readings.Intervals(completions)
      if len(warm) >= _WARMUP_MAX_LOOPS or readings.WarmedUp(
          warm, _WARMUP_MIN_LOOPS, _WARMUP_AGREE):
        break
    start = len(completions) - 1     # the last warm-up loop: the clock's zero
    warm_loop_s = statistics.median(warm[-2:])
    n_loops = readings.LoopsForWindow(ctx.seconds, warm_loop_s, _MIN_READINGS)
    ctx.Note("warmup_loops", start)
    ctx.Note("warmup_intervals_s", [round(x, 5) for x in warm])
    ctx.Note("window_loops", n_loops)
    ctx.SetupEnds(completions[start])
    last = start + n_loops           # index of the window's last completion

    if ctx.trace:
      # trace the window's last loops: whole steps, and stop_trace's own
      # seconds fall after the window
      n_traced = min(n_loops, max(2, math.ceil(_TRACE_TAIL_S / warm_loop_s)))
      while dispatched < last + 1 - n_traced:
        _OneLoop()
      prog.Flush()
      results.extend(prog.PollCompletedResults())
      jax.profiler.start_trace(ctx.trace_dir)
    while dispatched < last + 1:
      _OneLoop()
    prog.Flush()
    results.extend(prog.PollCompletedResults())
    device.Fence(state)
    if ctx.trace:
      jax.profiler.stop_trace()
  finally:
    prog.SetLoopDoneCallback(None)

  window = completions[start:last + 1]
  intervals = readings.Intervals(window)
  assert len(intervals) == n_loops, (len(intervals), n_loops)
  tok_s = readings.PlainTotal(intervals, tokens_per_loop, chips)
  median_tok_s = readings.MedianOfLoops(intervals, tokens_per_loop, chips)
  ctx.Note("loop_intervals_s", [round(x, 5) for x in intervals])
  ctx.Note("train_tok_s_plain_total", tok_s)
  ctx.Note("train_tok_s_median_of_loops", median_tok_s)

  run = {
      "intervals": intervals, "chips": chips,
      "loop_results": results[-n_loops:], "train_tok_s": tok_s,
      "loop_median_tok_s": median_tok_s,
      "sizes": sizes, "layers": layers,
      "flops_per_token": flops_lib.TrainFlopsPerToken(sizes, layers),
  }
  end_to_end = {"train_tok_s": tok_s}
  correct, detail = _Correct(ctx, task, reference, mesh, state, sizes)
  ctx.Note("correct_detail", detail)
  losses = [r.get("loss") for r in results if "loss" in r]
  finite = bool(losses) and all(math.isfinite(x) for x in losses)
  ctx.Note("loop_losses", [round(x, 4) for x in losses[-n_loops:]])
  prog.Shutdown()
  compared = {}
  if "max_abs_diff" in detail:
    compared = {
        "logit_max_abs_diff": {"value": detail["max_abs_diff"],
                               "limit": detail["tolerance"]},
        "loss_rel_diff": {"value": detail["loss_rel_diff"],
                          "limit": detail["loss_tolerance"]}}
  return {"run": run, "end_to_end": end_to_end,
          "correct": bool(correct and finite),
          "attempted": n_loops * steps_per_loop, "failed": 0,
          "compared": compared}


def _Correct(ctx, task, reference, mesh, state, sizes) -> tuple[bool, dict]:
  """The measured weights through the program's own forward (the flash
  kernel, bf16) against the plain reference (f32, highest precision, no
  kernel, no remat) over the same weights, on a seeded sample of rows: the
  logits over the whole vocabulary at a seeded sample of positions, and the
  loss over the rows."""
  import contextlib
  import jax
  import jax.numpy as jnp
  import numpy as np
  from lingvo_tpu.core import input_policy
  from lingvo_tpu.parallel import mesh as mesh_lib

  spec = ctx.cell["config"]["correct"]
  cap = sizes.get("logit_cap", 30.0)
  gen = input_policy.Instantiate(task.p.input.Copy().Set(
      seed=(ctx.seed + 17) % (2**31)))
  batch = gen.GetPreprocessedInputBatch()
  rng = np.random.RandomState(ctx.seed % (2**32))
  rows = np.sort(
      rng.permutation(sizes["batch_size"])[:int(spec["sample_rows"])])
  sample = batch.Transform(lambda x: jnp.asarray(np.asarray(x)[rows]))
  n, t = len(rows), sizes["seq_len"]
  flat = np.sort(rng.permutation(n * t)[:int(spec["sample_positions"])])
  at_row, at_pos = jnp.asarray(flat // t), jnp.asarray(flat % t)

  def _Program(theta, b):
    logits = task.ComputePredictions(theta, b).logits
    return task.EvalStep(theta, b)[0].loss[0], logits[at_row, at_pos]

  def _Reference(theta, b):
    logits = reference.Logits(theta, b.ids, b.segment_ids, cap)
    logp = jax.nn.log_softmax(logits, -1)
    loss = -jnp.mean(jnp.take_along_axis(logp, b.labels[..., None], -1))
    # the same with the packed documents not kept apart: how far a lost
    # segment mask moves the reference's own logits, beside the tolerance
    merged = reference.Logits(theta, b.ids, None, cap)
    return loss, logits[at_row, at_pos], merged[at_row, at_pos]

  scope = (mesh_lib.MeshContext(mesh) if mesh is not None
           else contextlib.nullcontext())
  with scope:
    got_loss, got = jax.device_get(jax.jit(_Program)(state.theta, sample))
    with jax.default_matmul_precision("highest"):
      want_loss, want, merged = jax.device_get(
          jax.jit(_Reference)(state.theta, sample))
  got_loss, want_loss = float(got_loss), float(want_loss)
  ok, detail = readings.CompareLogits(got, want,
                                      float(spec["train_logit_tol"]))
  rel = abs(got_loss - want_loss) / max(abs(want_loss), 1e-9)
  ok = ok and math.isfinite(got_loss) and math.isfinite(want_loss) and (
      rel <= float(spec["train_loss_rel_tol"]))
  detail.update(
      program_loss=got_loss, reference_loss=want_loss, loss_rel_diff=rel,
      loss_tolerance=spec["train_loss_rel_tol"], rows=[int(r) for r in rows],
      segments_merged_max_abs_diff=float(np.abs(merged - want).max()))
  return ok, detail
