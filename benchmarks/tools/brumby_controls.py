#!/usr/bin/env python3
"""Controls of `correct` for a cell whose stack is power-retention layers
(slot state beside the open chunk's pages). Each changes ONE thing, in the
engine's own step program and in the probe's alike, while the other side
keeps what the file says:

  fp8_mixers      every mixer's floating weights (the q, k, v, gate and
                  output projections and the heads' norms) rounded to fp8
                  e4m3, on the host (control.py says why): the nearest
                  precision below the file's bf16
  bf16_state      the slot state is kept in bf16: every tile of S and z the
                  fold's kernel writes is rounded to bf16 as it is written
                  (on the chip; the XLA form a CPU runs is left sound).
                  NOT seen by brumby14b_serve_longwrite's `correct` (0.0461
                  beside a sound 0.0465, PR 47: its rounding is of the size
                  of what the bf16 stream carries already, PERF.md 7(u)), so
                  this control exits 1 there; tests/test_brumby.py holds the
                  state's f32 on the CPU
  no_fold         one page in eight (logical pages 3, 11, 19, ...) is not
                  folded into the state: its decay is applied, its tokens
                  are lost
  no_reset        a slot's state is not zeroed where a row starts a request:
                  a reused slot carries on from what its last occupant left
  one_gate        every KV head of a layer decays at the mean of the layer's
                  gate projections
  degree1         the REFERENCE weighs by the first power of the score (the
                  served program cannot: its feature map is the second's)
  no_normaliser   the REFERENCE leaves the sum of the weights out
  none            nothing: a sound run

  python3 benchmarks/tools/brumby_controls.py --workload <cell> --seed <n> \\
      --control <name> [--seconds 10] [--weights '{"gate_offset": 5}']

`--weights` replaces the configuration's `weights` for this run (both sides
get the same). One run, in this process, through run.py's own path; the last
line of standard output is that run's line with `"control"` in it, and the
run's `correct_detail` note before it. Exit code 0 when a control came out
not correct (or `none` correct), else 1.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import importlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

_PATCHED = []      # (object, attribute, what it was): undone when main ends

CONTROLS = ("none", "fp8_mixers", "bf16_state", "no_fold", "no_reset",
            "one_gate", "degree1", "no_normaliser")
_STATED = {"degree1": {"degree": 1}, "no_normaliser": {"normalise": False}}


def _Patch(obj, name, new):
  _PATCHED.append((obj, name, getattr(obj, name)))
  setattr(obj, name, new)


def _ServedWeights(control: str):
  """Every ServingLoop serves the seed's weights with the control's change
  (the reference keeps the seed's)."""
  import jax
  import jax.numpy as jnp
  import ml_dtypes
  import numpy as np
  from lingvo_tpu.serving import engine as engine_lib
  inner = engine_lib.ServingLoop.__init__

  def _Fp8(x):
    # saturating: e4m3 has no infinity, and a value past 448 would be a NaN
    top = float(ml_dtypes.finfo(ml_dtypes.float8_e4m3fn).max)
    host = np.clip(np.asarray(x).astype(np.float32), -top, top).astype(
        ml_dtypes.float8_e4m3fn)
    return jnp.asarray(host.astype(np.float32).astype(
        np.asarray(x[:0]).dtype))

  def _Init(self, task, theta, *args, **kw):
    def _Leaf(path, x):
      keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
      floating = jnp.issubdtype(x.dtype, jnp.floating)
      if control == "fp8_mixers" and "atten" in keys and floating:
        return _Fp8(x)
      if control == "one_gate" and keys[-1] == "w_gate":
        return jnp.broadcast_to(jnp.mean(x.astype(jnp.float32), -1,
                                         keepdims=True), x.shape
                                ).astype(x.dtype)
      return x

    inner(self, task, jax.tree_util.tree_map_with_path(_Leaf, theta),
          *args, **kw)

  _Patch(engine_lib.ServingLoop, "__init__", _Init)


def _Break(control: str):
  import jax.numpy as jnp
  from lingvo_tpu.ops import power_retention as op
  if control == "no_reset":
    _Patch(op, "BuildStepPlan",
           functools.partial(op.BuildStepPlan, reset=False))
  elif control == "no_fold":
    operands = op._FoldOperands

    def _Operands(plan, pool, span_table, page):
      kf, va, w = operands(plan, pool, span_table, page)
      lost = ((plan.j0[plan.e_row] + plan.e_jj) % 8 == 3)
      return kf, jnp.where(lost[:, None, None, None], 0.0, va), w

    _Patch(op, "_FoldOperands", _Operands)
  elif control == "bf16_state":
    # inside the fold's kernel, where the state is written: a pass over the
    # slots afterwards would move 0.6 GB a layer a step and the window would
    # not open. The XLA form (a CPU's) is left as it is.
    kernel = op._FoldKernel

    def _Kernel(*refs, **kw):
      kernel(*refs, **kw)
      for out in refs[-2:]:
        out[...] = out[...].astype(jnp.bfloat16).astype(jnp.float32)

    _Patch(op, "_FoldKernel", _Kernel)


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--control", choices=CONTROLS, default="none")
  ap.add_argument("--seconds", type=float, default=10.0)
  ap.add_argument("--weights", default="")
  ap.add_argument("--rehearse", action="store_true")
  ap.add_argument("--out", default=os.path.join(ROOT, "bench_out", "control"))
  args = ap.parse_args(argv)
  if args.rehearse:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
  from benchmarks import run as run_mod
  from benchmarks.harness import spec

  cell = spec.Cell(spec.LoadBenchmark(), args.workload)
  reference = importlib.import_module(
      "benchmarks.references." + cell["config"]["reference"])
  stated = _STATED.get(args.control, {})
  if args.weights or stated:
    seeded = reference.SeededWeights
    weights = json.loads(args.weights) if args.weights else None
    _Patch(reference, "SeededWeights", lambda theta, **kw: seeded(
        theta, **(kw if weights is None else weights), **stated))
  if args.control in ("fp8_mixers", "one_gate"):
    _ServedWeights(args.control)
  elif args.control not in stated and args.control != "none":
    _Break(args.control)
  run_args = argparse.Namespace(
      workload=args.workload, seed=args.seed, seconds=args.seconds, trace=0,
      rehearse=args.rehearse, out=args.out, traffic_override="")
  out = io.StringIO()
  try:
    with contextlib.redirect_stdout(out):
      rc = run_mod._Run(run_args)
  finally:
    while _PATCHED:
      obj, name, old = _PATCHED.pop()
      setattr(obj, name, old)
  if rc != 0:
    sys.stdout.write(out.getvalue())
    return 2
  lines = out.getvalue().strip().splitlines()
  for text in lines[:-1]:
    if '"correct_detail"' in text or '"serve_tok_s_between' in text:
      print(text, flush=True)
  line = json.loads(lines[-1])
  line["control"] = args.control
  if args.weights:
    line["weights"] = json.loads(args.weights)
  print(json.dumps(line), flush=True)
  return 0 if line["correct"] == (args.control == "none") else 1


if __name__ == "__main__":
  sys.exit(main())
