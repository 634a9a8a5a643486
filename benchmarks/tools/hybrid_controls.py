#!/usr/bin/env python3
"""Controls of `correct` for a cell whose stack keeps slot state beside pages
(scan layers, differential attention, layers that read another layer's
pages). Each breaks ONE thing of the served program, in the engine's own step
program and in the probe's alike, while the reference keeps what the file
says:

  no_reset        a slot's scan state and convolution tail are not zeroed
                  where a row starts a request: a reused slot carries on from
                  what its last occupant left
  tail_dropped    the convolution tail is zeroed at the start of every chunk
                  of several tokens: a prompt's chunks do not carry it on
  cross_table     the layers that own no pages read the LAST WINDOW layer's
                  block table instead of the full layer's
  lambda_zero     every differential attention layer's lambda is 0
  wrong_page      the full layer's table has the trash page for every row's
                  first logical page (what its own table and the tables of
                  the layers that follow it read there)
  fp8_mixers      every mixer's floating weights (scan, attention, memory
                  unit) rounded to fp8 e4m3, on the host
  fp8_ffn_tail    the feed-forward weights of the stack's last block rounded
                  to fp8 e4m3 (the whole model at once does not fit beside
                  the seed's own weights, which the reference keeps: 7.7 GB
                  twice and a 4.3 GB pool on a 16 GB chip)
  none            nothing: a sound run

  python3 benchmarks/tools/hybrid_controls.py --workload <cell> --seed <n> \\
      --control <name> [--seconds 10] [--weights '{"attention_out_scale": 4}']

`--weights` replaces the configuration's `weights` for this run (both sides
get the same). One run, in this process, through run.py's own path; the last
line of standard output is that run's line with `"control"` in it, and the
run's `correct_detail` note before it. Exit code 0 when a control came out
not correct (or `none` correct), else 1.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

_PATCHED = []      # (object, attribute, what it was): undone when main ends

CONTROLS = ("none", "no_reset", "tail_dropped", "cross_table", "lambda_zero",
            "wrong_page", "fp8_mixers", "fp8_ffn_tail")


def _Patch(obj, name, new):
  _PATCHED.append((obj, name, getattr(obj, name)))
  setattr(obj, name, new)


def _Fp8(control: str):
  """Every ServingLoop serves the chosen weights rounded to fp8 (on the host:
  control.py says why)."""
  import jax
  import jax.numpy as jnp
  import ml_dtypes
  import numpy as np
  from lingvo_tpu.serving import engine as engine_lib
  inner = engine_lib.ServingLoop.__init__

  def _Round(x):
    host = np.asarray(x.astype(jnp.float32))
    return jnp.asarray(host.astype(ml_dtypes.float8_e4m3fn).astype(
        np.float32)).astype(x.dtype)

  def _Init(self, task, theta, *args, **kw):
    last = max(theta.stack.keys())                    # block_<n>, n < 10
    def _Leaf(path, x):
      keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
      mine = ("atten" in keys if control == "fp8_mixers"
              else "fflayer" in keys and last in keys and "ln" not in keys)
      floating = jnp.issubdtype(x.dtype, jnp.floating)
      return _Round(x) if mine and floating else x
    inner(self, task, jax.tree_util.tree_map_with_path(_Leaf, theta),
          *args, **kw)

  _Patch(engine_lib.ServingLoop, "__init__", _Init)


def _Break(control: str):
  import jax.numpy as jnp
  from lingvo_tpu.core import attention
  from lingvo_tpu.core import ssm
  from lingvo_tpu.core import transformer
  if control in ("no_reset", "tail_dropped"):
    step = ssm.Mamba1Layer.RaggedStep

    def _Step(self, theta, x, states, shared, rows, table=None, depth=None):
      if control == "no_reset":
        rows = rows._replace(row_q_pos=jnp.maximum(rows.row_q_pos, 1))
      else:
        states = states.Copy()
        states.conv = jnp.where((rows.row_len > 1)[:, None, None], 0.0,
                                states.conv)
      return step(self, theta, x, states, shared, rows, table, depth)

    _Patch(ssm.Mamba1Layer, "RaggedStep", _Step)
  elif control == "lambda_zero":
    lam = attention.DifferentialAttention._Lambda
    _Patch(attention.DifferentialAttention, "_Lambda",
           lambda self, th, depth: (0.0, lam(self, th, depth)[1]))
  elif control in ("cross_table", "wrong_page"):
    step = transformer.BlockSequence.RaggedStep

    def _Step(self, theta, inputs, cached_states, block_tables, rows, **kw):
      full = [k for k, w in enumerate(self.PageWindows()) if not w][-1]
      if control == "wrong_page":
        trash = cached_states.kv_pool.key.shape[0] - 1
        block_tables = block_tables.at[full, :, 0].set(trash)
        return step(self, theta, inputs, cached_states, block_tables, rows,
                    **kw)
      # the readers follow `full`'s entry of block_tables: hand them the
      # table before it for the blocks after the full layer's
      was = self._table_of
      self._table_of = [
          [("read", full - 1, None) if t and t[0] == "read" else t
           for t in tables] for tables in was]
      try:
        return step(self, theta, inputs, cached_states, block_tables, rows,
                    **kw)
      finally:
        self._table_of = was

    _Patch(transformer.BlockSequence, "RaggedStep", _Step)


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
  if args.weights:
    weights, seeded = json.loads(args.weights), reference.SeededWeights
    _Patch(reference, "SeededWeights",
           lambda theta, **_: seeded(theta, **weights))
  if args.control.startswith("fp8_"):
    _Fp8(args.control)
  elif args.control != "none":
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
