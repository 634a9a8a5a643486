#!/usr/bin/env python3
"""The control of a serving cell's `correct`: the same run with the engine
serving its weights rounded to the nearest precision below the configuration's
bf16 (fp8 e4m3), while the reference keeps what the seed made. It has to come
out as not correct: the number compared (`logit_max_abs_diff`) has to lie
over its limit, and far over what sound runs read.

  python3 benchmarks/tools/control.py --workload <serve cell> --seed <n> [--seconds 10]

One run, in this process (a chip belongs to one process), through run.py's own
path; the last line of standard output is that run's line, with
`"control": "fp8_e4m3_weights"` in it. Exit code 0 when the control failed as
it must, 1 when it passed as correct. Not run by the benchmark's own runs;
tests/benchmark/test_correct.py keeps the same at a size a test can hold.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def LowerPrecisionWeights():
  """From now on every ServingLoop serves fp8-rounded weights. Rounded on
  the host (ml_dtypes): a v5e has no fp8, and a convert to fp8 and back
  inside a jitted program left the weights as they were there (all six
  controls read 0.048-0.072, a sound run's reading; my chip runs, PR 31)."""
  import jax
  import jax.numpy as jnp
  import ml_dtypes
  import numpy as np
  from lingvo_tpu.serving import engine as engine_lib
  inner = engine_lib.ServingLoop.__init__

  def _Round(x):
    if not jnp.issubdtype(x.dtype, jnp.floating):
      return x
    host = np.asarray(x.astype(jnp.float32))
    return jnp.asarray(host.astype(ml_dtypes.float8_e4m3fn).astype(
        np.float32)).astype(x.dtype)

  def _Init(self, task, theta, *args, **kw):
    inner(self, task, jax.tree_util.tree_map(_Round, theta), *args, **kw)

  engine_lib.ServingLoop.__init__ = _Init


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, default=10.0)
  ap.add_argument("--rehearse", action="store_true")
  ap.add_argument("--out", default=os.path.join(ROOT, "bench_out", "control"))
  args = ap.parse_args(argv)
  from benchmarks import run as run_mod
  if args.rehearse:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
  LowerPrecisionWeights()
  run_args = argparse.Namespace(
      workload=args.workload, seed=args.seed, seconds=args.seconds, trace=0,
      rehearse=args.rehearse, out=args.out, traffic_override="")
  out = io.StringIO()
  with contextlib.redirect_stdout(out):
    rc = run_mod._Run(run_args)
  if rc != 0:
    sys.stdout.write(out.getvalue())
    return 2
  line = json.loads(out.getvalue().strip().splitlines()[-1])
  line["control"] = "fp8_e4m3_weights"
  print(json.dumps(line), flush=True)
  return 1 if line["correct"] else 0


if __name__ == "__main__":
  sys.exit(main())
