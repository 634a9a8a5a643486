#!/usr/bin/env python3
"""Controls of `correct` for a cell whose stack is two-branch layers under a
residual multiplier: Mamba-2 mixers of ONE group with slot state, gated
experts weighed by a softmax over the chosen logits beside a shared one (a
share of them held), one grouped-query attention layer over pages, constants
on the embedding, the scores and the logits. Each breaks ONE thing of the
served program, in the engine's own step program and in the probe's alike,
while the reference keeps what the file says:

  no_embedding_multiplier  the embedding is not multiplied by 12
  no_residual_multiplier   a branch's output is added whole, not times 0.22
  no_attention_multiplier  scores times head size ** -0.5, not times 1/128
  no_logits_scaling        the logits are not divided by 16
  sigmoid_weights    the ten chosen experts weighed by sigmoid(logit), not by
                     the softmax over the ten
  nine_experts       a token reaches its nine best experts, not ten
  no_shared          the shared expert's output projection is zero
  gate_after_norm    the Mamba-2 layer norms first and gates after
  norm_groups        the gated norm's mean square over 8 groups of 1,024
                     channels, not over all 8,192
  no_reset           a slot's scan state and convolution tail are not zeroed
                     where a row starts a request
  tail_dropped       the convolution tail is zeroed at the start of every
                     chunk of several tokens
  fp8_mixers         every mixer's floating weights (Mamba-2, attention)
                     rounded to fp8 e4m3, on the host
  none               nothing: a sound run

  python3 benchmarks/tools/granite_controls.py --workload <cell> --seed <n> \\
      --control <name> [--seconds 10] [--weights '{"router_scale": 4}']

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

# a constant left out: the task param that holds it -> its neutral value
_MULTIPLIERS = {
    "no_embedding_multiplier": ("embedding_multiplier", 1.0),
    "no_residual_multiplier": ("residual_multiplier", 1.0),
    "no_attention_multiplier": ("atten_tpl.score_scale", None),
    "no_logits_scaling": ("logits_scaling", 1.0),
}
CONTROLS = ("none",) + tuple(_MULTIPLIERS) + (
    "sigmoid_weights", "nine_experts", "no_shared", "gate_after_norm",
    "norm_groups", "no_reset", "tail_dropped", "fp8_mixers")


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
    # on the host, and back in the weights' own dtype on the device
    host = np.asarray(x).astype(np.float32).astype(ml_dtypes.float8_e4m3fn)
    return jnp.asarray(host.astype(np.float32).astype(
        np.asarray(x[:0]).dtype))

  def _Init(self, task, theta, *args, **kw):
    def _Leaf(path, x):
      keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
      floating = jnp.issubdtype(x.dtype, jnp.floating)
      if control == "fp8_mixers" and "atten" in keys and floating:
        return _Fp8(x)
      if control == "no_shared" and keys[-1] == "w_shared_down":
        return jnp.zeros_like(x)
      return x

    inner(self, task, jax.tree_util.tree_map_with_path(_Leaf, theta),
          *args, **kw)

  _Patch(engine_lib.ServingLoop, "__init__", _Init)


def _Break(control: str):
  import jax
  import jax.numpy as jnp
  from benchmarks.harness import model as model_lib
  from lingvo_tpu.core import moe
  from lingvo_tpu.core import ssm
  if control in _MULTIPLIERS:
    lay = model_lib.LayTaskParams
    key, neutral = _MULTIPLIERS[control]

    def _Lay(tp, task_params):
      lay(tp, {**task_params, key: neutral})

    _Patch(model_lib, "LayTaskParams", _Lay)
  elif control in ("sigmoid_weights", "nine_experts"):
    def _Route(self, th, logits):
      del th
      k = self.p.num_experts_per_token
      top, idx = jax.lax.top_k(logits, k)
      if control == "sigmoid_weights":
        return idx, jax.nn.sigmoid(top)
      # the tenth pair keeps its place and weighs nothing
      w = jax.nn.softmax(top[:, :k - 1], axis=-1)
      return idx, jnp.concatenate([w, jnp.zeros_like(w[:, :1])], axis=-1)

    _Patch(moe.DroplessMoELayer, "_Route", _Route)
  elif control in ("no_reset", "tail_dropped"):
    mix = ssm.Mamba2Layer.RaggedMix

    def _Mix(self, theta, x, states, shared, rows, **kw):
      if control == "no_reset":
        rows = rows._replace(row_q_pos=jnp.maximum(rows.row_q_pos, 1))
      else:
        states = states.Copy()
        states.conv = jnp.where((rows.row_len > 1)[:, None, None], 0.0,
                                states.conv)
      return mix(self, theta, x, states, shared, rows, **kw)

    _Patch(ssm.Mamba2Layer, "RaggedMix", _Mix)
  elif control in ("gate_after_norm", "norm_groups"):
    def _GateNorm(self, th, y, z):
      p = self.p
      groups = 8 if control == "norm_groups" else p.num_groups
      lead = z.shape[:-1]
      gate = jax.nn.silu(z.astype(jnp.float32))
      v = y.reshape(lead + (self._e,))
      if control == "norm_groups":
        v = v * gate
      by_group = v.reshape(lead + (groups, self._e // groups))
      ms = jnp.mean(jnp.square(by_group), axis=-1, keepdims=True)
      normed = (by_group * jax.lax.rsqrt(ms + p.norm_epsilon)).reshape(
          lead + (self._e,)) * (1.0 + th.norm_scale.astype(jnp.float32))
      if control == "gate_after_norm":
        normed = normed * gate
      return normed.astype(self.fprop_dtype)

    _Patch(ssm.Mamba2Layer, "_GateNorm", _GateNorm)


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
  if args.control in ("fp8_mixers", "no_shared"):
    _ServedWeights(args.control)
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
