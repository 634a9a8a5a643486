#!/usr/bin/env python3
"""Controls of `correct` for a cell whose stack is single-branch layers:
Mamba-2 mixers with slot state, sigmoid-routed two-matrix experts beside a
shared one, one grouped-query attention layer over pages. Each breaks ONE
thing of the served program, in the engine's own step program and in the
probe's alike, while the reference keeps what the file says:

  wrong_expert    a token's sixth-best expert (by score plus bias) is
                  replaced by its seventh
  no_shared       the shared expert's output projection is zero
  bias_weighs     the selection bias weighs as well as chooses: the weights
                  are taken from score + bias
  no_scale        the routed scaling factor (2.5) is left out
  no_reset        a slot's scan state and convolution tail are not zeroed
                  where a row starts a request: a reused slot carries on from
                  what its last occupant left
  tail_dropped    the convolution tail is zeroed at the start of every chunk
                  of several tokens: a prompt's chunks do not carry it on
  gate_after_norm the Mamba-2 layer norms first and gates after
  one_decay       every head of a Mamba-2 layer decays at the layer's mean
                  A_log
  wrong_page      the attention layer's table has the trash page for every
                  row's first logical page
  fp8_experts     the up projections of ONE expert layer's 128 experts (the
                  stack's last; 1.3 GB held twice: both matrices twice do not
                  fit beside a 12.5 GB model) rounded to fp8 e4m3, on the
                  host
  fp8_mixers      every mixer's floating weights (Mamba-2, attention) rounded
                  to fp8 e4m3, on the host
  none            nothing: a sound run

  python3 benchmarks/tools/nemotron_controls.py --workload <cell> --seed <n> \\
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

CONTROLS = ("none", "wrong_expert", "no_shared", "bias_weighs", "no_scale",
            "no_reset", "tail_dropped", "gate_after_norm", "one_decay",
            "wrong_page", "fp8_experts", "fp8_mixers")


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
    # on the host (control.py says why), and in the weights' own dtype on
    # the device: an f32 copy of a layer's experts is 2.6 GB
    host = np.asarray(x).astype(np.float32).astype(ml_dtypes.float8_e4m3fn)
    return jnp.asarray(host.astype(np.float32).astype(
        np.asarray(x[:0]).dtype))

  def _Init(self, task, theta, *args, **kw):
    last = max(k for k, v in theta.stack.items()
               if "fflayer" in v.x_layers[-1])       # block_<n>, n < 10

    def _Leaf(path, x):
      keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
      floating = jnp.issubdtype(x.dtype, jnp.floating)
      if control == "fp8_mixers" and "atten" in keys and floating:
        return _Fp8(x)
      if control == "fp8_experts" and last in keys and keys[-1] == "w_up":
        return _Fp8(x)
      if control == "no_shared" and keys[-1] == "w_shared_down":
        return jnp.zeros_like(x)
      if control == "one_decay" and keys[-1] == "a_log":
        return jnp.broadcast_to(jnp.mean(x.astype(jnp.float32), -1,
                                         keepdims=True), x.shape
                                ).astype(x.dtype)
      return x

    inner(self, task, jax.tree_util.tree_map_with_path(_Leaf, theta),
          *args, **kw)

  _Patch(engine_lib.ServingLoop, "__init__", _Init)


def _Break(control: str):
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.core import moe
  from lingvo_tpu.core import ssm
  from lingvo_tpu.core import transformer
  if control in ("wrong_expert", "bias_weighs", "no_scale"):
    route = moe.DroplessMoELayer._Route

    def _Route(self, th, logits):
      p = self.p
      if control == "no_scale":
        idx, w = route(self, th, logits)
        return idx, w / p.routed_scale
      k = p.num_experts_per_token
      scores = jax.nn.sigmoid(logits)
      biased = scores + th.router_bias.astype(jnp.float32)
      if control == "bias_weighs":
        _, idx = jax.lax.top_k(biased, k)
        chosen = jnp.take_along_axis(biased, idx, axis=-1)
      else:
        _, idx = jax.lax.top_k(biased, k + 1)
        idx = jnp.concatenate([idx[:, :k - 1], idx[:, k:]], axis=-1)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
      return idx, p.routed_scale * chosen / jnp.sum(chosen, -1, keepdims=True)

    _Patch(moe.DroplessMoELayer, "_Route", _Route)
  elif control in ("no_reset", "tail_dropped"):
    step = ssm.Mamba2Layer.RaggedStep

    def _Step(self, theta, x, states, shared, rows, table=None, depth=None,
              plan=None):
      if control == "no_reset":
        rows = rows._replace(row_q_pos=jnp.maximum(rows.row_q_pos, 1))
      else:
        states = states.Copy()
        states.conv = jnp.where((rows.row_len > 1)[:, None, None], 0.0,
                                states.conv)
      return step(self, theta, x, states, shared, rows, table, depth, plan)

    _Patch(ssm.Mamba2Layer, "RaggedStep", _Step)
  elif control == "gate_after_norm":
    def _GateNorm(self, th, y, z):
      p = self.p
      groups = p.num_groups
      lead = z.shape[:-1]
      by_group = y.reshape(lead + (groups, self._e // groups))
      ms = jnp.mean(jnp.square(by_group), axis=-1, keepdims=True)
      normed = (by_group * jax.lax.rsqrt(ms + p.norm_epsilon)).reshape(
          lead + (self._e,)) * (1.0 + th.norm_scale.astype(jnp.float32))
      return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(
          self.fprop_dtype)

    _Patch(ssm.Mamba2Layer, "_GateNorm", _GateNorm)
  elif control == "wrong_page":
    step = transformer.BlockSequence.RaggedStep

    def _Step(self, theta, inputs, cached_states, block_tables, rows, **kw):
      trash = cached_states.kv_pool.key.shape[0] - 1
      return step(self, theta, inputs, cached_states,
                  block_tables.at[:, :, 0].set(trash), rows, **kw)

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
  if args.control in ("fp8_experts", "fp8_mixers", "no_shared", "one_decay"):
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
