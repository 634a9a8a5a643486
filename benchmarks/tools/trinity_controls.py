#!/usr/bin/env python3
"""Controls of `correct` for a cell of gated, head-normed grouped attention
(window layers rotated, full layers without a position), four norms a layer,
a leading dense layer and sigmoid-routed experts beside a shared one. Each
breaks ONE thing of the served program, in the engine's own step program and
in the probe's alike, while the reference keeps what the file says:

  no_head_norm          q and k are not normed over a head's dims
  head_norm_after_rope  they are normed AFTER the rotation, not before it
  no_gate               the attend's output is not gated
  gate_after_out_proj   the gate (its first D dims) multiplies the output
                        projection's result, not its input
  full_layer_rotated    the full layer rotates q and k as a window layer does
  window_not_rotated    the window layers carry no position
  no_attention_post_norm  the attention branch's output is added un-normed
  no_ffn_post_norm      the feed-forward's (dense and experts) is
  lead_layer_experts    the leading layer's feed-forward is an expert layer
                        (the reference refuses the weights by their names:
                        the run fails, it does not read a number)
  bias_weighs           the selection bias is added to the weights too
  route_scale_1         the routed weights are not multiplied by 2.826
  no_renormalisation    the chosen scores are not divided by their sum
  seven_experts         a token reaches its seven best experts, not eight
  no_shared             the shared expert's output projection is zero
  no_embedding_scale    the embedding is not multiplied by sqrt(D)
  window_page_short     a window layer sees one page less than its window
  fp8_weights           every floating weight but the routed experts'
                        matrices rounded to fp8 e4m3, on the host (the
                        whole model twice does not fit beside its pool)
  fp8_experts           the routed experts' matrices of the LAST expert
                        layer alone rounded to fp8 e4m3
  none                  nothing: a sound run

  python3 benchmarks/tools/trinity_controls.py --workload <cell> --seed <n> \\
      --control <name> [--seconds 10] [--weights '{"router_scale": 4}']

`--weights` replaces the configuration's `weights` for this run (both sides
get the same). One run, in this process, through run.py's own path; the last
line of standard output is that run's line with `"control"` in it, and the
run's `correct_detail` note before it. Exit code 0 when a control came out
not correct (or `none` correct), else 1. tests/test_trinity.py holds each
control the CPU can show at the tiny size, through `Break`.
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

_PATCHED = []      # (object, attribute, what it was): undone by `Restore`

# a control that is a task param: the param -> the broken value (a callable
# is given the file's task params)
_TASK_PARAMS = {
    "route_scale_1": {"expert_ffn_tpl.routed_scale": 1.0},
    "no_embedding_scale": {"scale_emb_sqrt_depth": False},
    "lead_layer_experts": {"layer_kinds": lambda tp: (
        [tp["layer_kinds"][0].replace("+dense", "+experts")]
        + list(tp["layer_kinds"][1:]))},
}
_METHODS = ("no_head_norm", "head_norm_after_rope", "no_gate",
            "gate_after_out_proj",
            "full_layer_rotated", "window_not_rotated",
            "no_attention_post_norm", "no_ffn_post_norm", "bias_weighs",
            "no_renormalisation", "seven_experts", "window_page_short")
_WEIGHTS = ("no_shared", "fp8_weights", "fp8_experts")
CONTROLS = ("none",) + tuple(_TASK_PARAMS) + _METHODS + _WEIGHTS


def _Patch(obj, name, new):
  _PATCHED.append((obj, name, getattr(obj, name)))
  setattr(obj, name, new)


def Restore():
  while _PATCHED:
    obj, name, old = _PATCHED.pop()
    setattr(obj, name, old)


def BrokenTaskParams(control: str, task_params: dict) -> dict:
  """The file's task params with the control's change (a control that is
  none of them: as they are)."""
  out = dict(task_params)
  for key, value in _TASK_PARAMS.get(control, {}).items():
    out[key] = value(task_params) if callable(value) else value
  return out


def BrokenWeights(control: str, theta):
  """The seed's weights with the control's change (the reference keeps the
  seed's)."""
  import jax
  import jax.numpy as jnp
  import ml_dtypes
  import numpy as np

  def _Fp8(x):
    # on the host, and back in the weights' own dtype on the device
    host = np.asarray(x).astype(np.float32).astype(ml_dtypes.float8_e4m3fn)
    return jnp.asarray(host.astype(np.float32)).astype(x.dtype)

  blocks = sorted(k for k in theta["stack"] if k.startswith("block_"))

  def _Leaf(path, x):
    keys = [str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path]
    floating = jnp.issubdtype(x.dtype, jnp.floating)
    routed = "fflayer" in keys and keys[-1] in ("w_gate", "w_up", "w_down")
    if control == "fp8_weights" and floating and not routed:
      return _Fp8(x)
    if control == "fp8_experts" and routed and keys[1] == blocks[-1]:
      return _Fp8(x)
    if control == "no_shared" and keys[-1] == "w_shared_down":
      return jnp.zeros_like(x)
    return x

  return jax.tree_util.tree_map_with_path(_Leaf, theta)


def Break(control: str):
  """Patches the program's classes for a control that is a method's; undone
  by `Restore`."""
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.core import attention
  from lingvo_tpu.core import moe
  mha = attention.MultiHeadedAttention
  if control == "no_head_norm":
    # the variables stay (the reference reads them); the program skips them
    _Patch(mha, "_QkNorm", lambda self, theta, q, k: (q, k))
  elif control == "no_gate":
    _Patch(mha, "_Gated", lambda self, theta, x, ctx: ctx)
  elif control == "head_norm_after_rope":
    from lingvo_tpu.core import layers
    norm, rot = mha._QkNorm, layers.RotaryPositionalEmbeddingLayer.FProp
    owners = {}    # a rotating layer's rotary child -> (the layer, its theta)

    def _QkNorm(self, theta, q, k):
      if not self.p.use_rotary_position_emb:
        return norm(self, theta, q, k)       # nothing rotates: as it was
      owners[id(self.rotary)] = (self, theta)
      return q, k

    def _Rotate(self, theta, x, position=None):
      out = rot(self, theta, x, position=position)
      owner, owner_theta = owners[id(self)]
      # q has the layer's heads, k its KV heads: each its own scale
      as_q, as_k = norm(owner, owner_theta, out, out)
      return as_q if x.shape[-2] == owner.p.num_heads else as_k

    _Patch(mha, "_QkNorm", _QkNorm)
    _Patch(layers.RotaryPositionalEmbeddingLayer, "FProp", _Rotate)
  elif control == "gate_after_out_proj":
    post, gates = mha._PostProj, {}

    def _Gated(self, theta, x, ctx):
      gates[id(self)] = self._HeadsProj(theta, "gate", x)
      return ctx

    def _PostProj(self, theta, ctx):
      out = post(self, theta, ctx)
      gate = gates.pop(id(self))
      gate = gate.reshape(gate.shape[:-2] + (-1,))[..., :out.shape[-1]]
      return out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)

    _Patch(mha, "_Gated", _Gated)
    _Patch(mha, "_PostProj", _PostProj)
  elif control in ("full_layer_rotated", "window_not_rotated",
                   "window_page_short"):
    init = attention.PooledAttention.__init__

    def _Init(self, params):
      p = params.Copy()
      if control == "full_layer_rotated" and not p.window:
        p.use_rotary_position_emb = True
      elif control == "window_not_rotated" and p.window:
        p.use_rotary_position_emb = False
      elif control == "window_page_short" and p.window:
        p.window -= Break.page_size
      init(self, p)

    _Patch(attention.PooledAttention, "__init__", _Init)
  elif control in ("no_attention_post_norm", "no_ffn_post_norm"):
    from lingvo_tpu.core import layers
    fprop = layers.RmsNorm.FProp
    # a layer's own post_ln is the mixer's, its fflayer's the feed-forward's
    ffn = control == "no_ffn_post_norm"

    def _FProp(self, theta, inputs):
      if self.path.endswith("/post_ln") and ffn == self.path.endswith(
          "fflayer/post_ln"):
        return inputs
      return fprop(self, theta, inputs)

    _Patch(layers.RmsNorm, "FProp", _FProp)
  elif control in ("bias_weighs", "no_renormalisation", "seven_experts"):
    def _Route(self, th, logits):
      p = self.p
      k = p.num_experts_per_token
      scores = jax.nn.sigmoid(logits)
      biased = scores + th.router_bias.astype(jnp.float32)
      _, idx = jax.lax.top_k(biased, k)
      chosen = jnp.take_along_axis(
          biased if control == "bias_weighs" else scores, idx, axis=-1)
      if control == "seven_experts":
        # the eighth pair keeps its place and weighs nothing
        chosen = chosen.at[:, k - 1].set(0.0)
      if control != "no_renormalisation":
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
      return idx, p.routed_scale * chosen

    _Patch(moe.DroplessMoELayer, "_Route", _Route)
  else:
    assert control in ("none",) + tuple(_TASK_PARAMS) + _WEIGHTS, control


Break.page_size = 128     # `window_page_short`: the cell's page (a test's: 8)


def _Served(control: str):
  """The harness builds the broken program and serves the broken weights."""
  from benchmarks.harness import model as model_lib
  from lingvo_tpu.serving import engine as engine_lib
  if control in _TASK_PARAMS:
    lay = model_lib.LayTaskParams
    _Patch(model_lib, "LayTaskParams", lambda tp, task_params: lay(
        tp, BrokenTaskParams(control, task_params)))
  elif control in _WEIGHTS:
    inner = engine_lib.ServingLoop.__init__

    def _Init(self, task, theta, *args, **kw):
      inner(self, task, BrokenWeights(control, theta), *args, **kw)

    _Patch(engine_lib.ServingLoop, "__init__", _Init)
  else:
    Break(control)


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
  if args.rehearse:
    Break.page_size = cell["config"]["rehearsal"]["serving"]["page_size"]
  if args.control != "none":
    _Served(args.control)
  run_args = argparse.Namespace(
      workload=args.workload, seed=args.seed, seconds=args.seconds, trace=0,
      rehearse=args.rehearse, out=args.out, traffic_override="")
  out = io.StringIO()
  refused = None
  try:
    with contextlib.redirect_stdout(out):
      rc = run_mod._Run(run_args)
  except AssertionError as e:
    # the reference holds the weights' names to the file's leading dense
    # layers: a program that built another stack is refused, not compared
    rc, refused = 0, repr(e)
  finally:
    Restore()
  if refused is not None:
    print(json.dumps({"control": args.control, "correct": False,
                      "refused_by_the_reference": refused}), flush=True)
    return 0 if args.control != "none" else 1
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
