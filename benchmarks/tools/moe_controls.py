#!/usr/bin/env python3
"""Controls of an expert cell's `correct`, and the routing laid beside the
reference's. `control.py` has the whole-model fp8 control; this one breaks
ONE thing of the served program at a time, in the engine's own step program
and in the probe's alike, while the reference keeps what the file says:

  fp8_first_experts  the three matrices of the experts of the block's first
                  layer (every repeat of it) rounded to fp8 e4m3
  fp8_but_experts every other floating weight rounded to fp8 e4m3 (the two
                  together are `control.py`'s whole-model control, which an
                  8 GB model cannot run: it holds the weights twice, the
                  seed's for the reference and the rounded ones, beside a
                  4 GB pool on a 16 GB chip; each of these holds 1.5-1.9 GB
                  twice)
  layer_offset    every layer reads the NEXT layer's experts in the stack
  window_page     a window layer sees one page less than its window
  seventh_expert  a token's sixth-best expert is replaced by its seventh
  best_expert     a token's best expert is replaced by its seventh
  none            nothing: a sound run, with the routing note below

  python3 benchmarks/tools/moe_controls.py --workload <cell> --seed <n> \
      --control <name> [--seconds 10] [--weights '{"router_scale": 1}']

Every run also notes `routing`: at each probed row's newest token, layer by
layer, whether the program's top-k experts (its own router logits, taken out
of the probe's step by a callback) are the reference's, and the reference's
margin between its k-th and (k+1)-th logit there. A logit difference that
comes with a changed set at a margin inside bf16's rounding is the
precision's; one that comes with equal sets is not. `--weights` replaces the
configuration's `weights` for this run (both sides get the same).

One run, in this process, through run.py's own path; the last line of
standard output is that run's line with `"control"` and `"routing"` in it.
Exit code 0 when a control came out not correct (or `none` correct), else 1.
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


def _Patch(obj, name, new):
  _PATCHED.append((obj, name, getattr(obj, name)))
  setattr(obj, name, new)


CONTROLS = ("none", "fp8_first_experts", "fp8_but_experts", "layer_offset",
            "window_page", "seventh_expert", "best_expert")
_EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


def _Fp8(control: str):
  import jax
  import jax.numpy as jnp
  import ml_dtypes
  import numpy as np
  from lingvo_tpu.serving import engine as engine_lib
  inner = engine_lib.ServingLoop.__init__

  def _Round(path, x):
    keys = [str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", ""))))
            for k in path]
    expert = keys[-1] in _EXPERT_MATRICES
    first = expert and keys[keys.index("x_layers") + 1] == "0"
    if not jnp.issubdtype(x.dtype, jnp.floating) or not (
        first if control == "fp8_first_experts" else not expert):
      return x
    host = np.asarray(x.astype(jnp.float32))
    return jnp.asarray(host.astype(ml_dtypes.float8_e4m3fn).astype(
        np.float32)).astype(x.dtype)

  def _Init(self, task, theta, *args, **kw):
    inner(self, task, jax.tree_util.tree_map_with_path(_Round, theta),
          *args, **kw)

  _Patch(engine_lib.ServingLoop, "__init__", _Init)


def _WindowPage():
  from lingvo_tpu.ops import ragged_block_attend as rba
  inner = rba.RaggedAttend

  def _Attend(*args, window=0, page_size, **kw):
    return inner(*args, window=max(window - page_size, 0) if window else 0,
                 page_size=page_size, **kw)

  _Patch(rba, "RaggedAttend", _Attend)


class _Experts:
  """Stands in for DroplessMoELayer._Experts: breaks the routing or the
  layer's place in the stack where a control asks, and hands the probe's
  router logits out where `capture` is set."""

  def __init__(self, control: str):
    from lingvo_tpu.core import moe
    self.control = control
    self.inner = moe.DroplessMoELayer._Experts
    self.capture = False
    self.logits = {}          # (layer path, repeat) -> [T, E]
    outer = self

    def _Call(layer_self, theta, x, logits, valid, layer=None):
      return outer(layer_self, theta, x, logits, valid, layer)

    _Patch(moe.DroplessMoELayer, "_Experts", _Call)

  def _Note(self, path, layer, logits):
    import numpy as np
    self.logits[(path, int(layer))] = np.asarray(logits)

  def __call__(self, layer_self, theta, x, logits, valid, layer):
    import functools
    import jax
    import jax.numpy as jnp
    k = layer_self.p.num_experts_per_token
    if self.control in ("seventh_expert", "best_expert"):
      order = jnp.argsort(-logits, axis=-1)
      gone = order[:, k - 1 if self.control == "seventh_expert" else 0]
      logits = logits.at[jnp.arange(logits.shape[0]), gone].set(-1e30)
    if self.capture:
      # what the experts are handed: a control's routing shows as flips
      jax.debug.callback(functools.partial(self._Note, layer_self.path),
                         jnp.asarray(0 if layer is None else layer), logits)
    if self.control == "layer_offset" and layer is not None:
      layer = (layer + 1) % theta.w_gate.shape[0]
    return self.inner(layer_self, theta, x, logits, valid, layer)


def _Routing(experts: _Experts, ref_routes, k: int) -> dict:
  """ref_routes [rows, layers, E] (the reference's, at the probed rows) ->
  per row: layers whose top-k set differs, and the reference's margins."""
  import numpy as np
  keys = sorted(experts.logits, key=lambda pk: (pk[1], pk[0]))
  prog = np.stack([experts.logits[key] for key in keys], 1)   # [T, layers, E]
  rows = []
  for want in np.asarray(ref_routes):
    # the packed token of this row: the one whose logits lie nearest (a
    # control's -1e30 held to a size whose square an f32 holds)
    t = int(np.argmin(((np.maximum(prog, -1e4) - want[None]) ** 2).sum(
        (1, 2))))
    got = prog[t]
    top_ref = np.argsort(-want, -1)
    margin = np.take_along_axis(want, top_ref[:, k - 1:k + 1], -1)
    margin = margin[:, 0] - margin[:, 1]
    flipped = [int(l) for l in range(want.shape[0])
               if set(top_ref[l, :k]) != set(np.argsort(-got[l])[:k])]
    rows.append({
        "packed_token": t, "layers_flipped": flipped,
        "margin_at_flips": [round(float(margin[l]), 5) for l in flipped],
        "margin_min": round(float(margin.min()), 5),
        "logit_std_by_layer": [round(float(x), 3) for x in want.std(-1)],
        "router_logit_max_abs_diff": round(float(np.abs(got - want).max()),
                                           5)})
  return {"rows": rows, "layers": [f"{p}@{r}" for p, r in keys]}


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
  from benchmarks.harness import serve_cell
  from benchmarks.harness import spec
  import jax

  cell = spec.Cell(spec.LoadBenchmark(), args.workload)
  reference = importlib.import_module(
      "benchmarks.references." + cell["config"]["reference"])
  if args.weights:
    weights, seeded = json.loads(args.weights), reference.SeededWeights
    _Patch(reference, "SeededWeights",
           lambda theta, **_: seeded(theta, **weights))
  if args.control.startswith("fp8_"):
    _Fp8(args.control)
  if args.control == "window_page":
    _WindowPage()
  experts = _Experts(args.control)

  # the probe's program is traced at its one call: the callback is in it
  # and in no step of the engine's own
  probe_call = serve_cell.LogitProbe._Call

  def _ProbeCall(self, name, fn, *a):
    experts.capture = name == "ragged" and self.armed.is_set() \
        and not self.done.is_set()
    try:
      return probe_call(self, name, fn, *a)
    finally:
      experts.capture = False

  _Patch(serve_cell.LogitProbe, "_Call", _ProbeCall)

  # the reference's router logits at the rows `correct` compares: taken
  # where it asks the reference for their logits (first half of the rows;
  # the second half are the one-wrong-page twins)
  routes = {}
  logits_at = reference.LogitsAt

  def _LogitsAt(theta, ids, at, *rest):
    n = ids.shape[0] // 2
    jax.debug.callback(lambda r: routes.update(ref=r),
                       reference.RouterLogitsAt(theta, ids[:n], at[:n]))
    return logits_at(theta, ids, at, *rest)

  _Patch(reference, "LogitsAt", _LogitsAt)

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
    if '"correct_detail"' in text:
      print(text, flush=True)
  line = json.loads(lines[-1])
  line["control"] = args.control
  if args.weights:
    line["weights"] = json.loads(args.weights)
  if "ref" in routes and experts.logits:
    k = cell["config"]["moe_num_active_primary_experts"]
    if args.rehearse:
      k = cell["config"]["rehearsal"]["task_params"][
          "expert_ffn_tpl.num_experts_per_token"]
    line["routing"] = _Routing(experts, routes["ref"], k)
  print(json.dumps(line), flush=True)
  sound = args.control == "none"
  return 0 if line["correct"] == sound else 1


if __name__ == "__main__":
  sys.exit(main())
