#!/usr/bin/env python3
"""Controls of `correct` for a cell of gated short-convolution layers whose
state is a tail a slot, rotated head-normed grouped attention at head size 64
(two KV heads a row of the pool), a leading dense layer and sigmoid-routed
experts with a selection bias. Each breaks ONE thing of the served program,
in the engine's own step program and in the probe's alike, while the
reference keeps what the file says:

  tail_dropped          a row of more than one token (a chunk of a prompt)
                        reads no tail: the convolution starts anew at every
                        chunk boundary
  gates_swapped         B and C change places: y = B * conv(C * X)
  taps_reversed         the taps in the other order
  no_rotation           the attention layers carry no position
  no_head_norm          q and k are not normed over a head's dims
  head_norm_after_rope  they are normed AFTER the rotation, not before it
  tile_mate             every KV head's keys and values land where its
                        tile-mate's belong (the two heads of a pool row
                        change places), so a query reads the wrong head of
                        its tile
  bias_weighs           the selection bias is added to the weights too
  no_renormalisation    the chosen scores are not divided by their sum
  three_experts         a token reaches its three best experts, not four
  fp8_weights           every floating weight but the routed experts'
                        matrices rounded to fp8 e4m3, on the host (the
                        whole model twice does not fit beside its pool)
  fp8_experts           the routed experts' matrices of the LAST expert
                        layer of each period rounded to fp8 e4m3
  none                  nothing: a sound run

  python3 benchmarks/tools/lfm2_controls.py --workload <cell> --seed <n> \\
      --control <name> [--seconds 10] [--weights '{"router_scale": 4}']
  python3 benchmarks/tools/lfm2_controls.py --workload <cell> --seed <n> \\
      --routing [--tokens 2048]

`--weights` replaces the configuration's `weights` for this run (both sides
get the same). One run, in this process, through run.py's own path; the last
line of standard output is that run's line with `"control"` in it, and the
run's `correct_detail` note before it. Exit code 0 when a control came out
not correct (or `none` correct), else 1. `--routing` serves nothing: it prints,
an expert layer, the stream's rms where the layer reads it and the spread of
its router's logits, its chosen scores and the experts a decode step of
`max_batch` tokens reaches, at the cell's seeded weights through the
reference's own functions (what `weights.router_layer_gain` is set from).
tests/test_lfm2.py holds each control the CPU can show at the tiny size,
through `Break`.
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

from benchmarks.tools import trinity_controls  # noqa: E402  (no JAX in it)

_PATCHED = []      # (object, attribute, what it was): undone by `Restore`

_METHODS = ("tail_dropped", "gates_swapped", "taps_reversed", "no_rotation",
            "no_head_norm", "head_norm_after_rope", "tile_mate",
            "bias_weighs", "no_renormalisation", "three_experts")
_WEIGHTS = ("fp8_weights", "fp8_experts")
# a control that benchmarks/tools/trinity_controls.py breaks already, under
# its name there (`seven_experts`: the last of a token's pairs weighs nothing)
_TRINITYS = {"no_head_norm": "no_head_norm",
             "head_norm_after_rope": "head_norm_after_rope",
             "bias_weighs": "bias_weighs",
             "no_renormalisation": "no_renormalisation",
             "three_experts": "seven_experts"}
CONTROLS = ("none",) + _METHODS + _WEIGHTS


def _Patch(obj, name, new):
  _PATCHED.append((obj, name, getattr(obj, name)))
  setattr(obj, name, new)


def Restore():
  while _PATCHED:
    obj, name, old = _PATCHED.pop()
    setattr(obj, name, old)
  trinity_controls.Restore()


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
  last = str(len(theta["stack"][blocks[-1]]["x_layers"]) - 1)

  def _Leaf(path, x):
    keys = [str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path]
    floating = jnp.issubdtype(x.dtype, jnp.floating)
    routed = "fflayer" in keys and keys[-1] in ("w_gate", "w_up", "w_down")
    if control == "fp8_weights" and floating and not routed:
      return _Fp8(x)
    if (control == "fp8_experts" and routed and keys[1] == blocks[-1]
        and keys[3] == last):
      return _Fp8(x)
    return x

  return jax.tree_util.tree_map_with_path(_Leaf, theta)


def Break(control: str):
  """Patches the program's classes for a control that is a method's; undone
  by `Restore`."""
  import jax.numpy as jnp
  from lingvo_tpu.core import attention
  from lingvo_tpu.core import ssm
  if control == "tail_dropped":
    fresh = ssm._FreshTail
    _Patch(ssm, "_FreshTail", lambda held, rows: jnp.where(
        (rows.row_len > 1)[:, None, None], 0.0, fresh(held, rows)))
  elif control == "gates_swapped":
    def _Gates(self, th, x):
      b, c, xx = jnp.split(jnp.einsum(
          "...d,df->...f", x, th.w_in).astype(jnp.float32), 3, axis=-1)
      return c * xx, b

    _Patch(ssm.ShortConvLayer, "_Gates", _Gates)
  elif control == "taps_reversed":
    conv = ssm._PackedConv
    _Patch(ssm, "_PackedConv", lambda u32, held_tail, conv_w, rows: conv(
        u32, held_tail, conv_w[::-1], rows))
  elif control == "no_rotation":
    init = attention.PooledAttention.__init__

    def _Init(self, params):
      init(self, params.Copy().Set(use_rotary_position_emb=False))

    _Patch(attention.PooledAttention, "__init__", _Init)
  elif control == "tile_mate":
    from lingvo_tpu.ops import run_write
    write = run_write.WriteRuns

    def _WriteRuns(k_pool, v_pool, k_new, v_new, pages, runs, **kw):
      # [T, rows, 2 H]: the two heads of a row change places
      swap = lambda x: jnp.roll(x, x.shape[-1] // 2, axis=-1)
      return write(k_pool, v_pool, swap(k_new), swap(v_new), pages, runs,
                   **kw)

    _Patch(run_write, "WriteRuns", _WriteRuns)
  elif control in _TRINITYS:
    # the head norm and the router: broken as Trinity's tool breaks them
    trinity_controls.Break(_TRINITYS[control])
  else:
    assert control in ("none",) + _WEIGHTS, control


def _Served(control: str):
  """The harness serves the broken program or the broken weights."""
  from lingvo_tpu.serving import engine as engine_lib
  if control in _WEIGHTS:
    inner = engine_lib.ServingLoop.__init__

    def _Init(self, task, theta, *args, **kw):
      inner(self, task, BrokenWeights(control, theta), *args, **kw)

    _Patch(engine_lib.ServingLoop, "__init__", _Init)
  else:
    Break(control)


def Routing(cell: dict, seed: int, tokens: int, rehearse: bool) -> dict:
  """An expert layer at a time, through the reference's own functions over
  one row of `tokens` seeded ids at the cell's seeded weights: the stream's
  rms where the layer's norm reads it, the spread (std) of its router's
  logits, the least and the median of a token's chosen scores, and the
  experts the first `max_batch` tokens reach."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  from benchmarks.harness import model as model_lib
  ref = importlib.import_module(
      "benchmarks.references." + cell["config"]["reference"])
  sizes = model_lib.Sizes(cell["config"], rehearse)
  mp = model_lib.ModelParams(sizes, num_layers=sizes["num_layers"],
                             flash=False, remat_policy=None, input_seed=seed)
  task = model_lib.Instantiate(mp.task)
  theta = jax.jit(lambda key: ref.SeededWeights(jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16)
      if jnp.issubdtype(x.dtype, jnp.floating) else x,
      task.InstantiateVariables(key)), **cell["config"]["weights"]))(
          jax.random.PRNGKey(seed % (2**31)))
  ids = jnp.asarray(np.random.RandomState(seed % (2**32)).randint(
      1, sizes["vocab_size"], size=tokens), jnp.int32)
  ref._ARCH.clear()
  ref._ARCH.update(ref._Arch(theta["emb"]["emb"].shape[1]))
  batch = sizes["serving"]["max_batch"]
  out = []

  def _Stats(ff, rep, x):
    u = ref._RmsNorm(x, ff["ln"]["scale"][rep])
    logits = u @ ref._F32(ff["w_router"][rep])
    idx, _ = ref.Route(ff, rep, u)
    chosen = jnp.take_along_axis(jax.nn.sigmoid(logits), idx, axis=-1)
    reached = jnp.zeros((logits.shape[-1],), bool).at[
        idx[:batch].reshape(-1)].set(True)
    return (jnp.sqrt(jnp.mean(jnp.square(x))), jnp.std(logits),
            jnp.min(chosen, -1).mean(), jnp.median(chosen), jnp.sum(reached),
            jnp.max(jnp.bincount(idx.reshape(-1), length=logits.shape[-1]))
            / (idx.size / logits.shape[-1]))

  with jax.default_matmul_precision("highest"):
    x = ref._F32(theta["emb"]["emb"][ids])
    n_blocks = tokens // min(ref._BLOCK, tokens)
    for layers, reps in ref._Blocks(theta):
      for rep in range(reps):
        for layer in layers:
          mixer = (ref._ShortConv if "conv_w" in layer["atten"]
                   else ref._Attention)
          x = jax.jit(mixer, static_argnums=(1, 3))(layer, rep, x, n_blocks)
          if "w_router" in layer["fflayer"]:
            stats = jax.jit(_Stats, static_argnums=1)(layer["fflayer"], rep,
                                                      x)
            out.append(dict(zip(
                ("stream_rms", "logit_std", "least_chosen_score",
                 "median_chosen_score", "experts_reached_by_a_decode_step",
                 "fullest_over_mean"), (round(float(v), 4) for v in stats))))
          x = jax.jit(ref._FeedForward, static_argnums=(1, 3))(
              layer, rep, x, n_blocks)
  return {"tokens": tokens, "expert_layers": out,
          "final_stream_rms": round(float(jnp.sqrt(jnp.mean(jnp.square(x)))),
                                    4)}


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--control", choices=CONTROLS, default="none")
  ap.add_argument("--seconds", type=float, default=10.0)
  ap.add_argument("--weights", default="")
  ap.add_argument("--routing", action="store_true")
  ap.add_argument("--tokens", type=int, default=2048)
  ap.add_argument("--rehearse", action="store_true")
  ap.add_argument("--out", default=os.path.join(ROOT, "bench_out", "control"))
  args = ap.parse_args(argv)
  if args.rehearse:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
  from benchmarks import run as run_mod
  from benchmarks.harness import spec

  cell = spec.Cell(spec.LoadBenchmark(), args.workload)
  if args.weights:
    cell["config"] = dict(cell["config"], weights=json.loads(args.weights))
  if args.routing:
    print(json.dumps({"routing": Routing(cell, args.seed, args.tokens,
                                         args.rehearse),
                      "weights": cell["config"]["weights"]}), flush=True)
    return 0
  reference = importlib.import_module(
      "benchmarks.references." + cell["config"]["reference"])
  if args.weights:
    weights, seeded = json.loads(args.weights), reference.SeededWeights
    _Patch(reference, "SeededWeights",
           lambda theta, **_: seeded(theta, **weights))
  if args.control != "none":
    _Served(args.control)
  run_args = argparse.Namespace(
      workload=args.workload, seed=args.seed, seconds=args.seconds, trace=0,
      rehearse=args.rehearse, out=args.out, traffic_override="")
  out = io.StringIO()
  try:
    with contextlib.redirect_stdout(out):
      rc = run_mod._Run(run_args)
  finally:
    Restore()
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
