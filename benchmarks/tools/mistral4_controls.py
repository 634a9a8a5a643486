#!/usr/bin/env python3
"""Controls of `correct` for a cell whose stack is latent attention (MLA)
over a paged latent pool and an expert layer that holds a share of the
experts its router scores. Each breaks ONE thing of the served program, in
the engine's own step program and in the probe's alike, while the reference
keeps what the file says:

  fp8_latent      a token's latent row is rounded to fp8 e4m3 before it is
                  written to the pool
  fp8_mla         every latent-attention weight (W_qa, W_qb, W_kva, W_kvb,
                  W_o) rounded to fp8 e4m3, on the host
  wrong_page      every row's first logical page is the trash page in every
                  layer's table: a page of the pool not read
  unrotated_k_r   the shared rotary key is written as projected, unrotated
  no_a_of_t       the query's position scale a(t) is left out (shows in a
                  probed row past the original window, 8,192)
  shifted_share   the held run of experts is taken one further on (experts
                  1-32 for 0-31)
  none            nothing: a sound run

  python3 benchmarks/tools/mistral4_controls.py --workload <cell> --seed <n> \\
      --control <name> [--seconds 10] [--weights '{"router_scale": 4}']

`--weights` replaces the configuration's `weights` for this run (both sides
get the same). One run, in this process, through run.py's own path; the last
line of standard output is that run's line with `"control"` in it, and the
run's `correct_detail` note before it. Exit code 0 when a control came out
not correct (or `none` correct), else 1.

`--routing` runs no cell: it makes the cell's weights from the seed, sends
`--tokens` random tokens through the model's own forward a layer at a time
(bf16, the expanded form) and prints, a layer, the stream's rms where the
router reads it, the spread of the router's logits, the largest softmax
weight a token gives one expert, the share of (token, expert) pairs the held
run gets and the fullest held expert over the mean: what `weights` has to
make neither uniform nor collapsed.
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

CONTROLS = ("none", "fp8_latent", "fp8_mla", "wrong_page", "unrotated_k_r",
            "no_a_of_t", "shifted_share")


def _Patch(obj, name, new):
  _PATCHED.append((obj, name, getattr(obj, name)))
  setattr(obj, name, new)


def _ServedWeights():
  """Every ServingLoop serves the seed's weights with the MLA layers' rounded
  to fp8 (the reference keeps the seed's)."""
  import jax
  import jax.numpy as jnp
  import ml_dtypes
  import numpy as np
  from lingvo_tpu.serving import engine as engine_lib
  inner = engine_lib.ServingLoop.__init__

  def _Fp8(x):
    host = np.asarray(x).astype(np.float32).astype(ml_dtypes.float8_e4m3fn)
    return jnp.asarray(host.astype(np.float32).astype(
        np.asarray(x[:0]).dtype))

  def _Init(self, task, theta, *args, **kw):
    def _Leaf(path, x):
      keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
      if "atten" in keys and keys[-1].startswith("w_"):
        return _Fp8(x)
      return x

    inner(self, task, jax.tree_util.tree_map_with_path(_Leaf, theta),
          *args, **kw)

  _Patch(engine_lib.ServingLoop, "__init__", _Init)


def _Break(control: str):
  import jax.numpy as jnp
  from lingvo_tpu.core import mla
  from lingvo_tpu.core import moe
  from lingvo_tpu.core import transformer
  from lingvo_tpu.ops import run_write
  if control == "fp8_latent":
    write = run_write.WriteRowRuns
    _Patch(run_write, "WriteRowRuns", lambda pool, new, pages, runs: write(
        pool, new.astype(jnp.float8_e4m3fn).astype(new.dtype), pages, runs))
  elif control == "wrong_page":
    step = transformer.RepeatedTransformerLayer.RaggedStep

    def _Step(self, theta, inputs, cached_states, block_tables, rows, **kw):
      trash = cached_states.body.self_atten.latent.shape[1] - 1
      return step(self, theta, inputs, cached_states,
                  block_tables.at[:, 0].set(trash), rows, **kw)

    _Patch(transformer.RepeatedTransformerLayer, "RaggedStep", _Step)
  elif control == "unrotated_k_r":
    rotate = mla.RotateInterleaved
    # the shared key is [B, T, R]; a head's query part [B, T, N, R]
    _Patch(mla, "RotateInterleaved", lambda x, pos, freq: (
        x if x.ndim == 3 else rotate(x, pos, freq)))
  elif control == "no_a_of_t":
    _Patch(mla.MultiHeadLatentAttention, "_QueryScale",
           lambda self, pos: jnp.full(jnp.shape(pos), self._scale,
                                      jnp.float32))
  elif control == "shifted_share":
    route = moe.DroplessMoELayer._Route

    def _Route(self, th, logits):
      idx, w = route(self, th, logits)
      return idx - 1, w

    _Patch(moe.DroplessMoELayer, "_Route", _Route)


def _Routing(cell: dict, seed: int, tokens: int, weights: dict | None) -> int:
  import jax
  import jax.numpy as jnp
  import numpy as np
  from benchmarks.harness import model as model_lib
  sizes = cell["config"]
  mp = model_lib.ModelParams(sizes, num_layers=sizes["num_layers"],
                             flash=False, remat_policy=None, input_seed=seed)
  task = model_lib.Instantiate(mp.task)
  reference = importlib.import_module(
      "benchmarks.references." + sizes["reference"])
  weights = sizes["weights"] if weights is None else weights

  def _Init(key):
    theta = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        task.InstantiateVariables(key))
    return reference.SeededWeights(theta, **weights)

  theta = jax.jit(_Init)(jax.random.PRNGKey(seed % (2**31)))
  ids = jnp.asarray(np.random.RandomState(seed % (2**32)).randint(
      1, sizes["vocab_size"], (1, tokens)), jnp.int32)
  body = task.stack.body
  ff = body.fflayer
  k, held, first = (ff.p.num_experts_per_token, ff.num_held,
                    ff.p.first_expert)

  @jax.jit
  def _Layer(theta_i, x):
    h, _ = body.self_atten.FProp(theta_i.self_atten, x)
    normed = ff.ln.FProp(theta_i.fflayer.ln, h.astype(jnp.float32))
    logits = jnp.einsum("btd,de->bte", normed,
                        theta_i.fflayer.w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)[0]
    probs = jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(logits, k)
    counts = jnp.bincount(idx.reshape(-1), length=logits.shape[-1])
    mine = counts[first:first + held]
    stats = dict(
        stream_rms=jnp.sqrt(jnp.mean(jnp.square(h.astype(jnp.float32)))),
        logit_std=jnp.mean(jnp.std(logits, -1)),
        top_weight=jnp.mean(jnp.max(probs, -1)),
        top_k_mass=jnp.mean(jnp.sum(jax.lax.top_k(probs, k)[0], -1)),
        held_pair_share=jnp.sum(mine) / jnp.sum(counts),
        held_max_over_mean=jnp.max(mine) / jnp.maximum(jnp.mean(mine), 1e-9),
        experts_with_a_token=jnp.sum(counts > 0))
    return body.fflayer.FProp(theta_i.fflayer, h), stats

  x = task.emb.EmbLookup(theta.emb, ids)
  print(json.dumps({"note": "routing", "weights": weights, "tokens": tokens,
                    "embedding_rms": float(jnp.sqrt(jnp.mean(jnp.square(
                        x.astype(jnp.float32)))))}), flush=True)
  for i in range(sizes["num_layers"]):
    theta_i = jax.tree_util.tree_map(lambda a: a[i], theta.stack.body)
    x, stats = _Layer(theta_i, x)
    print(json.dumps({"layer": i, **{n: round(float(v), 4)
                                     for n, v in stats.items()}}), flush=True)
  return 0


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--control", choices=CONTROLS, default="none")
  ap.add_argument("--seconds", type=float, default=10.0)
  ap.add_argument("--weights", default="")
  ap.add_argument("--routing", action="store_true")
  ap.add_argument("--tokens", type=int, default=1024)
  ap.add_argument("--rehearse", action="store_true")
  ap.add_argument("--out", default=os.path.join(ROOT, "bench_out", "control"))
  args = ap.parse_args(argv)
  if args.rehearse:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
  from benchmarks import run as run_mod
  from benchmarks.harness import spec

  cell = spec.Cell(spec.LoadBenchmark(), args.workload)
  if args.routing:
    return _Routing(cell, args.seed, args.tokens,
                    json.loads(args.weights) if args.weights else None)
  reference = importlib.import_module(
      "benchmarks.references." + cell["config"]["reference"])
  if args.weights:
    weights, seeded = json.loads(args.weights), reference.SeededWeights
    _Patch(reference, "SeededWeights",
           lambda theta, **_: seeded(theta, **weights))
  if args.control == "fp8_mla":
    _ServedWeights()
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
