#!/usr/bin/env python
"""MoE performance sweep: where does the 64-expert step time go?

Runs controlled variants of the MoE bench config on the attached accelerator
and prints one JSON line per variant. The key control is the DENSE TWIN —
same dims/layers as the MoE's active path but with plain FFNs — which
separates "small-geometry MFU ceiling" from "MoE machinery overhead".

Usage: python tools/moe_sweep.py [variant ...]
Variants: dense_twin moe_b8 moe_b16 moe_b32 sinkhorn hash groups16 cap125
          einsum noflash experts8 experts16 experts32 experts64

The experts* ladder confirms the MoE scaling contract: total params grow
~linearly with the expert count while ACTIVE params/token (dense + top_k/E
of the expert weights) stay near-flat — so step time should too. Every
number is a device time: without a TPU the tool exits non-zero at once.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import bench  # noqa: E402


def _Build(jax, jnp, model_registry, **kw):
  mp = model_registry.GetParams("lm.synthetic_packed_input.MoELmTiny",
                                "Train")
  mp.task.input = mp.input
  mp.task.model_dim = 1024
  mp.task.hidden_dim = 4096
  mp.task.moe_hidden_dim = 2048
  mp.task.num_heads = 16
  mp.task.num_layers = 6
  mp.task.num_experts = 64
  mp.task.moe_num_groups = 8
  mp.task.vocab_size = 32768
  mp.task.input.vocab_size = 32768
  mp.task.input.seq_len = 1024
  mp.task.input.batch_size = 8
  mp.task.remat_policy = "dots"
  from lingvo_tpu.core import attention as attention_lib
  mp.task.atten_tpl = attention_lib.MultiHeadedAttention.Params().Set(
      use_flash_attention=True)
  mp.task.fprop_dtype = jnp.bfloat16
  for k, v in kw.items():
    if k == "batch_size":
      mp.task.input.batch_size = v
    elif k == "use_flash":
      mp.task.atten_tpl.use_flash_attention = v
    elif k == "beta1":
      mp.task.train.learner.optimizer.beta1 = v
    else:
      setattr(mp.task, k, v)
  return mp


def _Phases(jax, jnp, mp):
  """Times fwd-only, fwd+bwd, and the full train step for one config —
  separates model compute from gradient and optimizer/param-traffic cost."""
  task = mp.task.Instantiate()
  task.FinalizePaths()
  state = task.CreateTrainState(jax.random.PRNGKey(0))
  from lingvo_tpu.core import input_policy
  gen = input_policy.Instantiate(mp.input)
  batch = gen.GetPreprocessedInputBatch().Transform(jnp.asarray)

  def _LossFn(theta):
    from lingvo_tpu.core import py_utils
    with py_utils.AuxLossContext() as aux:
      metrics, _ = task.FProp(theta, batch)
    total = jnp.asarray(metrics.loss[0], jnp.float32)
    return total + sum(jnp.asarray(v, jnp.float32) for v in aux.values())

  fwd = jax.jit(_LossFn)

  def _ValAndGradNorm(th):
    v, g = jax.value_and_grad(_LossFn)(th)
    return v + 0.0, sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(g))

  fwdbwd = jax.jit(_ValAndGradNorm)
  res = {}
  for name, fn in (("fwd_ms", fwd), ("fwdbwd_ms", fwdbwd)):
    res[name] = round(bench._StepTime(
        lambda _o, fn=fn: fn(state.theta), 10) * 1e3, 2)

  step_fn = jax.jit(task.TrainStep, donate_argnums=(0,))
  holder = [state]

  def _Dispatch(_):
    holder[0], out = step_fn(holder[0], batch)
    return out

  res["train_ms"] = round(bench._StepTime(_Dispatch, 10) * 1e3, 2)
  return res


def _Micro(jax, jnp):
  """Times the MoE FFN layer's components in isolation at bench shapes:
  gating math, dispatch gather, expert FFN, full layer — fwd only."""
  from lingvo_tpu.parallel import gshard
  g, s, d, e, hdim = 8, 1024, 1024, 64, 2048
  key = jax.random.PRNGKey(0)
  x = jax.random.normal(key, (g, s, d), jnp.bfloat16)
  wg = jax.random.normal(key, (d, e), jnp.bfloat16) * 0.02
  wi = jax.random.normal(key, (e, d, hdim), jnp.bfloat16) * 0.02
  wo = jax.random.normal(key, (e, hdim, d), jnp.bfloat16) * 0.02
  c = int(s / e * 2.0)

  def _gating(a, wg, wi, wo):
    del wi, wo
    logits = jnp.einsum("GSD,DE->GSE", a, wg)
    out = gshard.Top2Gating(logits, None, 2.0, build_tensors=False)
    return out.indices, out.positions, out.gates

  def _dispatch(a, wg, wi, wo):
    del wi, wo
    gating = gshard.Top2Gating(
        jnp.einsum("GSD,DE->GSE", a, wg), None, 2.0, build_tensors=False)
    return gshard.IndexedDispatch(a, gating, e)

  ein = jnp.zeros((e, g, c, d), jnp.bfloat16)

  def _ffn_body(expert_in, wi, wo):
    h = jnp.einsum("EGCD,EDH->EGCH", expert_in, wi)
    h = jax.nn.relu(h)
    return jnp.einsum("EGCH,EHD->EGCD", h, wo)

  def _ffn(a, wg, wi, wo):
    del wg
    return _ffn_body(a, wi, wo)

  def _full(a, wg, wi, wo):
    gating = gshard.Top2Gating(
        jnp.einsum("GSD,DE->GSE", a, wg), None, 2.0, build_tensors=False)
    expert_in = gshard.IndexedDispatch(a, gating, e)
    return gshard.IndexedCombine(_ffn_body(expert_in, wi, wo), gating)

  res = {}
  for name, fn, arg in (("gating", _gating, x), ("dispatch", _dispatch, x),
                        ("ffn", _ffn, ein), ("full_layer", _full, x)):
    # scalar output; weights are explicit args because closed-over arrays
    # embed as HLO constants
    def _scalar(a, wg_, wi_, wo_, fn=fn):
      leaves = jax.tree_util.tree_leaves(fn(a, wg_, wi_, wo_))
      return sum(jnp.sum(l[..., :1].astype(jnp.float32)) for l in leaves)
    jfn = jax.jit(_scalar)
    res[f"{name}_ms"] = round(bench._StepTime(
        lambda _o, jf=jfn, a=arg: jf(a, wg, wi, wo), 20) * 1e3, 3)
  return res


def _Time(jax, jnp, mp, peak):
  task = mp.task.Instantiate()
  task.FinalizePaths()
  state = task.CreateTrainState(jax.random.PRNGKey(0))
  from lingvo_tpu.core import input_policy, py_utils
  gen = input_policy.Instantiate(mp.input)
  batch = gen.GetPreprocessedInputBatch().Transform(jnp.asarray)
  step_fn = jax.jit(task.TrainStep, donate_argnums=(0,))
  holder = [state]

  def _Dispatch(_):
    holder[0], out = step_fn(holder[0], batch)
    return out

  step = bench._StepTime(_Dispatch, 10)
  ntok = int(np.prod(batch.ids.shape))
  n_params = py_utils.CountParams(holder[0].theta)
  expert_params = sum(
      int(np.prod(np.shape(v))) for k, v in holder[0].theta.FlattenItems()
      if ".moe." in f".{k}." and k.rsplit(".", 1)[-1] in ("wi", "wo"))
  gating = getattr(mp.task, "moe_gating_policy", "top2")
  # active experts/token: 1 for top-1 routers; 2 for top2; expert_choice
  # averages capacity_factor experts per token by construction
  if gating in ("sinkhorn", "hash"):
    top_k = 1.0
  elif gating == "expert_choice":
    top_k = float(getattr(mp.task, "moe_capacity_factor", 2.0))
  else:
    top_k = 2.0
  active = (n_params - expert_params) + \
      expert_params * top_k / max(mp.task.num_experts, 1)
  if mp.task.num_experts == 0:
    active = n_params
  b, t = batch.ids.shape
  flops = 6.0 * active * ntok + 12.0 * b * t * t * mp.task.model_dim * \
      mp.task.num_layers
  return {"step_ms": round(step * 1e3, 2),
          "tok_s": round(ntok / step, 1),
          "params_m": round(n_params / 1e6, 1),
          "active_m": round(active / 1e6, 1),
          "mfu": round(flops / (step * peak), 4)}


VARIANTS = {
    "dense_twin": dict(num_experts=0, hidden_dim=4096),
    "moe_b8": dict(),
    "moe_b16": dict(batch_size=16),
    "moe_b32": dict(batch_size=32),
    "sinkhorn": dict(moe_gating_policy="sinkhorn"),
    "hash": dict(moe_gating_policy="hash"),
    "expert_choice": dict(moe_gating_policy="expert_choice"),
    "groups16": dict(moe_num_groups=16),
    "groups32": dict(moe_num_groups=32),
    "cap125": dict(moe_capacity_factor=1.25),
    "einsum": dict(moe_dispatch_method="einsum"),
    "noflash": dict(use_flash=False),
    "noremat": dict(remat_policy="none"),
    "b16_groups16": dict(batch_size=16, moe_num_groups=16),
    "dense_twin_b16": dict(num_experts=0, hidden_dim=4096, batch_size=16),
    "nomom_b8": dict(beta1=0.0),
    "nomom_b16": dict(beta1=0.0, batch_size=16),
    "nomom_b24": dict(beta1=0.0, batch_size=24),
    "moe_b24": dict(batch_size=24),
    # expert-count ladder: total params scale ~E, active params ~flat
    "experts8": dict(num_experts=8),
    "experts16": dict(num_experts=16),
    "experts32": dict(num_experts=32),
    "experts64": dict(),
}


def main():
  import gc
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.core import compile_cache
  compile_cache.Configure()
  from lingvo_tpu import model_registry
  import lingvo_tpu.models.all_params  # noqa: F401
  peak = bench._PeakFlops(bench._RequireTpu(jax))
  names = sys.argv[1:] or ["dense_twin", "moe_b8", "moe_b16"]
  for name in names:
    try:
      if name == "micro":
        res = _Micro(jax, jnp)
      elif name.startswith("phases:"):
        res = _Phases(jax, jnp,
                      _Build(jax, jnp, model_registry,
                             **VARIANTS[name.split(":", 1)[1]]))
      else:
        res = _Time(jax, jnp, _Build(jax, jnp, model_registry,
                                     **VARIANTS[name]), peak)
    except Exception as e:  # noqa: BLE001
      res = {"error": f"{type(e).__name__}: {e}"[:200]}
    print(json.dumps({"variant": name, **res}), flush=True)
    gc.collect()


if __name__ == "__main__":
  main()
