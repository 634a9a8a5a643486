#!/usr/bin/env python
"""KV-cache dtype ladder: what does an int8 KV cache actually buy?

Sweeps the KV pool storage dtype — fp32 / bf16 / int8 (with per-slot-
per-head f32 scale sidecars, docs/quantized_serving.md) — over the same
serving stack and prints one JSON line per variant with:

  - kv_bytes_per_token (scale sidecars included — the honest number, via
    quant.kv.StackKvCensus, the same census the serving engine prices its
    page pool with),
  - admitted_sequences at a fixed HBM budget (the budget = what `slots`
    fp32 sequences need at budget_seq_len). Acceptance bar: int8 admits
    >= 1.8x the sequences bf16 does at serving head dims,
  - measured decode tokens/sec through the dense-cache decode path
    (chunked Prefill + greedy ExtendStep scan with quantize-on-write /
    dequantize-on-read when int8),
  - score_delta_mean_abs: mean |delta| of teacher-forced next-token
    log-probs through the decode cache vs the fp32 variant — the decode-
    path ScoreSequences number (plain ScoreSequences never touches the KV
    cache, so the delta must be measured through ExtendStep).

Usage: python tools/kv_quant_sweep.py [variant ...]
Variants: fp32 bf16 int8 (default: all three)
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import bench  # noqa: E402

# kv_cache_dtype per variant (None = the layer's fprop dtype = fp32 here)
VARIANTS = {"fp32": None, "bf16": "bfloat16", "int8": "int8"}


def _Build(jax, kv_cache_dtype):
  """A serving-shaped LM at a serving head dim (the >= 1.8x bf16 -> int8
  admission claim needs dim_per_head >= 36; tiny test heads would hide it
  under the constant sidecar overhead)."""
  from lingvo_tpu.models.lm import layers as lm_layers
  on_cpu = jax.devices()[0].platform == "cpu"
  if on_cpu:
    p = lm_layers.TransformerLm.Params().Set(
        name="lm", vocab_size=128, model_dim=256, num_layers=2, num_heads=4,
        hidden_dim=512, use_rotary=True)
  else:
    p = lm_layers.TransformerLm.Params().Set(
        name="lm", vocab_size=32768, model_dim=1024, num_layers=8,
        num_heads=16, hidden_dim=4096, use_rotary=True)
  p.kv_cache_dtype = kv_cache_dtype
  task = p.Instantiate()
  task.FinalizePaths()
  return task


def _DecodeScore(jax, jnp, task, theta, ids):
  """Teacher-forced next-token log-probs THROUGH the decode cache:
  log P(ids[t+1] | ids[<=t]) from per-step ExtendStep logits. This is the
  ScoreSequences contract evaluated on the path KV quantization actually
  touches."""
  b, t = ids.shape

  @jax.jit
  def run(theta, ids):
    states = task.InitDecodeState(theta, b, t)

    def _Step(states, ids_t):
      logits, states = task.ExtendStep(theta, ids_t[:, None], states)
      return states, jax.nn.log_softmax(logits.astype(jnp.float32), -1)

    _, logps = jax.lax.scan(_Step, states, ids.swapaxes(0, 1))
    logps = logps.swapaxes(0, 1)                      # [B, T, V]
    return jnp.take_along_axis(logps[:, :-1], ids[:, 1:, None],
                               axis=-1)[..., 0]       # [B, T-1]

  return np.asarray(run(theta, ids))


def _DecodeTps(jax, jnp, task, theta, on_tpu):
  """Measured decode throughput (the GShardDecode hot loop, minus host
  I/O): quantize-on-write + dequantize-on-read ride inside ExtendStep when
  the cache is int8."""
  b = 4
  p_len, steps = (256, 256) if on_tpu else (16, 32)
  total = p_len + steps
  prompts = jax.random.randint(jax.random.PRNGKey(1), (b, p_len), 1,
                               task.p.vocab_size)

  @jax.jit
  def run(theta, prompts):
    states = task.InitDecodeState(theta, b, total)
    logits, states = task.Prefill(theta, prompts, states, live_len=p_len)

    def _Sample(carry, _):
      states, lg = carry
      nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
      nl, states = task.ExtendStep(theta, nxt[:, None], states)
      return (states, nl), nxt

    (_, _), out = jax.lax.scan(_Sample, (states, logits[:, -1, :]), None,
                               length=steps)
    return out

  t = bench._StepTime(lambda _: run(theta, prompts), 4)
  return {
      "prompt_len": p_len, "decode_steps": steps, "batch": b,
      "wall_ms": round(t * 1e3, 2),
      "tokens_per_sec": round(b * steps / t, 1),
  }


def _Measure(jax, jnp, name, kv_cache_dtype, slots=8, budget_seq_len=4096):
  from lingvo_tpu.quant import kv as kv_quant
  task = _Build(jax, kv_cache_dtype)
  on_tpu = jax.devices()[0].platform != "cpu"
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))

  census = kv_quant.StackKvCensus(task)
  bpt = census["kv_bytes_per_token"]
  # fixed-HBM admission: the budget = `slots` FP32 sequences at
  # budget_seq_len; how many of THIS variant's sequences fit the same HBM
  fp32_task = _Build(jax, VARIANTS["fp32"])
  fp32_bpt = kv_quant.StackKvCensus(fp32_task)["kv_bytes_per_token"]
  budget = slots * budget_seq_len * fp32_bpt
  admitted = int(budget // (budget_seq_len * bpt))

  # decode-path score delta vs the fp32 variant (same theta, same ids)
  rng = np.random.RandomState(0)
  ids = jnp.asarray(rng.randint(1, task.p.vocab_size, size=(2, 24)),
                    jnp.int32)
  score = _DecodeScore(jax, jnp, task, theta, ids)
  score_f32 = _DecodeScore(jax, jnp, fp32_task, theta, ids)
  delta = float(np.mean(np.abs(score - score_f32)))

  res = {
      "kv_cache_dtype": census["kv_cache_dtype"],
      "kv_bytes_per_token": bpt,
      "kv_bytes_per_token_fp32": fp32_bpt,
      "compression_vs_fp32": round(fp32_bpt / bpt, 3),
      "admitted_sequences": {
          "budget_seq_len": budget_seq_len,
          "budget_bytes": budget,
          "fp32_sequences": slots,
          "sequences": admitted,
      },
      "score_delta_mean_abs": round(delta, 6),
      "decode": _DecodeTps(jax, jnp, task, theta, on_tpu),
  }
  del name
  return res


def main():
  import gc
  import jax
  import jax.numpy as jnp
  names = sys.argv[1:] or list(VARIANTS)
  for name in names:
    try:
      res = _Measure(jax, jnp, name, VARIANTS[name])
    except Exception as e:  # noqa: BLE001
      res = {"error": f"{type(e).__name__}: {e}"[:200]}
    print(json.dumps({"variant": name, **res}), flush=True)
    gc.collect()


if __name__ == "__main__":
  main()
