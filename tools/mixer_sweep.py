#!/usr/bin/env python
"""Sequence-mixer sweep: what does O(1) decode state actually buy?

Sweeps the three stack shapes from docs/sequence_mixers.md — pure
attention, pure SSM, and the hybrid (attention every Nth layer) — across
sequence lengths 1k-32k and prints one JSON line per variant with:

  - decode_state_bytes_per_seq at each length (via jax.eval_shape, so the
    32k points cost nothing even on a CPU host). The acceptance bar: the
    SSM curve is FLAT, the attention curve is linear, the hybrid grows at
    attention_share/num_layers of the attention slope.
  - slots_at_hbm_budget: how many concurrent sequences fit a fixed decode
    HBM budget (the budget = what `slots` attention sequences need at
    `budget_seq_len`) — the more-concurrent-requests-at-fixed-HBM claim.
  - measured decode throughput (chunked Prefill + greedy ExtendStep scan)
    at a length the host can actually run.

Usage: python tools/mixer_sweep.py [variant ...]
Variants: attention ssm hybrid (default: all three)
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import bench  # noqa: E402

SEQ_LADDER = (1024, 2048, 4096, 8192, 16384, 32768)

# mixer_atten_every_n per variant: 1 = attention at every layer (the plain
# stack), 0 = pure SSM, and the recipe's own spacing for the hybrid
VARIANTS = {"attention": 1, "ssm": 0, "hybrid": None}


def _Build(jax, jnp, model_registry, every_n):
  on_cpu = jax.devices()[0].platform == "cpu"
  name = ("lm.synthetic_packed_input.DenseLmSsmHybridTiny" if on_cpu else
          "lm.synthetic_packed_input.DenseLmSsmHybrid")
  mp = model_registry.GetParams(name, "Train")
  mp.task.input = mp.input
  if every_n is not None:
    mp.task.mixer_atten_every_n = every_n
  task = mp.task.Instantiate()
  task.FinalizePaths()
  return mp, task


def _StateBytesPerSeq(jax, task, theta, max_len, b=4):
  """Decode-state bytes for one sequence at max_len — abstract eval only,
  nothing is allocated (the 32k attention point would be real HBM)."""
  states = jax.eval_shape(lambda th: task.InitDecodeState(th, b, max_len),
                          theta)
  total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
              for x in jax.tree_util.tree_leaves(states)
              if hasattr(x, "shape"))
  return total // b


def _DecodeTps(jax, jnp, task, theta, on_tpu):
  """Measured decode throughput: chunked Prefill + greedy ExtendStep scan
  (the GShardDecode hot loop, minus host I/O)."""
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


def _Measure(jax, jnp, model_registry, name, every_n,
             slots=8, budget_seq_len=8192):
  mp, task = _Build(jax, jnp, model_registry, every_n)
  on_tpu = jax.devices()[0].platform != "cpu"
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))

  ladder = {str(s): _StateBytesPerSeq(jax, task, theta, s)
            for s in SEQ_LADDER}
  lo, hi = ladder[str(SEQ_LADDER[0])], ladder[str(SEQ_LADDER[-1])]

  res = {
      "every_n": task.p.mixer_atten_every_n if every_n is None else every_n,
      "decode_state_bytes_per_seq": ladder,
      "state_growth_1k_to_32k": round(hi / max(lo, 1), 2),
      "state_flat": hi == lo,
      "decode": _DecodeTps(jax, jnp, task, theta, on_tpu),
  }
  # fixed-HBM admission: budget = `slots` ATTENTION sequences at
  # budget_seq_len; how many of THIS variant's sequences fit the same HBM
  _, atten_task = _Build(jax, jnp, model_registry, VARIANTS["attention"])
  atten_theta = jax.eval_shape(
      lambda k: atten_task.InstantiateVariables(k), jax.random.PRNGKey(0))
  budget = slots * _StateBytesPerSeq(jax, atten_task, atten_theta,
                                     budget_seq_len)
  mine = _StateBytesPerSeq(jax, task, theta, budget_seq_len)
  res["slots_at_hbm_budget"] = {
      "budget_seq_len": budget_seq_len,
      "budget_bytes": budget,
      "attention_slots": slots,
      "slots": int(budget // max(mine, 1)),
  }
  del name
  return res


def main():
  import gc
  import jax
  import jax.numpy as jnp
  from lingvo_tpu import model_registry
  import lingvo_tpu.models.all_params  # noqa: F401
  names = sys.argv[1:] or list(VARIANTS)
  for name in names:
    try:
      res = _Measure(jax, jnp, model_registry, name, VARIANTS[name])
    except Exception as e:  # noqa: BLE001
      res = {"error": f"{type(e).__name__}: {e}"[:200]}
    print(json.dumps({"variant": name, **res}), flush=True)
    gc.collect()


if __name__ == "__main__":
  main()
