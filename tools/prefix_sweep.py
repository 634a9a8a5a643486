#!/usr/bin/env python
"""Prefix-cache ladder: what does cross-request KV sharing actually buy?

Sweeps share fraction x KV pool dtype over the same serving stack
(serving/engine.py + serving/prefix_cache.py) and prints one JSON line
per variant. Each variant plays an identical seeded Poisson request
stream — `share` of the requests open with one common system prompt —
against two engines at the SAME page pool, prefix cache ON vs OFF, and
reports:

  - prefill_tokens: prompt tokens actually computed by each engine (the
    engine's `prompt_tokens` counter). At share=0.9 the cache must cut
    this >= 2x; at share=0 the two engines should match (the cache costs
    nothing when nothing is shareable),
  - kv_page_peak: peak resident pages — the fixed-HBM footprint story,
  - slots_live_peak: peak admitted concurrency. The pool is sized below
    slots x per-request footprint, so sharing (borrowed pages are not
    charged to the pool) converts directly into admitted sequences,
  - streams_identical: greedy token streams byte-identical ON vs OFF
    within a variant — sharing may never shift a single token,
  - prefix_cache: the ON engine's hits/misses/hit_tokens/cow_copies/
    evictions counters (observe/schema.py PREFIX_CACHE_STATS_KEYS).

Usage: python tools/prefix_sweep.py [variant ...]
Variants: share0-bf16 share0-int8 share50-bf16 share50-int8
          share90-bf16 share90-int8 (default: all six)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

# (share_fraction, kv_cache_dtype) per variant
VARIANTS = {
    "share0-bf16": (0.0, "bfloat16"),
    "share0-int8": (0.0, "int8"),
    "share50-bf16": (0.5, "bfloat16"),
    "share50-int8": (0.5, "int8"),
    "share90-bf16": (0.9, "bfloat16"),
    "share90-int8": (0.9, "int8"),
}


def _Build(jax):
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
  task = p.Instantiate()
  task.FinalizePaths()
  return task


def _Stream(rng, vocab, share, n_req, sys_len, t_lo, t_hi, o_lo, o_hi,
            mean_gap_s):
  """Seeded Poisson arrivals; `share` of the prompts open with one
  common system prompt (the sweep's independent variable)."""
  sys_prompt = rng.randint(1, vocab, sys_len).astype(np.int32)
  prompts = []
  for _ in range(n_req):
    tail = rng.randint(1, vocab, rng.randint(t_lo, t_hi + 1)).astype(
        np.int32)
    if rng.rand() < share:
      prompts.append(np.concatenate([sys_prompt, tail]))
    else:
      prompts.append(tail)
  max_news = rng.randint(o_lo, o_hi + 1, n_req)
  arrivals = np.concatenate(
      [[0.0], np.cumsum(rng.exponential(mean_gap_s, n_req - 1))])
  return sys_prompt, prompts, max_news, arrivals


def _Measure(jax, share, kv_cache_dtype):
  from lingvo_tpu.serving import engine as engine_lib
  on_tpu = jax.devices()[0].platform != "cpu"
  if on_tpu:
    n_req, b_slots, page, max_seq = 32, 8, 128, 1024
    sys_len, t_lo, t_hi, o_lo, o_hi = 256, 32, 128, 32, 128
  else:
    n_req, b_slots, page, max_seq = 12, 4, 8, 64
    sys_len, t_lo, t_hi, o_lo, o_hi = 32, 4, 14, 8, 16

  task = _Build(jax)
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  rng = np.random.RandomState(0)
  sys_prompt, prompts, max_news, arrivals = _Stream(
      rng, task.p.vocab_size, share, n_req, sys_len, t_lo, t_hi,
      o_lo, o_hi, mean_gap_s=0.005)

  # page-bound pool (half of slots x worst-case footprint): concurrency
  # is limited by pages, which is exactly what sharing relieves
  full_pages = -(-(sys_len + t_hi + o_hi) // page)
  num_pages = (b_slots * full_pages) // 2

  def _Play(prefix_cache):
    eng = engine_lib.ServingLoop(
        task, theta, page_size=page, num_pages=num_pages,
        max_batch=b_slots, max_seq_len=max_seq,
        prefill_chunk=16 if on_tpu else 4,
        kv_cache_dtype=kv_cache_dtype, prefix_cache=prefix_cache)
    # compile both programs + pre-warm the tree with the system prompt
    warm = sys_prompt[None, :]
    eng.RunBatch(warm, np.array([sys_len], np.int32), 4)
    eng.Start()
    t0 = time.perf_counter()
    handles = []
    for i in range(n_req):
      dt = t0 + arrivals[i] - time.perf_counter()
      if dt > 0:
        time.sleep(dt)
      handles.append(eng.Submit(prompts[i], int(max_news[i])))
    streams = [h.Result(timeout=1200) for h in handles]
    wall = time.perf_counter() - t0
    stats = eng.Stats()
    eng.Stop()
    return streams, wall, stats

  s_off, wall_off, stats_off = _Play(None)
  s_on, wall_on, stats_on = _Play(True)
  total_useful = int(np.sum(max_news))

  return {
      "share_fraction": share,
      "kv_cache_dtype": stats_on["kv_cache_dtype"],
      "requests": n_req,
      "slots": b_slots,
      "page_size": page,
      "num_pages": num_pages,
      "streams_identical": s_on == s_off,
      "prefill_tokens": {"off": stats_off["prompt_tokens"],
                         "on": stats_on["prompt_tokens"]},
      "prefill_tokens_ratio": round(
          stats_off["prompt_tokens"] / max(stats_on["prompt_tokens"], 1), 3),
      "kv_page_peak": {"off": stats_off["kv_pages"]["peak_in_use"],
                       "on": stats_on["kv_pages"]["peak_in_use"]},
      "slots_live_peak": {"off": stats_off["scheduler"]["slots_live_peak"],
                          "on": stats_on["scheduler"]["slots_live_peak"]},
      "prefix_cache": stats_on["prefix_cache"],
      "tokens_per_sec": {"off": round(total_useful / wall_off, 1),
                         "on": round(total_useful / wall_on, 1)},
  }


def main():
  import gc
  import jax
  names = sys.argv[1:] or list(VARIANTS)
  for name in names:
    try:
      share, dtype = VARIANTS[name]
      res = _Measure(jax, share, dtype)
    except Exception as e:  # noqa: BLE001
      res = {"error": f"{type(e).__name__}: {e}"[:200]}
    print(json.dumps({"variant": name, **res}), flush=True)
    gc.collect()


if __name__ == "__main__":
  main()
