#!/usr/bin/env python
"""Scheduler ladder: what does SLO-aware admission actually buy?

Sweeps scheduler variant x offered load over the same serving stack
(serving/engine.py + serving/scheduler.py) and prints one JSON line per
variant. Each variant plays an identical seeded Poisson request stream —
a saturating low-priority "bulk" tenant plus sparse high-priority "vip"
probes — against the SAME page pool, and reports:

  - ttft_ms per priority class (p50/p99): the sweep's headline. Under
    saturation, fifo head-of-line-blocks the vip probes behind bulk
    work; priority admission jumps them to the front of the queue; spill
    preemption additionally evicts running bulk work, so vip p99 TTFT
    must drop variant over variant,
  - preemptions / restores / spilled_pages / host_bytes_peak: what the
    host tier moved to get there,
  - tenant_tokens + jain_fairness: tokens served per tenant and Jain's
    index over them (tools/fleet_report.py) — priority scheduling
    deliberately trades bulk fairness for vip latency; the index
    quantifies how much,
  - streams_identical: greedy token streams byte-identical across ALL
    variants at the same pool — scheduling may delay tokens, never
    change them.

Variants: {fifo, prio, spill} x {lo, hi} offered load.
  fifo  — scheduler_mode='fifo' (the bit-exact legacy baseline)
  prio  — scheduler_mode='priority' with preemption disabled: classes
          reorder the queue but running work is never evicted
  spill — full priority mode: preemption by KV page spill to host

Usage: python tools/sched_sweep.py [variant ...]
Variants: fifo-lo fifo-hi prio-lo prio-hi spill-lo spill-hi
          (default: all six)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from tools.fleet_report import JainFairness  # noqa: E402

# (scheduler_mode, allow_preempt, load_scale) per variant; load_scale
# multiplies the offered arrival rate (hi ~ 4x past saturation)
VARIANTS = {
    "fifo-lo": ("fifo", False, 1.0),
    "fifo-hi": ("fifo", False, 4.0),
    "prio-lo": ("priority", False, 1.0),
    "prio-hi": ("priority", False, 4.0),
    "spill-lo": ("priority", True, 1.0),
    "spill-hi": ("priority", True, 4.0),
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


def _Stream(rng, vocab, n_bulk, n_vip, bulk_out, vip_out, p_lo, p_hi,
            mean_gap_s, load_scale):
  """Seeded two-tenant mix: n_bulk priority-0 'bulk' requests saturate
  the pool; n_vip priority-5 'vip' probes arrive interleaved. Returns
  [(arrival_s, prompt, max_new, priority, tenant)] sorted by arrival."""
  reqs = []
  t = 0.0
  for _ in range(n_bulk):
    prompt = rng.randint(1, vocab, rng.randint(p_lo, p_hi + 1)).astype(
        np.int32)
    reqs.append((t, prompt, bulk_out, 0, "bulk"))
    t += rng.exponential(mean_gap_s / load_scale)
  # vip probes spread across the bulk window
  span = max(t, 1e-6)
  for i in range(n_vip):
    prompt = rng.randint(1, vocab, rng.randint(p_lo, p_hi + 1)).astype(
        np.int32)
    reqs.append((span * (i + 1) / (n_vip + 1), prompt, vip_out, 5, "vip"))
  reqs.sort(key=lambda r: r[0])
  return reqs


def _Measure(jax, scheduler_mode, allow_preempt, load_scale):
  from lingvo_tpu.serving import engine as engine_lib
  on_tpu = jax.devices()[0].platform != "cpu"
  if on_tpu:
    n_bulk, n_vip, b_slots, page, max_seq = 24, 6, 8, 128, 1024
    bulk_out, vip_out, p_lo, p_hi = 192, 16, 32, 128
    mean_gap_s = 0.02
  else:
    n_bulk, n_vip, b_slots, page, max_seq = 10, 3, 2, 8, 64
    bulk_out, vip_out, p_lo, p_hi = 24, 4, 4, 12
    mean_gap_s = 0.01

  task = _Build(jax)
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  rng = np.random.RandomState(0)
  reqs = _Stream(rng, task.p.vocab_size, n_bulk, n_vip, bulk_out, vip_out,
                 p_lo, p_hi, mean_gap_s, load_scale)

  # pool sized to b_slots x worst-case footprint: slots, not pages, are
  # the contended resource — preemption frees a SLOT by spilling pages
  full_pages = -(-(p_hi + bulk_out) // page)
  num_pages = b_slots * full_pages

  eng = engine_lib.ServingLoop(
      task, theta, page_size=page, num_pages=num_pages, max_batch=b_slots,
      max_seq_len=max_seq, prefill_chunk=16 if on_tpu else 4,
      scheduler_mode=scheduler_mode)
  eng.sched.allow_preempt = allow_preempt
  # compile the step program off the clock
  eng.RunBatch(np.array([[1, 2, 3, 4]], np.int32), np.array([4], np.int32), 2)
  eng.Start()
  t0 = time.perf_counter()
  handles = []
  for arrival, prompt, max_new, priority, tenant in reqs:
    dt = t0 + arrival - time.perf_counter()
    if dt > 0:
      time.sleep(dt)
    handles.append((eng.Submit(prompt, int(max_new), eos_id=None,
                               priority=priority, tenant=tenant),
                    priority, tenant))
  streams = [(h.Result(timeout=1200), pr, tn) for h, pr, tn in handles]
  wall = time.perf_counter() - t0
  stats = eng.Stats()
  host_peak = (eng.sched.host_store.Stats()["peak_host_bytes"]
               if eng.sched.host_store is not None else 0)
  eng.Stop()

  ttft_by_class: dict = {}
  for (h, pr, _tn) in handles:
    if h.first_token_time is not None:
      ttft_by_class.setdefault(pr, []).append(
          (h.first_token_time - h.submit_time) * 1e3)
  tenant_tokens: dict = {}
  for toks, _pr, tn in streams:
    tenant_tokens[tn] = tenant_tokens.get(tn, 0) + len(toks)

  sched = stats["scheduler"]
  return {
      "scheduler_mode": scheduler_mode,
      "allow_preempt": allow_preempt,
      "load_scale": load_scale,
      "requests": len(reqs),
      "slots": b_slots,
      "num_pages": num_pages,
      "wall_s": round(wall, 3),
      "ttft_ms": {
          f"c{pr}": {"p50": round(float(np.percentile(v, 50)), 2),
                     "p99": round(float(np.percentile(v, 99)), 2)}
          for pr, v in sorted(ttft_by_class.items())},
      "preemptions": sched["preemptions"],
      "restores": sched["restores"],
      "spilled_pages": sched["spilled_pages"],
      "restored_pages": sched["restored_pages"],
      "host_bytes_peak": host_peak,
      "tenant_tokens": tenant_tokens,
      "jain_fairness": round(JainFairness(tenant_tokens.values()), 4),
      "streams": [[int(t) for t in toks] for toks, _pr, _tn in streams],
  }


def main():
  import gc
  import jax
  names = sys.argv[1:] or list(VARIANTS)
  baseline_streams: dict = {}   # load_scale -> first variant's streams
  for name in names:
    try:
      mode, preempt, load = VARIANTS[name]
      res = _Measure(jax, mode, preempt, load)
      # byte-identity across variants at the same offered load: compare
      # against the first variant measured at this load_scale
      streams = res.pop("streams")
      base = baseline_streams.setdefault(load, streams)
      res["streams_identical"] = streams == base
    except Exception as e:  # noqa: BLE001
      res = {"error": f"{type(e).__name__}: {e}"[:200]}
    print(json.dumps({"variant": name, **res}), flush=True)
    gc.collect()


if __name__ == "__main__":
  main()
