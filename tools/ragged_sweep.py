#!/usr/bin/env python
"""Ragged-step ladder: per-step prefill token budget sweep.

Plays bench.py's seeded mixed-length greedy streams (byte-identity
asserted inside the bench) against the one-program ragged engine at a
ladder of `prefill_token_budget` values — the single knob the unified
step exposes (docs/ragged_step.md): the packed width is
max_batch * (k + 1) + budget, so a bigger budget buys prefill
throughput with a wider (slower) step while decode rows keep their
mandatory lanes either way. Each rung replays BOTH variance arms
against the padded three-program legacy baseline, so the ladder shows
where the waste and throughput ratios peak for a given stream shape.

One JSON line per rung with the bench's full arm breakdown
(tokens_per_sec_ratio, waste_per_step_ratio, decode_p99_ms per mode)
plus the acceptance booleans.

Usage: python tools/ragged_sweep.py [budget ...]   (default: chunk x {1,2,4})
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import bench  # noqa: E402


def main():
  import jax
  import jax.numpy as jnp
  from lingvo_tpu import model_registry
  import lingvo_tpu.models.all_params  # noqa: F401

  on_tpu = jax.devices()[0].platform != "cpu"
  chunk = 64 if on_tpu else 8
  budgets = [int(a) for a in sys.argv[1:]] or [chunk, 2 * chunk, 4 * chunk]
  for b in budgets:
    res = bench._BenchRaggedStep(jax, jnp, model_registry, on_tpu, budget=b)
    print(json.dumps({"variant": f"budget-{b}", **res}), flush=True)


if __name__ == "__main__":
  main()
