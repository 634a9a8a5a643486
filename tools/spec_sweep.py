#!/usr/bin/env python
"""Speculative-decoding ladder: draft source x tree width w x depth k.

Plays bench.py's seeded Poisson serving stream (greedy, byte-identity
asserted inside the bench) against spec-decode engines over the grid

    w in {1, 2, 4}  x  k in {2, 4, 8}  x  draft in {self, model}

where `self` is 1-layer early-exit self-speculation over the target's
own theta, `model` is an independent tiny pageless SSM draft
(docs/speculative_decoding.md), and w > 1 submits a token TREE of w
root-anchored branches per speculating row (w == 1 is chain
speculation, bitwise the pre-tree engine). One JSON line per variant
with tokens_per_sec_speedup, acceptance_rate, the accepted-length AND
accepted-depth histograms, and branch / width-clamp counters — the grid
shows the acceptance/verify-width trade directly: extra siblings only
pay while the target actually forks where the draft hedges, and extra
depth only while the draft keeps matching. (Acceptance between two
random-init models skews unrealistically high — both collapse to
last-token echo — so read the speedups as machinery cost at a GIVEN
acceptance, not as what a distilled draft would deliver.)

The shared baseline (the plain engine on the same stream) is measured
once and echoed first.

Usage: python tools/spec_sweep.py [k ...]        (default: 2 4 8)
       SPEC_SWEEP_WS=1,2 python tools/spec_sweep.py
       SPEC_SWEEP_DRAFTS=self python tools/spec_sweep.py
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
  ks = [int(a) for a in sys.argv[1:]] or [2, 4, 8]
  ws = [int(w) for w in
        os.environ.get("SPEC_SWEEP_WS", "1,2,4").split(",")]
  drafts = os.environ.get("SPEC_SWEEP_DRAFTS", "self,model").split(",")
  grid = [(d, k, w) for w in ws for k in ks for d in drafts]
  res = bench._BenchSpecDecode(jax, jnp, model_registry, on_tpu,
                               variants=grid)
  base = {k: v for k, v in res.items() if k != "variants"}
  print(json.dumps({"variant": "baseline", **base}), flush=True)
  for v in res["variants"]:
    print(json.dumps(
        {"variant": f"{v['draft']}-w{v['w']}-k{v['k']}", **v}),
        flush=True)


if __name__ == "__main__":
  main()
