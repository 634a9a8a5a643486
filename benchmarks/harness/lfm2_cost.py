"""What a stack of gated short-convolution layers with a few rotated
grouped-query attention layers among them needs, and what of the program's
counters and scopes a reader takes (new with PR 63; nothing else in the
harness reads it).

The stack (PERF.md section 4, `lfm2_24b`): the file's
`task_params.layer_kinds`, one name a layer, `short_conv+...` a convolution
mixer and `gqa_rope+...` an attention layer of 32 query heads over 8 KV heads
of 64 (two KV heads side by side on a token's row of the pool).

The mixer's device time is read by the program's own scopes (`scope_ms`):
`short_conv`, the mixer's whole branch (the two projections, the gates, the
taps, the tail), and `short_conv_taps` inside it, what is neither a matmul
nor a gate. The attention layers' kernel is the grouped ragged attend, named
after `ragged_attend`; its cost is counted at the REQUIRED head size (64) for
the layers that attend, so whatever the kernel pads or computes twice shows
as a lower share. The engine's step records carry the cumulative counter
`slot_state_bytes`: what the step's live rows read and wrote of slot state,
counted when the step is dispatched. A program that declares no such scope
or counter (the parent of PR 63) gives every function here None.
"""

from __future__ import annotations

import json

from benchmarks.harness import flops
from benchmarks.harness import hybrid_cost
from benchmarks.harness import layer_lib
from benchmarks.harness import moe_cost
from benchmarks.harness import nemotron_cost
from benchmarks.harness import spans
from benchmarks.harness import xplane

SHORT_CONV = "short_conv"
SHORT_CONV_TAPS = "short_conv_taps"
SLOT_STATE_BYTES = "slot_state_bytes"


def Layers(sizes: dict) -> dict:
  """{mixer's name: layers of it} over the depth the file runs."""
  mixers = [kind.partition("+")[0]
            for kind in sizes["task_params"]["layer_kinds"]]
  return {m: mixers.count(m) for m in set(mixers)}


def ShortConvMs(run):
  """Milliseconds a step under `short_conv`, its taps included."""
  return nemotron_cost.ScopeMs(run, SHORT_CONV)


def ShortConvTapsMs(run):
  """Milliseconds a step under `short_conv_taps`."""
  return nemotron_cost.ScopeMs(run, SHORT_CONV_TAPS)


def H64AttendRoofline(run):
  """The grouped ragged attend kernel of the stack's attention layers (two of
  nine here) against its roofline over the traced steps: every query head's
  products at the head size the file states, the KV heads' pages."""
  kernel_s = xplane.KernelSeconds(run["trace"], layer_lib.RAGGED_KERNEL)
  if kernel_s is None:
    return None
  s = run["sizes"]
  n = run["trace_step"]["count"]
  layers = Layers(s).get("gqa_rope", 0)
  ops = nbytes = 0.0
  for rows in hybrid_cost.TracedStepRows(run, n):
    o, b = flops.RaggedAttendStepCost(
        rows, run["packed_t"], s["num_heads"], s["dim_per_head"], layers,
        num_kv_heads=s["num_kv_heads"])
    ops, nbytes = ops + o, nbytes + b
  share, bound = flops.RooflineShare(ops, nbytes, kernel_s, run["peak"])
  print(json.dumps({"note": "h64_attend_roofline", "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": n,
      "kernel_s": kernel_s}}), flush=True)
  return share


def SlotStateMbStep(run):
  """MB of slot state read and written a step over the window's steps (the
  program's own count: a live row's convolution tails, once in, once out)."""
  records = spans.StepRecords(run)
  grew = moe_cost.CounterDeltas(run, (SLOT_STATE_BYTES,))
  if grew is None or not records or len(records) < 2:
    return None
  return grew[SLOT_STATE_BYTES] / (len(records) - 1) / 1e6
