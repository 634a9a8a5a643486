"""What the pass over the slots' Mamba-2 states needs, and what of the
program's counters and kernels a reader takes (new with PR 57; nothing else
in the harness reads it).

The stack (PERF.md section 4, `granite4hsmall`): `nemotron_cost.Layers`'
letters, nine `M` in twenty; a Mamba-2 layer's state is ONE group's
`[Hm P, N]` f32 a slot (8,192 x 128: 4.2 MB) beside the convolution's K - 1
rows of C channels.

The engine's step records carry the cumulative counter `ssd_state_rows`:
live rows times Mamba-2 layers whose state a step read and wrote, counted
when the step is dispatched. The row pass is a kernel named `ssd_row_pass`
(`xplane.KernelSeconds`). A program without the counter or the kernel's name
(the parent of PR 57) gives every function here None.
"""

from __future__ import annotations

import json

from benchmarks.harness import flops
from benchmarks.harness import hybrid_cost
from benchmarks.harness import moe_cost
from benchmarks.harness import nemotron_cost
from benchmarks.harness import spans
from benchmarks.harness import xplane

SSD_ROW_PASS = "ssd_row_pass"
STATE_ROWS = "ssd_state_rows"


def _Mixer(sizes: dict) -> dict:
  tp = sizes["task_params"]
  hm, p = int(tp["mixer_tpl.num_heads"]), int(tp["mixer_tpl.head_dim"])
  g, n = int(tp["mixer_tpl.num_groups"]), int(tp["mixer_tpl.state_dim"])
  return {"hm": hm, "e": hm * p, "g": g, "n": n,
          "k": int(tp["mixer_tpl.conv_width"])}


def StateBytesPerRow(sizes: dict) -> float:
  """Bytes of one slot's state of one Mamba-2 layer, f32: the scan's
  [E, N] and the convolution's K - 1 rows of E + 2 G N channels."""
  m = _Mixer(sizes)
  return 4.0 * (m["e"] * m["n"] + (m["k"] - 1) * (m["e"] + 2 * m["g"] * m["n"]))


def StateGbStep(run):
  """GB of slot state read and written a step, over the window's steps:
  every (live row, Mamba-2 layer) reads its state once and writes it once."""
  records = spans.StepRecords(run)
  grew = moe_cost.CounterDeltas(run, (STATE_ROWS,))
  if grew is None or not records or len(records) < 2:
    return None
  return (2.0 * StateBytesPerRow(run["sizes"]) * grew[STATE_ROWS]
          / (len(records) - 1) / 1e9)


def RowPassCost(state_rows: float, tokens: float, sizes: dict
                ) -> tuple[float, float]:
  """(operations, bytes) of the pass over the slots' scan states, whatever
  implements it: `state_rows` (live row, layer) states [E, N] f32 read and
  written once; each of the `tokens` (token, layer) pairs' input and output
  (E each, f32), its step size (Hm) and its B and C (G N each); 5 operations
  a state element a token, as `nemotron_cost.SsdScanStepCost` counts."""
  m = _Mixer(sizes)
  ops = 5.0 * m["e"] * m["n"] * tokens
  nbytes = 4.0 * (2.0 * m["e"] * m["n"] * state_rows
                  + tokens * (2.0 * m["e"] + m["hm"] + 2.0 * m["g"] * m["n"]))
  return ops, nbytes


def RowPassRoofline(run):
  """The `ssd_row_pass` kernels' device time in the traced steps against
  the larger of their HBM and MXU times: the states from the program's own
  count over the same steps, the tokens from those steps' live rows."""
  kernel_s = xplane.KernelSeconds(run["trace"], SSD_ROW_PASS)
  n = run["trace_step"]["count"]
  grew = moe_cost.CounterDeltas(run, (STATE_ROWS,), last_steps=n)
  if kernel_s is None or grew is None:
    return None
  s = run["sizes"]
  layers = nemotron_cost.Layers(s)["M"]
  tokens = layers * sum(new for rows in hybrid_cost.TracedStepRows(run, n)
                        for new, _ in rows if new > 0)
  ops, nbytes = RowPassCost(grew[STATE_ROWS], tokens, s)
  share, bound = flops.RooflineShare(ops, nbytes, kernel_s, run["peak"])
  print(json.dumps({"note": "ssd_row_pass_roofline", "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": n,
      "kernel_s": kernel_s, "state_rows": grew[STATE_ROWS],
      "token_layers": tokens}}), flush=True)
  return share
