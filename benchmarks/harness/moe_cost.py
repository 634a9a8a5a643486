"""What an expert layer's grouped matmuls need, and the program's counters a
reader takes it from (new with PR 35; nothing else in the harness reads it).

The engine's step records (`observe.trace.StepTrace.counters`, read through
`spans.StepRecords`) carry the cumulative counters `moe_tokens_routed`
((token, expert) pairs, summed over layers), `moe_experts_active` (experts
that got a token, summed over layers), `moe_expert_load_max` / `_mean`, and
`window_pages_released` / `_allocated`, as they stood when a step's record
closed. A program without them (the parent of PR 35) gives records with no
`counters`: every function here then returns None, nothing to read.
"""

from __future__ import annotations

from benchmarks.harness import spans

# What the grouped matmuls' kernels are named after in a trace
# (xplane.ScopeOfOp): the program's `moe_experts` scope where XLA names the
# ops after it, and `gmm`, the name megablox's own jit gives its Pallas
# kernel inside that scope ('%gmm.N': found on the chip, my chip run, PR 35)
KERNEL_SCOPES = ("moe_experts", "gmm")


def CounterDeltas(run, keys, last_steps: int | None = None):
  """{key: growth of the counter} between the window's first step record and
  its last, or over its last `last_steps` records (a traced tail). None
  where the program's records carry no such counters."""
  records = spans.StepRecords(run)
  if not records:
    return None
  if last_steps is not None:
    records = records[-(last_steps + 1):]
  first = getattr(records[0], "counters", None)
  last = getattr(records[-1], "counters", None)
  if not first or not last or any(k not in last for k in keys):
    return None
  return {k: last[k] - first[k] for k in keys}


def ExpertWidth(sizes: dict) -> int:
  return int(sizes["task_params"]["expert_ffn_tpl.hidden_dim"])


def GroupedMatmulCost(pairs: float, active_experts: float, model_dim: int,
                      expert_dim: int, bytes_per_elem: int = 2
                      ) -> tuple[float, float]:
  """(operations, bytes) of the gate, up and down projections of `pairs`
  (token, expert) pairs over `active_experts` experts that got a token (both
  summed over layers and steps): 2 x 3 x D x F operations a pair; the three
  [D, F] matrices of every active expert read once, each pair's D-vector
  read once and written once (the F-wide hidden vector need not leave the
  chip)."""
  ops = 2.0 * 3 * model_dim * expert_dim * pairs
  nbytes = bytes_per_elem * (3.0 * model_dim * expert_dim * active_experts
                             + 2.0 * model_dim * pairs)
  return ops, nbytes
