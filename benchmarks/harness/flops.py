"""Operations and bytes that the algorithm requires, computed from shapes.

These are the benchmark's own functions: `mfu_required` and every
`<kernel>_roofline` divide what is computed here by a measured time. Required
means required: causal attention is counted at half of T^2 and nothing that
remat recomputes is counted (bench._BenchDense counted attention at the full
T^2; that formula is kept below only as `causal=False`, the form that XLA's
own count of an unfused forward+backward agrees with, which is what
tests/benchmark/test_flops.py checks against core/computation_cost.py).
"""

from __future__ import annotations


def DenseLmParams(cfg: dict, num_layers: int | None = None) -> dict:
  """Parameter counts of a TransformerLm of the DenseLm family."""
  d, ff, v = cfg["model_dim"], cfg["hidden_dim"], cfg["vocab_size"]
  n = cfg["num_heads"]
  h = cfg.get("dim_per_head") or d // n
  layers = cfg["num_layers"] if num_layers is None else num_layers
  atten = 4 * d * n * h
  ffn = 2 * d * ff
  return {"layers": layers, "per_layer_matmul": atten + ffn,
          "embedding": v * d}


def TrainFlopsPerToken(cfg: dict, num_layers: int | None = None,
                       seq_len: int | None = None, causal: bool = True
                       ) -> float:
  """Forward + backward operations one trained token requires.

  6 per matmul parameter (2 forward, 4 backward), 6 per embedding parameter
  for the tied softmax (the lookup is a gather and costs nothing), and
  attention: QK^T and PV are 4*T*N*H operations a token forward, 12 with
  the backward, halved when `causal` because a token attends to the T/2
  tokens before it on average.
  """
  c = DenseLmParams(cfg, num_layers)
  atten = AttentionFlopsPerToken(cfg, num_layers, seq_len) * (
      0.5 if causal else 1.0)
  return 6.0 * c["per_layer_matmul"] * c["layers"] + 6.0 * c["embedding"] + atten


def AttentionFlopsPerToken(cfg: dict, num_layers: int | None = None,
                           seq_len: int | None = None) -> float:
  """QK^T and PV forward and backward over the full T^2, per token."""
  layers = cfg["num_layers"] if num_layers is None else num_layers
  t = cfg["seq_len"] if seq_len is None else seq_len
  n = cfg["num_heads"]
  h = cfg.get("dim_per_head") or cfg["model_dim"] // n
  return 12.0 * t * n * h * layers


def FlashTrainStepCost(batch: int, seq_len: int, num_heads: int,
                       dim_per_head: int, num_layers: int,
                       bytes_per_elem: int = 2) -> tuple[float, float]:
  """(operations, bytes) flash attention needs for one train step.

  Forward: two matmuls (QK^T, PV), 4*T^2*N*H a row, halved for causality.
  Backward: five matmuls (S again, dP, dV, dQ, dK), 2.5 times the forward.
  The forward that remat runs a second time is not required and not
  counted. Bytes: forward reads q, k, v and writes o; backward reads q, k,
  v, o, do and writes dq, dk, dv; 12 tensors of [B, T, N, H].
  """
  fwd = 4.0 * batch * seq_len * seq_len * num_heads * dim_per_head * 0.5
  ops = 3.5 * fwd * num_layers
  nbytes = 12.0 * batch * seq_len * num_heads * dim_per_head * bytes_per_elem
  return ops, nbytes * num_layers


def RaggedAttendStepCost(rows: list[tuple[int, int]], packed_tokens: int,
                         num_heads: int, dim_per_head: int, num_layers: int,
                         bytes_per_elem: int = 2) -> tuple[float, float]:
  """(operations, bytes) the paged ragged attention needs for one step.

  rows: (tokens this step, context length after the step) for each live
  sequence. A token at position p attends to p+1 cached tokens: 4*N*H
  operations each. A row's K and V pages are read once, and q and o of the
  whole packed axis are read and written once.
  """
  ops = 0.0
  kv_tokens = 0
  for new, ctx in rows:
    if new <= 0:
      continue
    first = ctx - new + 1           # context seen by the row's first token
    ops += 4.0 * num_heads * dim_per_head * new * (first + ctx) / 2.0
    kv_tokens += ctx
  nbytes = (2.0 * kv_tokens + 2.0 * packed_tokens) * (
      num_heads * dim_per_head * bytes_per_elem)
  return ops * num_layers, nbytes * num_layers


def RooflineShare(ops: float, nbytes: float, seconds: float, peak
                  ) -> tuple[float, str]:
  """(percent of the roofline reached, which bound): the least time the chip
  could take over the time it took. No clamp: over 100 means the count or
  the time is wrong, and that must show."""
  t_ops = ops / peak.flops_bf16
  t_bytes = nbytes / peak.hbm_bytes_s
  bound = "compute" if t_ops >= t_bytes else "memory"
  return 100.0 * max(t_ops, t_bytes) / seconds, bound
