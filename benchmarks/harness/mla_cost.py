"""What the latent attend kernel of a stack of latent-attention (MLA) layers
needs, and what of the program's scopes and counters a reader takes (new with
PR 54; nothing else in the harness reads it).

The stack (PERF.md section 4, `mistralsmall4`): `num_layers` layers, each
latent attention in the absorbed form and an expert layer. N query heads
attend over ONE row a token, `[c_kv | k_r]` of `kv_lora_rank +
qk_rope_head_dim` = 320 values, whose first `kv_lora_rank` = 256 are also the
value. The costs count 320 whatever the program stores (it stores 384, whole
lane tiles).

- operations: a score of 320 products and a value of 256 a (query head,
  attended token): `2 x N x (320 + 256)`;
- bytes: a latent row (16 bits a value) once a (query block, context token) a
  layer: a block's queries share a page in VMEM, and a block is the tokens the
  kernel's query block holds (`run["attend_bq"]` queries, N a token); every
  packed token's q (N x 320) read and its context (N x 256) written once.

The kernel's name in a trace (`xplane.KernelSeconds`): `mla_attend`, the
`jax.named_scope` directly round its call. A program without it (the parent
of PR 54) gives every function here None.
"""

from __future__ import annotations

from benchmarks.harness import hybrid_cost
from benchmarks.harness import moe_cost
from benchmarks.harness import nemotron_cost

MLA_ATTEND = "mla_attend"
# what the layer does round its attend kernel and its page write
MIXER = ("qkv_proj", "rope", "mla_absorb", "out_proj")


def LatentRow(sizes: dict) -> tuple[int, int]:
  """(values a token's row holds, those of them that are its value)."""
  return (int(sizes["kv_lora_rank"]) + int(sizes["qk_rope_head_dim"]),
          int(sizes["kv_lora_rank"]))


def AttendStepCost(rows: list[tuple[int, int]], packed_tokens: int,
                   sizes: dict, block_tokens: int, bytes_per_elem: int = 2
                   ) -> tuple[float, float]:
  """(operations, bytes) the latent attend needs for one step. rows: (tokens
  this step, context length after the step) of each live row."""
  n, layers = int(sizes["num_heads"]), int(sizes["num_layers"])
  row, value = LatentRow(sizes)
  attended = read = 0.0
  for new, ctx in rows:
    if new <= 0:
      continue
    attended += new * (ctx - (new - 1) / 2.0)
    first = ctx - new                       # context before the step's tokens
    for lo in range(0, new, max(1, block_tokens)):
      read += first + min(new, lo + block_tokens)   # the block's last horizon
  ops = layers * 2.0 * n * (row + value) * attended
  nbytes = layers * bytes_per_elem * (
      row * read + packed_tokens * n * (row + value))
  return ops, nbytes


def AttendRoofline(run):
  """The `mla_attend` kernels' device time in the traced steps against the
  larger of their HBM and MXU times over the same steps' live rows."""
  s = run["sizes"]
  if "kv_lora_rank" not in s:
    return None
  block = max(1, int(run.get("attend_bq") or 1024) // int(s["num_heads"]))
  return hybrid_cost.KernelRoofline(
      run, MLA_ATTEND,
      lambda rows: AttendStepCost(rows, run["packed_t"], s, block))


def AttendShare(run):
  return hybrid_cost.KernelShare(run, MLA_ATTEND)


def MixerMs(run):
  """Milliseconds a step under the projections, the rotation, the absorption
  and the output projection; None where the program declares no
  `mla_absorb` (the other three are older than this mixer)."""
  if nemotron_cost.ScopeMs(run, "mla_absorb") is None:
    return None
  return nemotron_cost.ScopeMs(run, *MIXER)


def HeldPairShare(run):
  """(token, expert) pairs whose expert this chip holds over all the pairs
  its routers chose, over the window's steps: 100 x held / (held +
  elsewhere); None where the program counts no pairs elsewhere."""
  grew = moe_cost.CounterDeltas(
      run, ("moe_tokens_routed", "moe_pairs_elsewhere"))
  if grew is None:
    return None
  pairs = grew["moe_tokens_routed"] + grew["moe_pairs_elsewhere"]
  return 100.0 * grew["moe_tokens_routed"] / pairs if pairs > 0 else None
