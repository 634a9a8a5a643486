"""Point-to-point device channels, the SPMD way.

Re-designs `lingvo/core/sendrecv.py` (Channel.Send/Recv wrapping TF _Send/
_Recv between named devices). Under JAX SPMD there are no per-device graphs
to stitch: point-to-point transfer IS `jax.lax.ppermute` over a mesh axis
inside `shard_map` — XLA lowers it to collective-permute on ICI, the same
wire primitive TF's _Send/_Recv pair used. These helpers name the common
patterns; `parallel/stacked_recurrent.py` and `parallel/pipeline.py` are the
in-tree consumers of the idiom.
"""

from __future__ import annotations

import jax


def Shift(x, axis_name: str, offset: int = 1, wrap: bool = False):
  """Sends each shard's `x` to the neighbor `offset` steps up the axis.

  Shard i's value arrives at shard i+offset (mod axis size if `wrap`).
  Without wrap, the lowest shards receive zeros (XLA's collective-permute
  semantics for unmatched targets) — the pipeline-fill behavior.
  """
  n = jax.lax.axis_size(axis_name)
  if wrap:
    perm = [(i, (i + offset) % n) for i in range(n)]
  else:
    perm = [(i, i + offset) for i in range(n) if 0 <= i + offset < n]
  return jax.lax.ppermute(x, axis_name, perm)


def SendRecv(x, pairs, axis_name: str):
  """Explicit (src, dst) channel list (ref Channel semantics).

  Shards not named as a dst receive zeros.
  """
  return jax.lax.ppermute(x, axis_name, list(pairs))


def SendPages(blocks, pairs, axis_name: str):
  """KV page handoff between fleet workers (serving/fleet.py).

  `blocks` is a pytree of per-paged-leaf [n, ...] page blocks — the
  gathered output of `ServingLoop.ExportPrefixBlocks` (int8 K/V pools
  and their f32 scale sidecars are separate leaves and ride the same
  pairs). Every leaf is ppermuted along `axis_name` with one explicit
  (src, dst) list, so a prefill worker's finished pages land on its
  decode worker in a single collective-permute; non-dst shards receive
  zeros they never read.
  """
  return jax.tree_util.tree_map(
      lambda x: jax.lax.ppermute(x, axis_name, list(pairs)), blocks)
