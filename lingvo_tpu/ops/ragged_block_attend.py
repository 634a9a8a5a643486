"""One ragged kernel for decode, chunked prefill, and spec-verify.

`ops/block_decode.py` gave each SEQUENCE one query per step; prefill and
the spec-verify window needed their own multi-query lowerings, so the
serving engine compiled three step programs and padded prefill rows to a
static chunk. This op is the unification the Ragged Paged Attention
formulation actually calls for: the batch axis is a PACKED TOKEN axis.
Each of the T query tokens carries

- `row_of[t]`  — which batch row (block table) it belongs to, and
- `q_end[t]`   — one past the global KV slot it may attend, i.e. its own
  causal horizon `q_pos + 1` within its sequence.

A `q_len=1` decode row contributes one token, a prefill chunk contributes
`q_len` tokens with ascending `q_end` (causal within the chunk for free —
each token simply sees a shorter prefix), and a spec-verify window is
`k+1` tokens the same way. One op, one compiled program; rows of wildly
different query lengths pack densely instead of padding to the widest.

Layout contract (the serving engine maintains it, same as block_decode):
- a row's logical slot s lives at pool page `block_tables[row, s // P]`,
  offset `s % P`; the K/V for every query token were written BEFORE the
  call (scatter-before-read), so token t's newest visible slot is its own.
- table entries past a row's live pages are unspecified — freed pages may
  already belong to another sequence and must never influence the output.
- `q_end[t] = 0` marks a PADDING token: output 0, no pages read.
- q arrives PRE-SCALED, exactly like BlockDecode/FlashDecode.

Two lowerings, asserted bit-identical (the established twin pattern):

- `_PallasRaggedAttend` — grid `(T, t_pages)`; `row_of`, the block tables,
  and `q_end` ride scalar prefetch, so the page index map resolves
  `block_tables[row_of[t], j]` before the DMA is issued. Dead pages clamp
  to the token's last live page (DMA elided, `pl.when` skips compute) —
  and because consecutive tokens of one row walk the same table, the
  revisited blocks hit the same elision.
- `_XlaRaggedAttend` — `fori_loop` with a dynamic trip count of
  `ceil(max(q_end) / P)` over per-token gathered pages. Tokens whose
  horizon falls short of the batch max process extra pages fully masked —
  bitwise a no-op through `_PageAttend` (alpha == 1, p == 0), which keeps
  the twins exactly equal despite different iteration spaces.

Both route every page through the SAME `_PageAttend` (and int8 pools
through the same `_DequantPages`), so the float-op sequence is identical
and interpret-mode equality holds bitwise — including against
`BlockDecode` itself: a T-token all-decode pack reproduces BlockDecode's
output bit for bit, which is what lets the engine collapse to one program
without moving a single token (asserted in tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu.ops.flash_attention import (  # single source of truth
    LANES, NEG_INF)
from lingvo_tpu.ops.flash_decode import _Finish, _PageAttend
from lingvo_tpu.ops.block_decode import _DequantPages
from lingvo_tpu.ops.block_decode import SupportedOnTpu  # noqa: F401  (same
# Mosaic tiling gate: page_size and h on the 128-lane minor axes; re-exported
# so callers gate the ragged kernel through one name per op module.)


# -- XLA twin (the CPU serving path) -----------------------------------------


def _AncestorOk(slot, c, lo, hi):
  """In-step ancestor visibility for key slots `slot` (already [?, P]).

  c = slot - q_start (position within the row's packed step window); bit c
  of the token's (lo | hi << 32) mask says whether step column c is an
  ancestor-or-self. Slots below the window (c < 0, the committed prefix)
  clip to bit 0, which every tree mask sets (the root is an ancestor of
  all); chain rows ship lo = hi = -1 so every bit reads 1 and the combined
  mask stays bitwise the pre-tree causal mask. Slots at c >= 64 only occur
  on chain rows (tree rows are capped at 64 columns), where -1 again
  yields 1."""
  cc = jnp.clip(c, 0, 63)
  word = jnp.where(cc < 32, lo, hi)
  sh = jnp.where(cc < 32, cc, cc - 32)
  return jnp.bitwise_and(jax.lax.shift_right_logical(word, sh), 1) == 1


def _XlaRaggedAttend(q, k_pool, v_pool, block_tables, row_of, q_end,
                     page_size: int, k_scale=None, v_scale=None,
                     q_start=None, anc_lo=None, anc_hi=None):
  """q: [T, N, H]; pools [NP, P, N, H]; tables [B, t_pages] int32;
  row_of/q_end [T] int32. -> [T, N, H].

  Dynamic trip count over the batch-max live page: per step the work is
  O(T * max(q_end)), not O(T * t_pages * P). k_scale/v_scale [NP, N, P]
  switch on the int8 path via the shared `_DequantPages`. q_start/anc_lo/
  anc_hi [T] int32 add per-token in-step ancestor masking for tree rows
  (None = chain semantics, bitwise the unmasked kernel)."""
  t, n, h = q.shape
  np_total, page, _, _ = k_pool.shape
  assert page == page_size, (page, page_size)
  t_pages = block_tables.shape[1]
  ends = q_end.astype(jnp.int32)
  if q_start is None:
    q_start = jnp.zeros((t,), jnp.int32)
    anc_lo = anc_hi = jnp.full((t,), -1, jnp.int32)
  starts = q_start.astype(jnp.int32)
  lo = anc_lo.astype(jnp.int32)
  hi = anc_hi.astype(jnp.int32)
  trip = jnp.clip((jnp.max(ends) + page_size - 1) // page_size, 0, t_pages)
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
  rows = jnp.clip(row_of.astype(jnp.int32), 0, tables.shape[0] - 1)
  tok_tables = tables[rows]                                # [T, t_pages]

  batched_attend = jax.vmap(_PageAttend)

  def _Body(j, carry):
    m, l, acc = carry
    pid = jax.lax.dynamic_index_in_dim(tok_tables, j, axis=1, keepdims=False)
    k_page = k_pool[pid]                                   # [T, P, N, H]
    v_page = v_pool[pid]
    if k_scale is not None:
      k_page = _DequantPages(k_page, k_scale[pid])
      v_page = _DequantPages(v_page, v_scale[pid])
    slot = j * page_size + jnp.arange(page_size, dtype=jnp.int32)  # [P]
    causal = slot[None, :] < ends[:, None]                 # [T, P]
    ok = _AncestorOk(slot[None, :], slot[None, :] - starts[:, None],
                     lo[:, None], hi[:, None])
    keep = (causal & ok).astype(jnp.float32)[:, None, :]
    return batched_attend(q, k_page, v_page, keep, m, l, acc)

  m0 = jnp.full((t, n, 1), NEG_INF, jnp.float32)
  l0 = jnp.zeros((t, n, 1), jnp.float32)
  acc0 = jnp.zeros((t, n, h), jnp.float32)
  _, l, acc = jax.lax.fori_loop(0, trip, _Body, (m0, l0, acc0))
  return _Finish(l, acc, q.dtype)


# -- Pallas TPU kernel -------------------------------------------------------


def _RaggedAttendKernel(row_of_ref, tables_ref, ends_ref, starts_ref,
                        lo_ref, hi_ref, q_ref, k_ref,
                        v_ref, *rest, page_size: int, t_pages: int):
  """One (token, logical page) program step; scratch carried over pages.

  Same body as `_BlockDecodeKernel` with the batch id replaced by the
  packed-token id: the per-program length is the TOKEN's causal horizon
  `q_end[t]`, not a per-sequence length. Float and int8 calls share the
  body (int8 threads two extra scale blocks, dequantized via the shared
  `_DequantPages`) so the control flow cannot drift."""
  if len(rest) == 6:
    ks_ref, vs_ref, out_ref, m_scr, l_scr, acc_scr = rest
  else:
    ks_ref = vs_ref = None
    out_ref, m_scr, l_scr, acc_scr = rest
  ti = pl.program_id(0)
  j = pl.program_id(1)
  ln = ends_ref[ti]

  @pl.when(j == 0)
  def _Init():
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

  @pl.when(j * page_size < ln)
  def _Accumulate():
    slot = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                       # [1, P]
    ok = _AncestorOk(slot, slot - starts_ref[ti],
                     lo_ref[ti], hi_ref[ti])                # [1, P]
    keep = ((slot < ln) & ok).astype(jnp.float32)           # [1, P]
    k_page, v_page = k_ref[0], v_ref[0]
    if ks_ref is not None:
      k_page = _DequantPages(k_page, ks_ref[0])
      v_page = _DequantPages(v_page, vs_ref[0])
    m, l, acc = _PageAttend(q_ref[0], k_page, v_page, keep, m_scr[:, :1],
                            l_scr[:, :1], acc_scr[:])
    m_scr[:] = jnp.broadcast_to(m, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l, l_scr.shape)
    acc_scr[:] = acc

  @pl.when(j == t_pages - 1)
  def _Emit():
    out_ref[0] = _Finish(l_scr[:, :1], acc_scr[:], out_ref.dtype)


def _PallasRaggedAttend(q, k_pool, v_pool, block_tables, row_of, q_end,
                        page_size: int, interpret: bool = False,
                        k_scale=None, v_scale=None,
                        q_start=None, anc_lo=None, anc_hi=None):
  """Pallas lowering of _XlaRaggedAttend. q: [T, N, H] -> [T, N, H]."""
  t, n, h = q.shape
  np_total, page, _, _ = k_pool.shape
  assert page == page_size, (page, page_size)
  t_pages = block_tables.shape[1]
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
  rows = jnp.clip(row_of.astype(jnp.int32), 0, tables.shape[0] - 1)
  ends = q_end.astype(jnp.int32)
  if q_start is None:
    q_start = jnp.zeros((t,), jnp.int32)
    anc_lo = anc_hi = jnp.full((t,), -1, jnp.int32)
  starts = q_start.astype(jnp.int32)
  lo = anc_lo.astype(jnp.int32)
  hi = anc_hi.astype(jnp.int32)

  # Dead logical pages clamp to the TOKEN's last live page: Pallas
  # re-requests the same physical block and elides the HBM DMA, pl.when
  # skips compute. A stale table entry past a token's horizon never
  # reaches VMEM — the page-reuse-after-eviction guarantee.
  def _PageIdx(ti, j, row_ref, tables_ref, ends_ref, s_ref, lo_ref, hi_ref):
    last = jnp.maximum(
        (ends_ref[ti] + page_size - 1) // page_size - 1, 0)
    last = jnp.minimum(last, t_pages - 1)
    return (tables_ref[row_ref[ti], jnp.minimum(j, last)], 0, 0, 0)

  def _ScaleIdx(ti, j, row_ref, tables_ref, ends_ref, s_ref, lo_ref, hi_ref):
    return _PageIdx(ti, j, row_ref, tables_ref, ends_ref,
                    s_ref, lo_ref, hi_ref)[:3]

  def _TokIdx(ti, j, r_ref, t_ref, e_ref, s_ref, lo_ref, hi_ref):
    return (ti, 0, 0)

  in_specs = [
      pl.BlockSpec((1, n, h), _TokIdx),
      pl.BlockSpec((1, page_size, n, h), _PageIdx),
      pl.BlockSpec((1, page_size, n, h), _PageIdx),
  ]
  operands = [rows, tables, ends, starts, lo, hi, q, k_pool, v_pool]
  if k_scale is not None:
    in_specs += [
        pl.BlockSpec((1, n, page_size), _ScaleIdx),
        pl.BlockSpec((1, n, page_size), _ScaleIdx),
    ]
    operands += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=6,
      grid=(t, t_pages),
      in_specs=in_specs,
      out_specs=pl.BlockSpec((1, n, h), _TokIdx),
      scratch_shapes=[
          pltpu.VMEM((n, LANES), jnp.float32),
          pltpu.VMEM((n, LANES), jnp.float32),
          pltpu.VMEM((n, h), jnp.float32),
      ],
  )
  kernel = functools.partial(_RaggedAttendKernel, page_size=page_size,
                             t_pages=t_pages)
  return pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((t, n, h), q.dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary")),
      interpret=interpret,
  )(*operands)


# -- public entry ------------------------------------------------------------


def RaggedAttend(q, k_pool, v_pool, block_tables, row_of, q_end, *,
                 page_size: int, k_scale=None, v_scale=None,
                 q_start=None, anc_lo=None, anc_hi=None,
                 lowering: str = "auto", interpret: bool | None = None):
  """Packed-token ragged paged attention — decode, prefill, and verify
  rows in one call.

  q: [T, N, H] packed query tokens, ALREADY scaled; every token's K/V was
  written to the pool before the call.
  k_pool/v_pool: [num_pages, page_size, N, H] global page pool.
  block_tables: [B, pages_per_seq] int32 physical page ids; entries past a
  row's live pages are arbitrary and never influence the output.
  row_of: [T] int32 — batch row (block-table index) of each token.
  q_end: [T] int32 — one past each token's highest attendable global slot
  (its `q_pos + 1`); 0 marks a padding token, whose output is 0.
  k_scale/v_scale: [num_pages, N, page_size] f32 sidecars for int8 pools
  (both or neither); pages dequantize in-kernel via `_DequantPages`.
  q_start/anc_lo/anc_hi: [T] int32 tree-speculation operands — q_start is
  the token's row step-window start (its row_q_pos) and anc_lo/anc_hi the
  64-bit ancestor-column bitmask; all three or none. None keeps chain
  semantics bitwise (every in-step predecessor visible).
  lowering: 'auto' (Pallas on real TPU, XLA twin elsewhere) | 'pallas' |
  'xla'. Returns [T, N, H].
  """
  assert q.ndim == 3, q.shape
  assert lowering in ("auto", "pallas", "xla"), lowering
  assert (k_scale is None) == (v_scale is None), "pass both scales or neither"
  tree_args = (q_start is not None, anc_lo is not None, anc_hi is not None)
  assert all(tree_args) or not any(tree_args), \
      "pass q_start+anc_lo+anc_hi together or none"
  if k_scale is not None:
    assert k_pool.dtype == jnp.int8, k_pool.dtype
  if q_start is not None:
    q_start = jnp.asarray(q_start)
    anc_lo = jnp.asarray(anc_lo)
    anc_hi = jnp.asarray(anc_hi)
  on_tpu = jax.default_backend() == "tpu"
  if lowering == "auto":
    lowering = "pallas" if on_tpu else "xla"
  if lowering == "xla":
    return _XlaRaggedAttend(q, k_pool, v_pool, block_tables,
                            jnp.asarray(row_of), jnp.asarray(q_end),
                            page_size, k_scale=k_scale, v_scale=v_scale,
                            q_start=q_start, anc_lo=anc_lo, anc_hi=anc_hi)
  if interpret is None:
    interpret = not on_tpu
  return _PallasRaggedAttend(q, k_pool, v_pool, block_tables,
                             jnp.asarray(row_of), jnp.asarray(q_end),
                             page_size, interpret=interpret,
                             k_scale=k_scale, v_scale=v_scale,
                             q_start=q_start, anc_lo=anc_lo, anc_hi=anc_hi)
