"""Block-table ragged paged attention over a global KV page pool.

The continuous-batching evolution of `ops/flash_decode.py`: instead of one
contiguous `[B, max_len]` cache slab per batch (every sequence pays max_len
HBM whether it uses it or not), K/V live in a GLOBAL pool of fixed-size
pages `[num_pages, page_size, N, H]` and each sequence owns an arbitrary
set of pages named by a per-sequence *block table* `[B, pages_per_seq]` of
physical page ids — the "Ragged Paged Attention" formulation. Sequences of
wildly different lengths share one pool, pages are recycled the moment a
sequence finishes, and admission/eviction never reshapes device buffers.

Layout contract (the serving engine maintains it):
- sequence i's tokens occupy logical slots [0, seq_len_i), contiguously;
  logical slot s lives at pool page `block_tables[i, s // page_size]`,
  offset `s % page_size`. No left-padding — unlike the gshard_decode dense
  layout there are no cache_paddings; dead slots are simply `>= seq_len`.
- block-table entries past a sequence's live pages are unspecified (the
  kernels clamp/mask; freed pages may already belong to another sequence,
  so they must never influence the output).
- q arrives PRE-SCALED, exactly like FlashDecode.

Two lowerings of the single-query decode op, asserted bit-identical:

- `_PallasBlockDecode` — grid `(B, pages_per_seq)`; the block table and the
  per-sequence lengths ride scalar prefetch, so the page index map resolves
  `block_tables[b, j]` before the DMA is issued (dead pages clamp to the
  last live page: Pallas re-requests the same block and elides the copy,
  `pl.when` skips their compute).
- `_XlaBlockDecode` — `fori_loop` with a dynamic trip count of
  `ceil(max(seq_lens) / page_size)` over per-row gathered pages. Rows whose
  lengths fall short of the batch max process extra pages fully masked —
  bitwise a no-op through `_PageAttend` (alpha == 1, p == 0), which is what
  keeps the twins exactly equal despite different iteration spaces.

`BlockPrefill` is the multi-query sibling (C prompt-chunk queries per row,
causal within the chunk) used for chunked prefill interleaved with decode;
it is an XLA-only lowering — the single-query kernel is the steady-state
hot op, prefill happens once per admitted request.

Quantized pools: when the pool stores int8 (see `lingvo_tpu/quant/kv.py`),
callers pass the f32 scale sidecars `k_scale`/`v_scale` of shape
[num_pages, N, page_size] — transposed so the Pallas scale block's minor
dimension is page_size (a multiple of 128 lanes whenever `SupportedOnTpu`
admits the kernel at all). Both lowerings dequantize through the SAME
`_DequantPages` helper right before `_PageAttend`, which is what keeps the
int8 twins bitwise-identical just like the float pair. In the Pallas
lowering the scales ride VMEM blocks whose index map resolves through the
scalar-prefetched block table — dead logical pages clamp to the row's last
live page, so scale DMAs are elided exactly like the K/V page DMAs. (The
full per-slot sidecar is too large for SMEM at serving sizes, so the
scales are NOT themselves scalar-prefetch operands — only the table and
lengths are.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu.ops.flash_attention import (  # single source of truth
    LANES, NEG_INF)
from lingvo_tpu.ops.flash_decode import _DotF32, _Finish, _PageAttend


def GatherPages(pool, block_tables):
  """pool [NP, P, N, H] + tables [B, T] -> dense [B, T*P, N, H].

  The dense-cache view of a block-table layout: row i's logical slots in
  order. Reference path for tests and the ineligible-config fallback in
  `MultiHeadedAttention.PagedStep` (out-of-range table entries clamp, the
  caller masks dead slots)."""
  b, t_pages = block_tables.shape
  np_total, page, n, h = pool.shape
  pages = pool[jnp.clip(block_tables, 0, np_total - 1)]  # [B, T, P, N, H]
  return pages.reshape(b, t_pages * page, n, h)


def GatherScales(scales, block_tables):
  """sidecar [NP, N, P] + tables [B, T] -> dense [B, T*P, N].

  The `GatherPages` sibling for scale sidecars: per-slot-per-head scales in
  logical-slot order, aligned with the [B, T*P, N, H] gathered pages, for
  the dense-fallback dequantization in `MultiHeadedAttention.PagedStep`."""
  b, t_pages = block_tables.shape
  np_total, n, page = scales.shape
  s = scales[jnp.clip(block_tables, 0, np_total - 1)]     # [B, T, N, P]
  return jnp.swapaxes(s, 2, 3).reshape(b, t_pages * page, n)


def _DequantPages(pages, scales):
  """pages [..., P, N, H] int8 + scales [..., N, P] f32 -> f32 pages.

  THE shared dequantize-on-read: both the Pallas kernel and the XLA twin
  (and `BlockPrefill`) funnel quantized pages through this exact sequence
  of float ops before `_PageAttend`, so the int8 lowerings stay
  bitwise-identical for the same reason the float ones do."""
  s = jnp.swapaxes(scales.astype(jnp.float32), -1, -2)[..., None]
  return pages.astype(jnp.float32) * s


# -- XLA twin (the CPU serving path) -----------------------------------------


def _XlaBlockDecode(q, k_pool, v_pool, block_tables, seq_lens,
                    page_size: int, k_scale=None, v_scale=None):
  """q: [B, N, H]; pools [NP, P, N, H]; tables [B, T] int32; seq_lens [B]
  int32 (live slots per row; the query attends slots < seq_len). -> [B, N, H].

  Dynamic trip count over the batch-max live page — per decode step the
  work is O(max live length over the batch), not O(T * page_size).
  k_scale/v_scale [NP, N, P] switch on the int8 path: pages dequantize
  through `_DequantPages` right before `_PageAttend` (scales None leaves
  the float path untouched, op for op)."""
  b = q.shape[0]
  np_total, page, n, h = k_pool.shape
  assert page == page_size, (page, page_size)
  t_pages = block_tables.shape[1]
  lens = seq_lens.astype(jnp.int32)
  # lens may legally reach (or, out of contract, exceed) the table capacity;
  # clamp the trip like the Pallas grid never exceeds t_pages.
  trip = jnp.clip((jnp.max(lens) + page_size - 1) // page_size, 0, t_pages)
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)

  batched_attend = jax.vmap(_PageAttend)

  def _Body(j, carry):
    m, l, acc = carry
    pid = jax.lax.dynamic_index_in_dim(tables, j, axis=1, keepdims=False)
    k_page = k_pool[pid]                                   # [B, P, N, H]
    v_page = v_pool[pid]
    if k_scale is not None:
      k_page = _DequantPages(k_page, k_scale[pid])
      v_page = _DequantPages(v_page, v_scale[pid])
    slot = j * page_size + jnp.arange(page_size, dtype=jnp.int32)  # [P]
    keep = (slot[None, :] < lens[:, None]).astype(jnp.float32)[:, None, :]
    return batched_attend(q, k_page, v_page, keep, m, l, acc)

  m0 = jnp.full((b, n, 1), NEG_INF, jnp.float32)
  l0 = jnp.zeros((b, n, 1), jnp.float32)
  acc0 = jnp.zeros((b, n, h), jnp.float32)
  _, l, acc = jax.lax.fori_loop(0, trip, _Body, (m0, l0, acc0))
  return _Finish(l, acc, q.dtype)


# -- Pallas TPU kernel -------------------------------------------------------


def _BlockDecodeKernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                       page_size: int, t_pages: int):
  """One (batch, logical page) program step; scratch carried over pages.

  One body serves both storage modes so the control flow cannot drift:
  the float call passes (out_ref, scratch...), the int8 call additionally
  threads the scale blocks (ks_ref, vs_ref, out_ref, scratch...) and
  dequantizes via the shared `_DequantPages` before `_PageAttend`."""
  if len(rest) == 6:
    ks_ref, vs_ref, out_ref, m_scr, l_scr, acc_scr = rest
  else:
    ks_ref = vs_ref = None
    out_ref, m_scr, l_scr, acc_scr = rest
  bi = pl.program_id(0)
  j = pl.program_id(1)
  ln = lens_ref[bi]

  @pl.when(j == 0)
  def _Init():
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

  @pl.when(j * page_size < ln)
  def _Accumulate():
    slot = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                       # [1, P]
    keep = (slot < ln).astype(jnp.float32)                  # [1, P]
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


def _PallasBlockDecode(q, k_pool, v_pool, block_tables, seq_lens,
                       page_size: int, interpret: bool = False,
                       k_scale=None, v_scale=None):
  """Pallas lowering of _XlaBlockDecode. q: [B, N, H] -> [B, N, H]."""
  b, n, h = q.shape
  np_total, page, _, _ = k_pool.shape
  assert page == page_size, (page, page_size)
  t_pages = block_tables.shape[1]
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
  lens = seq_lens.astype(jnp.int32)

  # Dead logical pages clamp to the row's last live page: Pallas re-requests
  # the same physical block and elides the HBM DMA, pl.when skips compute.
  # A stale table entry past the live range therefore never reaches VMEM.
  def _PageIdx(bi, j, tables_ref, lens_ref):
    last = jnp.maximum(
        (lens_ref[bi] + page_size - 1) // page_size - 1, 0)
    last = jnp.minimum(last, t_pages - 1)
    return (tables_ref[bi, jnp.minimum(j, last)], 0, 0, 0)

  # Scale sidecar blocks resolve their page through the same prefetched
  # table lookup, so their DMAs are elided for dead pages exactly like k/v.
  def _ScaleIdx(bi, j, tables_ref, lens_ref):
    return _PageIdx(bi, j, tables_ref, lens_ref)[:3]

  in_specs = [
      pl.BlockSpec((1, n, h), lambda bi, j, t_ref, l_ref: (bi, 0, 0)),
      pl.BlockSpec((1, page_size, n, h), _PageIdx),
      pl.BlockSpec((1, page_size, n, h), _PageIdx),
  ]
  operands = [tables, lens, q, k_pool, v_pool]
  if k_scale is not None:
    in_specs += [
        pl.BlockSpec((1, n, page_size), _ScaleIdx),
        pl.BlockSpec((1, n, page_size), _ScaleIdx),
    ]
    operands += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=2,
      grid=(b, t_pages),
      in_specs=in_specs,
      out_specs=pl.BlockSpec((1, n, h),
                             lambda bi, j, t_ref, l_ref: (bi, 0, 0)),
      scratch_shapes=[
          pltpu.VMEM((n, LANES), jnp.float32),
          pltpu.VMEM((n, LANES), jnp.float32),
          pltpu.VMEM((n, h), jnp.float32),
      ],
  )
  kernel = functools.partial(_BlockDecodeKernel, page_size=page_size,
                             t_pages=t_pages)
  return pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((b, n, h), q.dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary")),
      interpret=interpret,
  )(*operands)


# -- public entries ----------------------------------------------------------


def BlockDecode(q, k_pool, v_pool, block_tables, seq_lens, *, page_size: int,
                k_scale=None, v_scale=None, lowering: str = "auto",
                interpret: bool | None = None):
  """Single-query block-table paged decode attention.

  q: [B, 1, N, H] — the newest query per sequence, ALREADY scaled (the
  caller wrote its K/V to the pool before calling; slot seq_len-1).
  k_pool/v_pool: [num_pages, page_size, N, H] global page pool.
  block_tables: [B, pages_per_seq] int32 physical page ids; entries past a
  row's live pages are arbitrary and never influence the output.
  seq_lens: [B] int32 live-slot counts (the query attends slots
  [0, seq_len)); 0 marks an inactive row, whose output is 0.
  k_scale/v_scale: [num_pages, N, page_size] f32 sidecars for int8 pools
  (both or neither); pages dequantize in-kernel via `_DequantPages`.
  lowering: 'auto' (Pallas on real TPU, XLA twin elsewhere) | 'pallas' |
  'xla'. Returns [B, 1, N, H].
  """
  assert q.ndim == 4 and q.shape[1] == 1, q.shape
  assert lowering in ("auto", "pallas", "xla"), lowering
  assert (k_scale is None) == (v_scale is None), "pass both scales or neither"
  if k_scale is not None:
    assert k_pool.dtype == jnp.int8, k_pool.dtype
  q3 = q[:, 0]
  on_tpu = jax.default_backend() == "tpu"
  if lowering == "auto":
    lowering = "pallas" if on_tpu else "xla"
  if lowering == "xla":
    out = _XlaBlockDecode(q3, k_pool, v_pool, block_tables,
                          jnp.asarray(seq_lens), page_size,
                          k_scale=k_scale, v_scale=v_scale)
  else:
    if interpret is None:
      interpret = not on_tpu
    out = _PallasBlockDecode(q3, k_pool, v_pool, block_tables,
                             jnp.asarray(seq_lens), page_size,
                             interpret=interpret,
                             k_scale=k_scale, v_scale=v_scale)
  return out[:, None]


def BlockPrefill(q, k_pool, v_pool, block_tables, q_pos, in_len, *,
                 page_size: int, k_scale=None, v_scale=None):
  """Ragged multi-query paged attention for chunked prefill steps.

  q: [B, C, N, H] pre-scaled chunk queries; query c of row b sits at global
  slot `q_pos[b] + c` and attends its own sequence's slots `<= q_pos[b] + c`
  (causal within the chunk; the chunk's K/V were written to the pool before
  this call). in_len: [B] int32 valid-query counts — queries `c >= in_len[b]`
  (decode rows' dead tail, inactive rows) return 0 and never contribute.
  k_scale/v_scale [NP, N, P] f32 sidecars dequantize int8 pools on read.
  XLA-only lowering (one fori_loop over live pages, online softmax); the
  single-query BlockDecode kernel is the steady-state path. -> [B, C, N, H].
  """
  b, c, n, h = q.shape
  np_total, page, _, _ = k_pool.shape
  assert page == page_size, (page, page_size)
  assert (k_scale is None) == (v_scale is None), "pass both scales or neither"
  t_pages = block_tables.shape[1]
  q_pos = q_pos.astype(jnp.int32)
  in_len = in_len.astype(jnp.int32)
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
  pos = q_pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]    # [B, C]
  valid = jnp.arange(c, dtype=jnp.int32)[None] < in_len[:, None]  # [B, C]
  end = q_pos + in_len
  trip = jnp.clip((jnp.max(end) + page_size - 1) // page_size, 0, t_pages)

  def _Body(j, carry):
    m, l, acc = carry
    pid = jax.lax.dynamic_index_in_dim(tables, j, axis=1, keepdims=False)
    k_page = k_pool[pid]                                   # [B, P, N, H]
    v_page = v_pool[pid]
    if k_scale is not None:
      k_page = _DequantPages(k_page, k_scale[pid])
      v_page = _DequantPages(v_page, v_scale[pid])
    slot = j * page_size + jnp.arange(page_size, dtype=jnp.int32)  # [P]
    keep = ((slot[None, None, :] <= pos[:, :, None])
            & valid[:, :, None])                           # [B, C, P]
    # [B, C, N, H] x [B, P, N, H] -> [B, C, N, P]
    s = _DotF32(q, k_page, (((3,), (3,)), ((0, 2), (0, 2))))
    s = jnp.moveaxis(s, 1, 2)                              # [B, C, N, P]
    s = jnp.where(keep[:, :, None, :], s, NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)             # [B, C, N, 1]
    m_new = jnp.maximum(m, m_cur)
    m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    # [B, C, N, P] x [B, P, N, H] -> [B, C, N, H]
    pv = _DotF32(p.astype(v_page.dtype), v_page,
                 (((3,), (1,)), ((0, 2), (0, 2))))
    pv = jnp.moveaxis(pv, 1, 2)
    return m_new, l_new, alpha * acc + pv

  m0 = jnp.full((b, c, n, 1), NEG_INF, jnp.float32)
  l0 = jnp.zeros((b, c, n, 1), jnp.float32)
  acc0 = jnp.zeros((b, c, n, h), jnp.float32)
  _, l, acc = jax.lax.fori_loop(0, trip, _Body, (m0, l0, acc0))
  return _Finish(l, acc, q.dtype)


def SupportedOnTpu(page_size: int, h: int,
                   kv_dtype: str = "float32") -> bool:
  """Whether the Pallas block-decode lowering can run on real TPU hardware.

  Same Mosaic tiling constraint as flash_decode: page_size rides the
  128-lane minor axis of the in-kernel keep tiles and h the minor axis of
  the k/v page blocks. int8 pools add no NEW constraint: the int8 minimum
  tile is (32, 128) sublanes x lanes, subsumed by the %128 gates, and the
  f32 scale sidecar's minor axis is page_size, already a lane multiple
  here. The XLA twin has no such constraint."""
  del kv_dtype  # int8 needs nothing extra today; fp8 may.
  return page_size % LANES == 0 and h % LANES == 0
