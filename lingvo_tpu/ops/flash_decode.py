"""Length-aware paged flash decode over a pre-allocated KV cache.

The incremental-decode hot op: one query token per sequence attends to a
`[B, S, N, H]` KV cache of which only slots `[0, time_step]` have ever been
written. The dense path (`attention.py` ExtendStep) reads all S slots every
step and masks the unwritten tail — O(S) work regardless of how little of
the cache is live. This op blocks the cache time axis into fixed-size
*pages* and only reads pages up to `time_step` (the tail page is masked
in-kernel), the "Ragged Paged Attention" formulation specialized to a
single query per sequence.

Two lowerings of the SAME algorithm, asserted bit-identical in tests:

- `_PallasDecode` — a Pallas TPU kernel. Grid `(B, num_pages)`; the page
  index map clamps to the last live page via a scalar-prefetched
  `time_step` (`pltpu.PrefetchScalarGridSpec`), so Pallas elides the HBM
  DMAs for dead pages, and `pl.when` skips their compute. Online softmax
  (running max / denominator / accumulator) in f32 VMEM scratch, same
  layout tricks as `ops/flash_attention.py` (per-row stats broadcast
  across the 128-lane minor dim).
- `_XlaDecode` — a pure-XLA twin: `lax.fori_loop` with a *dynamic* trip
  count of `time_step // page_size + 1` over `dynamic_slice`d pages. This
  is the CPU serving path: Pallas interpret mode charges ~8-10 ms per grid
  step on CPU regardless of the compute inside, which would bury the
  paging win; the XLA loop actually skips dead pages.

Both lowerings route every page through `_PageAttend`, so the float-op
sequence is identical and interpret-mode equality holds bitwise.

Contract differences from FlashAttention:
- q arrives PRE-SCALED (the caller applies per-dim-scale / 1/sqrt(h));
  no internal scaling.
- no causal masking beyond the `slot <= time_step` length mask (the one
  query IS the newest position).
- a fully-masked row (every live slot padded) returns 0, not the dense
  path's uniform-softmax garbage; callers never expose such rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu.ops.flash_attention import (  # single source of truth
    LANES, NEG_INF, SUBLANES)


def _DotF32(a, b, dims):
  """dot_general with f32 accumulation, native input dtype (MXU fast path)."""
  return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _PageAttend(q, k_page, v_page, keep, m, l, acc):
  """One page of online-softmax attention for one sequence.

  q: [N, H] (pre-scaled), k_page/v_page: [P, N, H], keep: f32 [1, P]
  (1.0 = attend, 0.0 = masked: dead slot or cache padding),
  m/l: f32 [N, 1] running max / denominator, acc: f32 [N, H].
  Returns updated (m, l, acc). Both lowerings call exactly this, so the
  float-op sequence (and thus the bits) match across Pallas and XLA.

  The two products are head-batched matrix-vector dots. Mosaic has no dot
  whose left operand lacks a free dimension (contracting q[N, H] against
  k_page[P, N, H] with N as the batch dim is refused by the TPU compiler),
  so the single query row carries a unit free dim: [N, 1, H] x [P, N, H].
  """
  # [N, 1, H] x [P, N, H] -> [N, 1, P], contraction over H, batch over N.
  s = _DotF32(q[:, None, :], k_page, (((2,), (2,)), ((0,), (1,))))[:, 0]
  s = jnp.where(keep > 0.5, s, NEG_INF)                  # [N, P]
  m_cur = jnp.max(s, axis=-1, keepdims=True)             # [N, 1]
  m_new = jnp.maximum(m, m_cur)
  # All-masked-so-far rows have m_new = NEG_INF; exp(s - m_new) would turn
  # masked entries into exp(0) = 1. Same guard as flash_attention._FwdKernel.
  m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
  p = jnp.exp(s - m_safe)                                # f32 [N, P]
  alpha = jnp.exp(m - m_new)                             # [N, 1]
  l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
  # [N, 1, P] x [P, N, H] -> [N, 1, H]: contraction over P, batch over N.
  pv = _DotF32(p.astype(v_page.dtype)[:, None, :], v_page,
               (((2,), (0,)), ((0,), (1,))))[:, 0]
  acc_new = acc * alpha + pv
  return m_new, l_new, acc_new


def _Finish(l, acc, dtype):
  return (acc / jnp.maximum(l, 1e-20)).astype(dtype)


# -- XLA twin (the CPU path) -------------------------------------------------


def _XlaDecode(q, k_cache, v_cache, time_step, page_size: int,
               cache_paddings=None):
  """q: [B, N, H], caches [B, S, N, H], time_step scalar int32 -> [B, N, H].

  Dynamic-trip-count fori_loop over live pages only: the work per decode
  step is O(time_step), not O(S).
  """
  b, s, n, h = k_cache.shape
  assert s % page_size == 0, (s, page_size)
  t = time_step.astype(jnp.int32)
  # t is in [0, s-1] per the ExtendStep contract; the clamp keeps an
  # out-of-contract t >= s from re-reading the (dynamic-slice-clamped) last
  # page with unclamped slot ids — the Pallas grid never exceeds num_pages,
  # and the twins must agree bitwise.
  num_live = jnp.minimum(t // page_size + 1, s // page_size)

  if cache_paddings is None:
    pad = jnp.zeros((b, s), jnp.float32)
  else:
    pad = cache_paddings.astype(jnp.float32)

  batched_attend = jax.vmap(_PageAttend)

  def _Body(pi, carry):
    m, l, acc = carry
    start = pi * page_size
    k_page = jax.lax.dynamic_slice_in_dim(k_cache, start, page_size, axis=1)
    v_page = jax.lax.dynamic_slice_in_dim(v_cache, start, page_size, axis=1)
    pad_page = jax.lax.dynamic_slice_in_dim(pad, start, page_size, axis=1)
    slot = start + jnp.arange(page_size, dtype=jnp.int32)   # [P]
    keep = ((slot[None, :] <= t).astype(jnp.float32)
            * (1.0 - pad_page))[:, None, :]                 # [B, 1, P]
    return batched_attend(q, k_page, v_page, keep, m, l, acc)

  m0 = jnp.full((b, n, 1), NEG_INF, jnp.float32)
  l0 = jnp.zeros((b, n, 1), jnp.float32)
  acc0 = jnp.zeros((b, n, h), jnp.float32)
  _, l, acc = jax.lax.fori_loop(0, num_live, _Body, (m0, l0, acc0))
  return _Finish(l, acc, q.dtype)


# -- Pallas TPU kernel -------------------------------------------------------


def _DecodeKernel(t_ref, q_ref, k_ref, v_ref, pad_ref, out_ref, m_scr, l_scr,
                  acc_scr, *, page_size: int, num_pages: int):
  """One (batch, page) program step; scratch carried across the page dim."""
  j = pl.program_id(1)
  t = t_ref[0]

  @pl.when(j == 0)
  def _Init():
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

  @pl.when(j * page_size <= t)
  def _Accumulate():
    q = q_ref[0]                                        # [N, H]
    k_page = k_ref[0]                                   # [P, N, H]
    v_page = v_ref[0]
    slot = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                   # [1, P]
    keep = ((slot <= t).astype(jnp.float32)
            * (1.0 - pad_ref[0][:1, :]))                # [1, P]
    m, l, acc = _PageAttend(q, k_page, v_page, keep, m_scr[:, :1],
                            l_scr[:, :1], acc_scr[:])
    m_scr[:] = jnp.broadcast_to(m, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l, l_scr.shape)
    acc_scr[:] = acc

  @pl.when(j == num_pages - 1)
  def _Emit():
    out_ref[0] = _Finish(l_scr[:, :1], acc_scr[:], out_ref.dtype)


def _PallasDecode(q, k_cache, v_cache, time_step, page_size: int,
                  cache_paddings=None, interpret: bool = False):
  """Pallas lowering of _XlaDecode. q: [B, N, H] -> [B, N, H]."""
  b, s, n, h = k_cache.shape
  assert s % page_size == 0, (s, page_size)
  num_pages = s // page_size
  if cache_paddings is None:
    pad = jnp.zeros((b, s), jnp.float32)
  else:
    pad = cache_paddings.astype(jnp.float32)
  # kv-side mask rides the same SUBLANES trick as flash_attention's segment
  # ids: broadcast over sublanes with the time axis minor.
  pad3 = jnp.broadcast_to(pad[:, None, :], (b, SUBLANES, s))
  t_arr = jnp.reshape(time_step.astype(jnp.int32), (1,))

  # Clamp dead pages to the last live page: Pallas re-requests the same
  # block and elides the DMA, so dead pages cost neither HBM bandwidth nor
  # (thanks to pl.when) compute.
  def _PageIdx(j, t_ref):
    return jnp.minimum(j, t_ref[0] // page_size)

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=1,
      grid=(b, num_pages),
      in_specs=[
          pl.BlockSpec((1, n, h), lambda bi, j, t_ref: (bi, 0, 0)),
          pl.BlockSpec((1, page_size, n, h),
                       lambda bi, j, t_ref: (bi, _PageIdx(j, t_ref), 0, 0)),
          pl.BlockSpec((1, page_size, n, h),
                       lambda bi, j, t_ref: (bi, _PageIdx(j, t_ref), 0, 0)),
          pl.BlockSpec((1, SUBLANES, page_size),
                       lambda bi, j, t_ref: (bi, 0, _PageIdx(j, t_ref))),
      ],
      out_specs=pl.BlockSpec((1, n, h), lambda bi, j, t_ref: (bi, 0, 0)),
      scratch_shapes=[
          pltpu.VMEM((n, LANES), jnp.float32),
          pltpu.VMEM((n, LANES), jnp.float32),
          pltpu.VMEM((n, h), jnp.float32),
      ],
  )
  kernel = functools.partial(_DecodeKernel, page_size=page_size,
                             num_pages=num_pages)
  return pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((b, n, h), q.dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary")),
      interpret=interpret,
  )(t_arr, q, k_cache, v_cache, pad3)


# -- public entry ------------------------------------------------------------


def FlashDecode(q, k_cache, v_cache, time_step, *, page_size: int,
                cache_paddings=None, lowering: str = "auto",
                interpret: bool | None = None):
  """Paged single-token decode attention.

  q: [B, 1, N, H] — the newest query, ALREADY scaled (per-dim scale or
  1/sqrt(h); unlike FlashAttention nothing is applied internally).
  k_cache/v_cache: [B, S, N, H] with slots [0, time_step] live (the caller
  writes slot `time_step` before calling). time_step: scalar int32.
  cache_paddings: optional [B, S] f32, 1.0 = never attend this slot.
  lowering: 'auto' (Pallas on real TPU, XLA twin elsewhere), 'pallas',
  or 'xla'. interpret: forced interpret mode for the Pallas lowering
  (auto: True off-TPU). Returns [B, 1, N, H].
  """
  assert q.ndim == 4 and q.shape[1] == 1, q.shape
  assert lowering in ("auto", "pallas", "xla"), lowering
  q3 = q[:, 0]
  on_tpu = jax.default_backend() == "tpu"
  if lowering == "auto":
    lowering = "pallas" if on_tpu else "xla"
  if lowering == "xla":
    out = _XlaDecode(q3, k_cache, v_cache, jnp.asarray(time_step),
                     page_size, cache_paddings)
  else:
    if interpret is None:
      interpret = not on_tpu
    out = _PallasDecode(q3, k_cache, v_cache, jnp.asarray(time_step),
                        page_size, cache_paddings, interpret=interpret)
  return out[:, None]


def SupportedShape(max_len: int, page_size: int) -> bool:
  """Whether a [B, max_len, N, H] cache can take the paged path."""
  return page_size > 0 and max_len % page_size == 0 and max_len >= page_size


def SupportedOnTpu(page_size: int, h: int) -> bool:
  """Whether the Pallas lowering can run on real TPU hardware.

  Conservative: page_size rides the 128-lane minor axis of the pad/keep
  tiles and h the minor axis of the k/v page blocks, so both must be
  LANES-aligned for Mosaic tiling (small shapes fail to lower or pad
  severely). The XLA twin has no such constraint — off-TPU callers should
  not consult this."""
  return page_size % LANES == 0 and h % LANES == 0
