"""Fused blockwise LM-head + cross-entropy: no [B, T, V] logits, ever.

The training-side twin of `ops/flash_decode.py`: `SimpleFullSoftmax` /
`SharedEmbeddingSoftmaxLayer` materialize full `[B, T, V]` logits and then
cast them to f32 for log-softmax — at vocab 32k that tensor is the peak
activation of the whole train step, and it is the one activation
`RepeatedTransformerLayer`'s remat_policy can never save (the head sits
outside the scanned stack). This op streams the vocabulary in fixed-size
blocks with an online logsumexp, so neither the forward nor the backward
pass ever holds more than one `[rows, block]` logits tile.

Forward, per vocab block (one `hidden @ emb_block` einsum each):
  running max `m` / denominator `l` (the flash-attention online-softmax
  recurrence), the gathered label logit, the running sum of logits (for
  label smoothing's uniform term), and a running argmax. From those five
  scalars per row: lse = m + log(l) and
  xent = lse - (1-ls) * label_logit - (ls/V) * sum_logits,
  algebraically identical to dense `-sum(q * log_softmax(logits))` with
  q = (1-ls) * onehot + ls/V.

Backward (`jax.custom_vjp`): recomputes each block's logits and softmax
from the saved lse and accumulates
  d_logits = ct_xent * (softmax - q) [+ the lse/label/sum cotangents]
  d_hidden += d_logits @ emb_block;  d_emb_block = d_logits^T @ hidden
block-by-block, so the backward is as memory-lean as the forward. The
`logits_soft_max` tanh cap chains through as (1 - (logit/cap)^2).

Two lowerings of the same algorithm (the `flash_decode` twin-kernel
pattern), both routing per-block math through `_BlockLogits`/`_BlockStats`:

- `_XlaStats` — a `lax.scan` over vocab blocks; the reference
  implementation and the CPU path (Pallas interpret mode charges ~8-10 ms
  per grid step regardless of the compute inside).
- `_PallasStats` — a Pallas TPU kernel, grid `(row_tiles, vocab_blocks)`
  with the running stats in f32 VMEM scratch broadcast across the 128-lane
  minor dim (the `flash_attention` layout trick).

Numerics (see docs/fused_xent.md):
- block logits are computed with f32 accumulation
  (`preferred_element_type`), bias-add / tanh cap / all running stats in
  f32. Under bf16 fprop this is slightly MORE accurate than the dense
  path (which forms bf16 logits before the f32 log-softmax) — close, not
  bit-exact. With f32 params both paths agree to float tolerance.
- labels must lie in [0, V); out-of-range labels give lse (dense gives 0).
- `per_example_xent`, `label_log_prob` and `lse` carry exact gradients;
  `argmax` is integer (no tangent).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu.ops.flash_attention import (  # single source of truth
    LANES, NEG_INF, SUBLANES)

_BIG_IDX = 2 ** 30  # plain int: jnp scalars would be captured consts in Pallas


class _Cfg(NamedTuple):
  """Static (hashable) config for the custom_vjp core."""
  block_size: int
  vocab: int          # true vocab size V (blocks may overhang, masked)
  vd: bool            # weight layout: True = [V, D], False = [D, V]
  soft_cap: float     # logits_soft_max tanh cap; 0 = off
  label_smoothing: float
  lowering: str       # 'auto' | 'pallas' | 'xla'
  interpret: bool | None


class FusedXentOutput(NamedTuple):
  """All leading dims match class_ids; everything but argmax is f32."""
  per_example_xent: jax.Array   # smoothed cross-entropy
  label_log_prob: jax.Array     # log softmax(logits)[label] (no smoothing)
  lse: jax.Array                # logsumexp over the full vocab
  argmax: jax.Array             # int32 argmax over the full vocab


def _DotF32(a, b, dims):
  """dot_general with f32 accumulation, native input dtype (MXU fast path)."""
  return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _NumBlocks(vocab: int, block: int) -> int:
  return -(-vocab // block)


def _BlockLogits(x, w_blk, b_blk, soft_cap: float, vd: bool):
  """One block of capped logits in f32.

  x: [R, D] (fprop dtype), w_blk: [bs, D] (vd) or [D, bs] (dv),
  b_blk: [1, bs]. Returns f32 [R, bs]. Shared by both lowerings so the
  float-op sequence matches across Pallas and XLA.
  """
  if vd:
    s = _DotF32(x, w_blk, (((1,), (1,)), ((), ())))
  else:
    s = _DotF32(x, w_blk, (((1,), (0,)), ((), ())))
  s = s + b_blk.astype(jnp.float32)
  if soft_cap > 0.0:
    s = soft_cap * jnp.tanh(s / soft_cap)
  return s


def _BlockStats(s, start, labels, valid, carry):
  """Online-stats update for one vocab block.

  s: f32 [R, bs] capped logits, start: traced int32 global offset of this
  block, labels: int32 [R, 1], valid: f32 [1, bs] (0.0 marks the padded
  overhang past V) or None when the block is statically known to be fully
  in-vocab — the masking passes vanish from the compiled loop then, which
  is why configs should prefer block sizes dividing V. carry:
  (m, l, sum_logits, label_logit, amax) with float stats [R, 1], amax
  int32 [R, 1] and sum_logits None when label smoothing is off (its only
  consumer). Both lowerings call exactly this, so Pallas and XLA agree
  (to dot-blocking tolerance).
  """
  m, l, sumlog, llog, amax = carry
  s_m = s if valid is None else jnp.where(valid > 0.5, s, NEG_INF)
  m_cur = jnp.max(s_m, axis=-1, keepdims=True)            # [R, 1]
  m_new = jnp.maximum(m, m_cur)
  # All-masked-so-far rows have m_new = NEG_INF; exp(s - m_new) would turn
  # masked entries into exp(0) = 1. Same guard as flash_attention.
  m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
  p = jnp.exp(s_m - m_safe)
  alpha = jnp.exp(m - m_new)                              # [R, 1]
  l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
  if sumlog is not None:
    masked = s if valid is None else jnp.where(valid > 0.5, s, 0.0)
    sumlog = sumlog + jnp.sum(masked, axis=-1, keepdims=True)
  iota = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)  # [R, bs]
  onehot = iota == (labels - start)
  llog_new = llog + jnp.sum(jnp.where(onehot, s, 0.0), axis=-1,
                            keepdims=True)
  # First-occurrence argmax (jnp.argmax tie-break): within the block the
  # smallest index attaining the max; across blocks strict > keeps the
  # earlier block on ties.
  idx_cur = start + jnp.min(
      jnp.where(s_m >= m_cur, iota, _BIG_IDX), axis=-1, keepdims=True)
  amax_new = jnp.where(m_cur > m, idx_cur, amax)
  return m_new, l_new, sumlog, llog_new, amax_new


def _InitCarry(rows: int, need_sumlog: bool):
  return (jnp.full((rows, 1), NEG_INF, jnp.float32),
          jnp.zeros((rows, 1), jnp.float32),
          jnp.zeros((rows, 1), jnp.float32) if need_sumlog else None,
          jnp.zeros((rows, 1), jnp.float32),
          jnp.zeros((rows, 1), jnp.int32))


def _PadVocab(w, b, cfg: _Cfg):
  """Pads weight/bias so the block loop is uniform; no-op (and no copy)
  when block_size divides V — configs should prefer that."""
  nb = _NumBlocks(cfg.vocab, cfg.block_size)
  v_pad = nb * cfg.block_size
  extra = v_pad - cfg.vocab
  if extra:
    w = jnp.pad(w, ((0, extra), (0, 0)) if cfg.vd else ((0, 0), (0, extra)))
    b = jnp.pad(b, (0, extra))
  return w, b, nb


def _SliceBlock(w, b, start, cfg: _Cfg):
  bs = cfg.block_size
  if cfg.vd:
    w_blk = jax.lax.dynamic_slice_in_dim(w, start, bs, axis=0)
  else:
    w_blk = jax.lax.dynamic_slice_in_dim(w, start, bs, axis=1)
  b_blk = jax.lax.dynamic_slice(b, (start,), (bs,))[None, :]
  return w_blk, b_blk


def _ValidMask(start, cfg: _Cfg):
  """None (statically) when every block is fully in-vocab: the masking
  passes disappear from the compiled per-block loop."""
  if cfg.vocab % cfg.block_size == 0:
    return None
  iota = jax.lax.broadcasted_iota(jnp.int32, (1, cfg.block_size), 1)
  return ((start + iota) < cfg.vocab).astype(jnp.float32)


# -- XLA reference lowering (the CPU path) -----------------------------------


def _XlaStats(x, w, b, labels, cfg: _Cfg):
  """x: [M, D], w: [V, D] or [D, V], b: [V], labels: int32 [M]
  -> (lse, label_logit, sum_logits, argmax), each [M]."""
  m_rows = x.shape[0]
  w_pad, b_pad, nb = _PadVocab(w, b, cfg)
  labels2 = labels[:, None]

  def _Body(carry, i):
    start = i * cfg.block_size
    w_blk, b_blk = _SliceBlock(w_pad, b_pad, start, cfg)
    s = _BlockLogits(x, w_blk, b_blk, cfg.soft_cap, cfg.vd)
    return _BlockStats(s, start, labels2, _ValidMask(start, cfg), carry), ()

  (m, l, sumlog, llog, amax), _ = jax.lax.scan(
      _Body, _InitCarry(m_rows, cfg.label_smoothing > 0.0),
      jnp.arange(nb, dtype=jnp.int32))
  lse = m[:, 0] + jnp.log(jnp.maximum(l[:, 0], 1e-37))
  return lse, llog[:, 0], None if sumlog is None else sumlog[:, 0], amax[:, 0]


# -- Pallas TPU kernel -------------------------------------------------------


def _FwdKernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, llog_ref, sum_ref,
               amax_ref, m_scr, l_scr, sum_scr, llog_scr, amax_scr, *,
               cfg: _Cfg, nb: int):
  """One (row_tile, vocab_block) program step; stats carried in scratch."""
  j = pl.program_id(1)

  @pl.when(j == 0)
  def _Init():
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    sum_scr[:] = jnp.zeros_like(sum_scr)
    llog_scr[:] = jnp.zeros_like(llog_scr)
    amax_scr[:] = jnp.zeros_like(amax_scr)

  start = j * cfg.block_size
  need_sumlog = cfg.label_smoothing > 0.0
  s = _BlockLogits(x_ref[:], w_ref[:], b_ref[:1, :], cfg.soft_cap, cfg.vd)
  carry = (m_scr[:, :1], l_scr[:, :1],
           sum_scr[:, :1] if need_sumlog else None, llog_scr[:, :1],
           amax_scr[:, :1])
  m, l, sumlog, llog, amax = _BlockStats(
      s, start, lab_ref[:, :1], _ValidMask(start, cfg), carry)
  m_scr[:] = jnp.broadcast_to(m, m_scr.shape)
  l_scr[:] = jnp.broadcast_to(l, l_scr.shape)
  if need_sumlog:
    sum_scr[:] = jnp.broadcast_to(sumlog, sum_scr.shape)
  llog_scr[:] = jnp.broadcast_to(llog, llog_scr.shape)
  amax_scr[:] = jnp.broadcast_to(amax, amax_scr.shape)

  @pl.when(j == nb - 1)
  def _Emit():
    lse = m_scr[:, :1] + jnp.log(jnp.maximum(l_scr[:, :1], 1e-37))
    lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape)
    llog_ref[:] = llog_scr[:]
    sum_ref[:] = sum_scr[:]
    amax_ref[:] = amax_scr[:]


_MIB = 1 << 20
# The compiler's default scoped-VMEM limit, and how far the kernel may raise
# it: a v5e TensorCore has 128 MiB of VMEM (Google Cloud TPU documentation,
# "TPU v5e"), the rest is left to the surrounding program.
_VMEM_DEFAULT_BYTES = 16 * _MIB
_VMEM_MAX_BYTES = 96 * _MIB


def _VmemLimitBytes(rb: int, bs: int, d: int, x_itemsize: int,
                    w_itemsize: int) -> int:
  """Scoped VMEM `_FwdKernel` asks for at a [rb, d] x [bs, d] tile pair.

  Pallas double-buffers every blocked operand and output; scratch and the
  in-kernel temporaries are single:
    inputs  2 * (rb*d*x_itemsize + bs*d*w_itemsize        x and weight tile
                 + SUBLANES*bs*4 + rb*LANES*4)            bias, labels
    outputs 2 * 4 * rb*LANES*4                            four stat columns
    scratch 5 * rb*LANES*4                                five running stats
    temps   6 * rb*bs*4          logits, masked logits, p, iota and the
                                 one-hot / argmax selects, all f32 [rb, bs]
  At rb=128, bs=1024, d=2048 that is 13 MiB with bf16 weights and 22 MiB
  with f32 weights: the weight tile dominates, and the f32 one does not fit
  the default. Raises where even the cap is too small."""
  stat = rb * LANES * 4
  inputs = rb * d * x_itemsize + bs * d * w_itemsize + SUBLANES * bs * 4 + stat
  need = 2 * (inputs + 4 * stat) + 5 * stat + 6 * rb * bs * 4
  if need > _VMEM_MAX_BYTES:
    raise ValueError(
        f"FusedXent Pallas tile [{bs}, {d}] x {w_itemsize} B needs "
        f"{need / _MIB:.0f} MiB of VMEM, over the {_VMEM_MAX_BYTES // _MIB} "
        "MiB the kernel may take; use a smaller block_size")
  return max(need, _VMEM_DEFAULT_BYTES)


def _PallasStats(x, w, b, labels, cfg: _Cfg, interpret: bool):
  """Pallas lowering of _XlaStats (row-tiled grid, stats in VMEM)."""
  m_rows, d = x.shape
  rb = min(128, SUBLANES * _NumBlocks(m_rows, SUBLANES))
  m_pad = rb * _NumBlocks(m_rows, rb)
  if m_pad != m_rows:
    x = jnp.pad(x, ((0, m_pad - m_rows), (0, 0)))
    labels = jnp.pad(labels, (0, m_pad - m_rows))
  w_pad, b_pad, nb = _PadVocab(w, b, cfg)
  bs = cfg.block_size
  # Row stats / per-row ints broadcast across the 128-lane minor dim and
  # the bias across SUBLANES (same Mosaic tiling trick as flash_attention).
  lab2 = jnp.broadcast_to(labels[:, None], (m_pad, LANES))
  b2 = jnp.broadcast_to(b_pad[None, :], (SUBLANES, nb * bs))
  if cfg.vd:
    w_spec = pl.BlockSpec((bs, d), lambda mi, j: (j, 0))
  else:
    w_spec = pl.BlockSpec((d, bs), lambda mi, j: (0, j))
  out_shape = [jax.ShapeDtypeStruct((m_pad, LANES), jnp.float32)] * 3 + [
      jax.ShapeDtypeStruct((m_pad, LANES), jnp.int32)]
  stat_spec = pl.BlockSpec((rb, LANES), lambda mi, j: (mi, 0))
  kernel = functools.partial(_FwdKernel, cfg=cfg, nb=nb)
  lse, llog, sumlog, amax = pl.pallas_call(
      kernel,
      grid=(m_pad // rb, nb),
      in_specs=[
          pl.BlockSpec((rb, d), lambda mi, j: (mi, 0)),
          w_spec,
          pl.BlockSpec((SUBLANES, bs), lambda mi, j: (0, j)),
          stat_spec,
      ],
      out_specs=[stat_spec] * 4,
      out_shape=out_shape,
      scratch_shapes=[pltpu.VMEM((rb, LANES), jnp.float32)] * 4 + [
          pltpu.VMEM((rb, LANES), jnp.int32)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary"),
          vmem_limit_bytes=_VmemLimitBytes(
              rb, bs, d, x.dtype.itemsize, w_pad.dtype.itemsize)),
      interpret=interpret,
  )(x, w_pad, b2, lab2)
  return (lse[:m_rows, 0], llog[:m_rows, 0],
          sumlog[:m_rows, 0] if cfg.label_smoothing > 0.0 else None,
          amax[:m_rows, 0])


# -- custom_vjp core ---------------------------------------------------------


def _Stats(x, w, b, labels, cfg: _Cfg):
  on_tpu = jax.default_backend() == "tpu"
  lowering = cfg.lowering
  if lowering == "auto":
    lowering = "pallas" if (
        on_tpu and SupportedOnTpu(cfg.block_size, x.shape[-1])) else "xla"
  if lowering == "xla":
    return _XlaStats(x, w, b, labels, cfg)
  interpret = cfg.interpret if cfg.interpret is not None else not on_tpu
  return _PallasStats(x, w, b, labels, cfg, interpret=interpret)


def _Finish(lse, llog, sumlog, cfg: _Cfg):
  ls = cfg.label_smoothing
  if ls > 0.0:
    return lse - (1.0 - ls) * llog - (ls / cfg.vocab) * sumlog
  return lse - llog  # sumlog is statically None then


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _FusedXentCore(x, w, b, labels, cfg: _Cfg):
  lse, llog, sumlog, amax = _Stats(x, w, b, labels, cfg)
  return _Finish(lse, llog, sumlog, cfg), llog - lse, lse, amax


def _CoreFwd(x, w, b, labels, cfg: _Cfg):
  lse, llog, sumlog, amax = _Stats(x, w, b, labels, cfg)
  out = (_Finish(lse, llog, sumlog, cfg), llog - lse, lse, amax)
  return out, (x, w, b, labels, lse)


def _CoreBwd(cfg: _Cfg, res, cts):
  """Block-recompute backward: d_logits = ct_xent * (softmax - q) + the
  label_log_prob / lse cotangents, chained through the tanh cap; never
  materializes more than one [M, block] tile."""
  x, w, b, labels, lse = res
  g_xent, g_llp, g_lse, _ = cts  # argmax is integer: no tangent
  m_rows = x.shape[0]
  ls = cfg.label_smoothing
  w_pad, b_pad, nb = _PadVocab(w, b, cfg)
  labels2 = labels[:, None]
  lse2 = lse[:, None]

  def _AsCol(g):
    # Symbolic-zero cotangents arrive as float0 ad.Zero stand-ins only for
    # whole outputs jax never touched; materialize as f32 columns.
    if g is None or getattr(g, "dtype", None) == jax.dtypes.float0:
      return jnp.zeros((m_rows, 1), jnp.float32)
    return g.astype(jnp.float32)[:, None]

  g1, g2, g3 = _AsCol(g_xent), _AsCol(g_llp), _AsCol(g_lse)
  # xent = lse - (1-ls)*llog - ls/V*sumlog; llp = llog - lse.
  # d/dlogit: lse -> softmax, llog -> onehot, sumlog -> 1 (on valid
  # entries). Collect the three cotangents into per-term coefficients:
  coef_p = g1 - g2 + g3              # softmax term
  coef_oh = g2 - (1.0 - ls) * g1     # onehot term
  coef_ones = -(ls / cfg.vocab) * g1 if ls > 0.0 else None

  def _Body(dx, i):
    start = i * cfg.block_size
    w_blk, b_blk = _SliceBlock(w_pad, b_pad, start, cfg)
    s = _BlockLogits(x, w_blk, b_blk, cfg.soft_cap, cfg.vd)
    valid = _ValidMask(start, cfg)
    s_m = s if valid is None else jnp.where(valid > 0.5, s, NEG_INF)
    p = jnp.exp(s_m - lse2)
    iota = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = (iota == (labels2 - start)).astype(jnp.float32)
    # Invalid entries vanish on their own: p = exp(NEG_INF - lse) = 0 and
    # the onehot never matches past V — only the smoothing term needs the
    # explicit mask.
    dz = coef_p * p + coef_oh * onehot
    if coef_ones is not None:
      dz = dz + (coef_ones if valid is None else coef_ones * valid)
    if cfg.soft_cap > 0.0:
      dz = dz * (1.0 - (s / cfg.soft_cap) ** 2)
    # Matmuls in fprop dtype with f32 accumulation, like the dense bwd
    # under mixed precision.
    dzc = dz.astype(x.dtype)
    if cfg.vd:
      dx = dx + _DotF32(dzc, w_blk, (((1,), (0,)), ((), ())))
    else:
      dx = dx + _DotF32(dzc, w_blk, (((1,), (1,)), ((), ())))
    # Each block's weight rows get their whole gradient from this one
    # step: emit [bs, D] (both layouts) as stacked scan outputs — written
    # in place, unlike a carried [V, D] buffer, which XLA copies per step.
    dw_blk = _DotF32(dzc, x, (((0,), (0,)), ((), ())))         # [bs, D]
    return dx, (dw_blk.astype(w.dtype), jnp.sum(dz, axis=0))

  dx, (dw, db) = jax.lax.scan(_Body, jnp.zeros(x.shape, jnp.float32),
                              jnp.arange(nb, dtype=jnp.int32))
  dw = dw.reshape(-1, x.shape[1])[:cfg.vocab]                  # [V, D]
  if not cfg.vd:
    dw = dw.T
  d_labels = np.zeros(labels.shape, jax.dtypes.float0)
  return (dx.astype(x.dtype), dw,
          db.reshape(-1)[:cfg.vocab].astype(b.dtype), d_labels)


_FusedXentCore.defvjp(_CoreFwd, _CoreBwd)


# -- public entry ------------------------------------------------------------


def FusedXent(inputs, weight, class_ids, *, block_size: int, bias=None,
              logits_soft_max: float = 0.0, label_smoothing: float = 0.0,
              weight_layout: str = "vd", lowering: str = "auto",
              interpret: bool | None = None) -> FusedXentOutput:
  """Blockwise fused LM-head + softmax cross-entropy.

  inputs: [..., D] activations (fprop dtype). weight: [V, D]
  (weight_layout='vd', the tied-embedding layout) or [D, V] ('dv', the
  SimpleFullSoftmax layout). class_ids: int32 [...] in [0, V).
  bias: optional [V]. logits_soft_max: tanh cap (0 = off).
  lowering: 'auto' (Pallas on real TPU when `SupportedOnTpu`, XLA
  elsewhere), 'pallas', or 'xla'. interpret: forced interpret mode for the
  Pallas lowering (auto: True off-TPU).

  Gradients flow to inputs/weight/bias through per_example_xent,
  label_log_prob and lse. Prefer a block_size dividing V: a ragged tail
  costs one padded copy of the weight per step.
  """
  assert weight_layout in ("vd", "dv"), weight_layout
  assert lowering in ("auto", "pallas", "xla"), lowering
  vd = weight_layout == "vd"
  vocab = weight.shape[0] if vd else weight.shape[1]
  d = weight.shape[1] if vd else weight.shape[0]
  assert inputs.shape[-1] == d, (inputs.shape, weight.shape)
  assert block_size > 0
  lead = class_ids.shape
  assert inputs.shape[:-1] == lead, (inputs.shape, lead)
  x = inputs.reshape(-1, d)
  labels = class_ids.reshape(-1).astype(jnp.int32)
  b = bias if bias is not None else jnp.zeros((vocab,), weight.dtype)
  cfg = _Cfg(block_size=int(min(block_size, vocab)),
             vocab=int(vocab), vd=vd, soft_cap=float(logits_soft_max),
             label_smoothing=float(label_smoothing), lowering=lowering,
             interpret=interpret)
  xent, llp, lse, amax = _FusedXentCore(x, weight, b, labels, cfg)
  return FusedXentOutput(
      per_example_xent=xent.reshape(lead),
      label_log_prob=llp.reshape(lead),
      lse=lse.reshape(lead),
      argmax=amax.reshape(lead))


def SupportedOnTpu(block_size: int, d: int) -> bool:
  """Whether the Pallas lowering can run on real TPU hardware.

  Conservative: the vocab block rides the 128-lane minor axis of the
  logits tile and D the minor axis of the activation/weight blocks, so
  both must be LANES-aligned for Mosaic tiling. The XLA lowering has no
  such constraint — off-TPU callers should not consult this."""
  return block_size % LANES == 0 and d % LANES == 0
