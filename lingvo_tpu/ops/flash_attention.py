"""Flash attention: fused blocked attention as Pallas TPU kernels.

The hot op behind long-context training: never materializes the [T, T]
probability matrix. Forward and backward are both Pallas kernels (the
reference's only recourse was approximate windowed/chunked attention,
`batch_major_attention.py:2656,4008`; it has no fused exact attention).

Design (TPU-first):
- 3D sequential grid `(batch*heads, q_block, k_block)` with K/V streamed
  through VMEM by BlockSpec — the kernel never holds more than one
  `[block, head_dim]` tile of K/V, so VMEM use is O(block * h), independent
  of sequence length. Pallas double-buffers the HBM->VMEM DMAs across grid
  steps automatically.
- Online softmax in f32 VMEM scratch (running max `m`, denominator `l`,
  accumulator `acc`) carried across the innermost (k) grid dimension.
- Forward also emits the logsumexp `lse = m + log(l)` per query row; the
  backward kernels recompute probabilities from (q, k, lse) per block —
  O(T) residual memory instead of O(T^2).
- Backward = two kernels, matching the standard flash-attention backward:
  a dK/dV pass (grid over k blocks, streaming q blocks) and a dQ pass
  (grid over q blocks, streaming k blocks), with
  `delta = rowsum(dout * out)` precomputed in XLA.
- Causal masking skips fully-masked blocks via `pl.when` (no FLOPs, no
  wrong-bound bug when block_q != block_k).

On CPU the kernels run in interpret mode (used by tests for exactness
against plain attention).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30

# Per-row stats (running max, denominator, logsumexp, delta) are stored
# broadcast across a 128-lane minor dim: TPU VMEM/HBM are (8, 128)-tiled and
# the Mosaic lowering rejects 2D blocks whose minor dims aren't tile-aligned
# (the round-1 on-hardware failure; same layout as jax's own TPU flash
# kernel's l/m residuals). Segment ids use the same trick: q-side ids
# broadcast over LANES, kv-side ids over SUBLANES with t on the minor axis.
LANES = 128
SUBLANES = 8

# Off-TPU, the Pallas kernel runs in interpret mode (~8-10 ms per grid step
# regardless of the compute inside); below this many T*N*H elements the
# plain-XLA lowering wins outright — bench measured flash_speedup 0.798 at
# [1, 256, 2, 32] — so auto-selected interpret mode falls back to XLA.
# Explicit `interpret=True` always runs the kernel (that's how the
# exactness tests exercise it).
_XLA_FALLBACK_MAX_ELEMS = 1 << 21


def _ApplyCausalMask(s, q_start, k_start, block_q: int, block_k: int):
  q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
  k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
  return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _ApplySegmentMask(s, sq_ref, sk_ref, block_q: int, block_k: int):
  """Masks cross-segment pairs: seg_q == seg_k keeps a pair.

  Padding carries segment 0, so pad queries still attend pad keys — every
  row keeps at least its diagonal, the online-softmax denominator stays
  well-conditioned, and pad outputs are finite garbage that the loss mask
  zeroes (their dout is exactly 0, so no gradient leaks through them).
  """
  del block_q, block_k
  sq = sq_ref[0][:, :1]    # [block_q, LANES] -> [block_q, 1]
  sk = sk_ref[0][:1, :]    # [SUBLANES, block_k] -> [1, block_k]
  return jnp.where(sq == sk, s, NEG_INF)


def _DotF32(a, b, contract):
  """Matmul keeping the inputs' native dtype with f32 accumulation.

  Pre-casting bf16 operands to f32 (the obvious way to get f32 math) forces
  the MXU into f32xf32 mode at a fraction of bf16 throughput; the fast path
  is native-dtype inputs + preferred_element_type=f32, like XLA's own
  attention fusions. `contract` = (a_axis, b_axis).
  """
  return jax.lax.dot_general(
      a, b, (((contract[0],), (contract[1],)), ((), ())),
      preferred_element_type=jnp.float32)


def _RecomputePandDs(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     sq_ref, sk_ref, q_start, k_start, *, block_q: int,
                     block_k: int, causal: bool, sm_scale: float):
  """Shared backward-block recompute: returns (q, k, do, p, ds).

  q/k/do keep their input dtype (MXU fast path); p and ds are f32
  (consumers cast them back for their matmuls). p = exp(s - lse)
  reproduces the forward probabilities from the saved logsumexp;
  ds = p * (dp - delta) * sm_scale is d(loss)/d(q k^T). Both backward
  kernels must use this same definition or dQ vs dK/dV gradients silently
  diverge.
  """
  q = q_ref[0]                                          # [block_q, h]
  k = k_ref[0]                                          # [block_k, h]
  v = v_ref[0]                                          # [block_k, h]
  do = do_ref[0]                                        # [block_q, h]
  lse = lse_ref[0][:, :1]                               # [block_q, 1]
  delta = delta_ref[0][:, :1]                           # [block_q, 1]
  s = _DotF32(q, k, (1, 1)) * sm_scale                  # [block_q, block_k]
  if causal:
    s = _ApplyCausalMask(s, q_start, k_start, block_q, block_k)
  if sq_ref is not None:
    s = _ApplySegmentMask(s, sq_ref, sk_ref, block_q, block_k)
  p = jnp.exp(s - lse)                                  # f32 [bq, bk]
  dp = _DotF32(do, v, (1, 1))                           # [block_q, block_k]
  ds = p * (dp - delta) * sm_scale
  return q, k, do, p, ds


def _FwdKernel(*refs, block_q: int, block_k: int, nk: int, causal: bool,
               sm_scale: float, has_seg: bool):
  """One (batch*head, q_block, k_block) program step."""
  if has_seg:
    (q_ref, k_ref, v_ref, sq_ref, sk_ref, out_ref, lse_ref, m_scr, l_scr,
     acc_scr) = refs
  else:
    q_ref, k_ref, v_ref, out_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    sq_ref = sk_ref = None
  qi = pl.program_id(1)
  kb = pl.program_id(2)
  q_start = qi * block_q
  k_start = kb * block_k

  @pl.when(kb == 0)
  def _Init():
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

  # A block contributes unless it is entirely in the causal future:
  # smallest q position is q_start, largest k position is k_start+block_k-1.
  def _Accumulate():
    q = q_ref[0]                                        # [block_q, h]
    k = k_ref[0]                                        # [block_k, h]
    v = v_ref[0]                                        # [block_k, h]
    s = _DotF32(q, k, (1, 1)) * sm_scale                # f32 [bq, bk]
    if causal:
      s = _ApplyCausalMask(s, q_start, k_start, block_q, block_k)
    if sq_ref is not None:
      s = _ApplySegmentMask(s, sq_ref, sk_ref, block_q, block_k)
    m_prev = m_scr[:, :1]                               # [block_q, 1]
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # Rows with no unmasked key yet have m_new = NEG_INF; exp(s - m_new)
    # would be exp(0) = 1 for masked entries (causal-only kernels dodge
    # this because the diagonal appears in k-block 0, but segment masks
    # don't). Substitute 0 so masked rows contribute p = exp(NEG_INF) = 0.
    m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)
    # p rounds to the input dtype for the MXU (standard flash practice)
    acc_scr[:] = acc_scr[:] * alpha + _DotF32(p.astype(v.dtype), v, (1, 0))

  if causal:
    pl.when(k_start <= q_start + block_q - 1)(_Accumulate)
  else:
    _Accumulate()

  if causal:
    # last contributing k block covers query position q_start + block_q - 1
    last_kb = jnp.minimum((q_start + block_q - 1) // block_k, nk - 1)
    is_last = kb == last_kb
  else:
    is_last = kb == nk - 1

  @pl.when(is_last)
  def _Emit():
    l = jnp.maximum(l_scr[:, :1], 1e-20)                # [block_q, 1]
    out_ref[0] = (acc_scr[:] / l).astype(out_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l),
                                  lse_ref.shape[1:]).astype(lse_ref.dtype)


def _FlashForward(q, k, v, seg, block_q: int, block_k: int, causal: bool,
                  interpret: bool):
  """q/k/v: [bn, t, h], seg: [bn, t] int32 or None
  -> (out [bn, t, h], lse [bn, t, LANES])."""
  bn, t, h = q.shape
  sm_scale = 1.0 / math.sqrt(h)
  nq, nk = t // block_q, t // block_k
  kernel = functools.partial(
      _FwdKernel, block_q=block_q, block_k=block_k, nk=nk, causal=causal,
      sm_scale=sm_scale, has_seg=seg is not None)
  if causal:
    # clamp the K/V block index so fully-masked grid steps re-request the
    # previous block — Pallas elides the DMA (no wasted HBM bandwidth).
    kv_blk = lambda i, j: jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
  else:
    kv_blk = lambda i, j: j
  inputs = [q, k, v]
  in_specs = [
      pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),
      pl.BlockSpec((1, block_k, h), lambda b, i, j: (b, kv_blk(i, j), 0)),
      pl.BlockSpec((1, block_k, h), lambda b, i, j: (b, kv_blk(i, j), 0)),
  ]
  if seg is not None:
    # seg is [b_true, t] (per-batch, not per-head); index maps divide the
    # flattened batch*head grid index back down so heads share one copy
    n_rep = bn // seg.shape[0]
    seg_q = jnp.broadcast_to(seg[:, :, None],
                             (seg.shape[0], t, LANES)).astype(jnp.int32)
    seg_kv = jnp.broadcast_to(seg[:, None, :],
                              (seg.shape[0], SUBLANES, t)).astype(jnp.int32)
    inputs += [seg_q, seg_kv]
    in_specs += [
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b // n_rep, i, 0)),
        pl.BlockSpec((1, SUBLANES, block_k),
                     lambda b, i, j: (b // n_rep, 0, kv_blk(i, j))),
    ]
  out, lse = pl.pallas_call(
      kernel,
      out_shape=[
          jax.ShapeDtypeStruct((bn, t, h), q.dtype),
          jax.ShapeDtypeStruct((bn, t, LANES), jnp.float32),
      ],
      grid=(bn, nq, nk),
      in_specs=in_specs,
      out_specs=[
          pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),
          pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_q, LANES), jnp.float32),
          pltpu.VMEM((block_q, LANES), jnp.float32),
          pltpu.VMEM((block_q, h), jnp.float32),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "arbitrary")),
      interpret=interpret,
  )(*inputs)
  return out, lse


def _DkDvKernel(*refs, block_q: int, block_k: int, nq: int, causal: bool,
                sm_scale: float, has_seg: bool):
  """One (batch*head, k_block, q_block) step: accumulate dK, dV."""
  if has_seg:
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
     dk_ref, dv_ref, dk_scr, dv_scr) = refs
  else:
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
     dk_scr, dv_scr) = refs
    sq_ref = sk_ref = None
  kb = pl.program_id(1)
  qi = pl.program_id(2)
  q_start = qi * block_q
  k_start = kb * block_k

  @pl.when(qi == 0)
  def _Init():
    dk_scr[:] = jnp.zeros_like(dk_scr)
    dv_scr[:] = jnp.zeros_like(dv_scr)

  def _Accumulate():
    q, _, do, p, ds = _RecomputePandDs(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
        q_start, k_start, block_q=block_q, block_k=block_k, causal=causal,
        sm_scale=sm_scale)
    dv_scr[:] = dv_scr[:] + _DotF32(p.astype(do.dtype), do, (0, 0))
    dk_scr[:] = dk_scr[:] + _DotF32(ds.astype(q.dtype), q, (0, 0))

  if causal:
    pl.when(k_start <= q_start + block_q - 1)(_Accumulate)
  else:
    _Accumulate()

  @pl.when(qi == nq - 1)
  def _Emit():
    dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _DqKernel(*refs, block_q: int, block_k: int, nk: int, causal: bool,
              sm_scale: float, has_seg: bool):
  """One (batch*head, q_block, k_block) step: accumulate dQ."""
  if has_seg:
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
     dq_ref, dq_scr) = refs
  else:
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
    sq_ref = sk_ref = None
  qi = pl.program_id(1)
  kb = pl.program_id(2)
  q_start = qi * block_q
  k_start = kb * block_k

  @pl.when(kb == 0)
  def _Init():
    dq_scr[:] = jnp.zeros_like(dq_scr)

  def _Accumulate():
    _, k, _, _, ds = _RecomputePandDs(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
        q_start, k_start, block_q=block_q, block_k=block_k, causal=causal,
        sm_scale=sm_scale)
    dq_scr[:] = dq_scr[:] + _DotF32(ds.astype(k.dtype), k, (1, 0))

  if causal:
    pl.when(k_start <= q_start + block_q - 1)(_Accumulate)
  else:
    _Accumulate()

  @pl.when(kb == nk - 1)
  def _Emit():
    dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _FlashBackward(q, k, v, seg, out, lse, do, block_q: int, block_k: int,
                   causal: bool, interpret: bool):
  bn, t, h = q.shape
  sm_scale = 1.0 / math.sqrt(h)
  nq, nk = t // block_q, t // block_k
  has_seg = seg is not None
  delta = jnp.broadcast_to(
      jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
              keepdims=True), (bn, t, LANES))           # [bn, t, LANES]
  if causal:
    kv_blk = lambda i, j: jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
    qi_of = lambda j, i: jnp.maximum(i, (j * block_k) // block_q)
  else:
    kv_blk = lambda i, j: j
    qi_of = lambda j, i: i
  q_idx = lambda b, j, i: (b, qi_of(j, i), 0)
  row_idx = lambda b, j, i: (b, qi_of(j, i), 0)

  dkdv_inputs = [q, k, v, do, lse, delta]
  dkdv_specs = [
      pl.BlockSpec((1, block_q, h), q_idx),                      # q
      pl.BlockSpec((1, block_k, h), lambda b, j, i: (b, j, 0)),  # k
      pl.BlockSpec((1, block_k, h), lambda b, j, i: (b, j, 0)),  # v
      pl.BlockSpec((1, block_q, h), q_idx),                      # do
      pl.BlockSpec((1, block_q, LANES), row_idx),                # lse
      pl.BlockSpec((1, block_q, LANES), row_idx),                # delta
  ]
  if has_seg:
    n_rep = bn // seg.shape[0]
    seg_q3 = jnp.broadcast_to(seg[:, :, None],
                              (seg.shape[0], t, LANES)).astype(jnp.int32)
    seg_kv3 = jnp.broadcast_to(seg[:, None, :],
                               (seg.shape[0], SUBLANES, t)).astype(jnp.int32)
    dkdv_inputs += [seg_q3, seg_kv3]
    dkdv_specs += [
        pl.BlockSpec((1, block_q, LANES),
                     lambda b, j, i: (b // n_rep, qi_of(j, i), 0)),
        pl.BlockSpec((1, SUBLANES, block_k),
                     lambda b, j, i: (b // n_rep, 0, j)),
    ]
  dk, dv = pl.pallas_call(
      functools.partial(
          _DkDvKernel, block_q=block_q, block_k=block_k, nq=nq,
          causal=causal, sm_scale=sm_scale, has_seg=has_seg),
      out_shape=[
          jax.ShapeDtypeStruct((bn, t, h), k.dtype),
          jax.ShapeDtypeStruct((bn, t, h), v.dtype),
      ],
      grid=(bn, nk, nq),
      in_specs=dkdv_specs,
      out_specs=[
          pl.BlockSpec((1, block_k, h), lambda b, j, i: (b, j, 0)),
          pl.BlockSpec((1, block_k, h), lambda b, j, i: (b, j, 0)),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_k, h), jnp.float32),
          pltpu.VMEM((block_k, h), jnp.float32),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "arbitrary")),
      interpret=interpret,
  )(*dkdv_inputs)

  dq_inputs = [q, k, v, do, lse, delta]
  dq_specs = [
      pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),  # q
      pl.BlockSpec((1, block_k, h), lambda b, i, j: (b, kv_blk(i, j), 0)),
      pl.BlockSpec((1, block_k, h), lambda b, i, j: (b, kv_blk(i, j), 0)),
      pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),  # do
      pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),  # lse
      pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),  # delta
  ]
  if has_seg:
    dq_inputs += [seg_q3, seg_kv3]
    dq_specs += [
        pl.BlockSpec((1, block_q, LANES),
                     lambda b, i, j: (b // n_rep, i, 0)),
        pl.BlockSpec((1, SUBLANES, block_k),
                     lambda b, i, j: (b // n_rep, 0, kv_blk(i, j))),
    ]
  dq = pl.pallas_call(
      functools.partial(
          _DqKernel, block_q=block_q, block_k=block_k, nk=nk, causal=causal,
          sm_scale=sm_scale, has_seg=has_seg),
      out_shape=jax.ShapeDtypeStruct((bn, t, h), q.dtype),
      grid=(bn, nq, nk),
      in_specs=dq_specs,
      out_specs=pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),
      scratch_shapes=[pltpu.VMEM((block_q, h), jnp.float32)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "arbitrary")),
      interpret=interpret,
  )(*dq_inputs)
  return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _FlashCore(q, k, v, seg, block_q, block_k, causal, interpret):
  out, _ = _FlashForward(q, k, v, seg, block_q, block_k, causal, interpret)
  return out


def _FlashCoreFwd(q, k, v, seg, block_q, block_k, causal, interpret):
  out, lse = _FlashForward(q, k, v, seg, block_q, block_k, causal, interpret)
  return out, (q, k, v, seg, out, lse)


def _FlashCoreBwd(block_q, block_k, causal, interpret, res, g):
  q, k, v, seg, out, lse = res
  dq, dk, dv = _FlashBackward(q, k, v, seg, out, lse, g, block_q, block_k,
                              causal, interpret)
  return dq, dk, dv, None


_FlashCore.defvjp(_FlashCoreFwd, _FlashCoreBwd)


def _XlaAttention(q, k, v, seg, causal: bool):
  """Plain-XLA twin of the kernel's semantics for small off-TPU shapes.

  q/k/v: [b, t, n, h]; seg: [b, t] int32 or None (pairs with different ids
  masked; pad rows carry id 0 and attend each other, matching the kernel).
  Scaling by 1/sqrt(h) applied internally, f32 softmax, output in q.dtype.
  Natively differentiable — no custom VJP needed.
  """
  b, t, n, h = q.shape
  s = jnp.einsum("bqnh,bknh->bnqk", q, k,
                 preferred_element_type=jnp.float32) / math.sqrt(h)
  keep = jnp.ones((b, 1, t, t), jnp.bool_)
  if causal:
    keep &= jnp.tril(jnp.ones((t, t), jnp.bool_))[None, None]
  if seg is not None:
    keep &= (seg[:, None, :, None] == seg[:, None, None, :])
  s = jnp.where(keep, s, NEG_INF)
  p = jax.nn.softmax(s, axis=-1)
  out = jnp.einsum("bnqk,bknh->bqnh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
  return out.astype(q.dtype)


def SelectedLowering(t: int, n: int, h: int,
                     interpret: bool | None = None) -> str:
  """Which lowering FlashAttention will run for a [*, t, n, h] input:
  'pallas' (real TPU), 'pallas-interpret' (explicit interpret=True, or a
  large off-TPU shape), or 'xla' (auto-interpret small shape)."""
  if interpret is None:
    if jax.default_backend() == "tpu":
      return "pallas"
    if t * n * h < _XLA_FALLBACK_MAX_ELEMS:
      return "xla"
    return "pallas-interpret"
  return "pallas-interpret" if interpret else "pallas"


def SupportedOnTpu(t: int, with_segments: bool = False) -> bool:
  """Whether a [*, t, *, *] input can lower on real TPU hardware.

  Without segments any t whose fitted blocks divide it works (t % 16 is
  plenty); the segment path additionally needs the fitted block_k to stay
  128-lane aligned, i.e. t a multiple of 128 (see _FlashForward specs).
  """
  if t % 16 != 0:
    return False
  return not with_segments or t % LANES == 0


def FlashAttention(q, k, v, *, causal: bool = True, segment_ids=None,
                   block_q: int = 1024, block_k: int = 1024,
                   interpret: bool | None = None):
  """Fused attention. q/k/v: [b, t, n, h] -> [b, t, n, h].

  segment_ids: optional [b, t] int — packed-input segment mask (pairs with
  different ids never attend; padding should carry id 0, whose positions
  produce finite loss-masked garbage rather than NaN). This is what lets
  the packed GShard LM recipe run on the fused kernel.

  Scaling by 1/sqrt(h) happens INSIDE (don't pre-scale q). Block sizes are
  shrunk automatically to the largest power of two dividing T; h should be a
  multiple of 128 for the MXU on real TPU. interpret=None auto-selects
  (True off-TPU).

  Default blocks are 1024x1024 (measured on v5e at [4,2048,8,128] fwd+bwd
  causal bf16: 1.87 ms vs 7.92 ms with 128x128 blocks and 8.37 ms for naive
  XLA attention — small blocks leave the MXU idle behind per-block VPU
  softmax work). VMEM at these defaults is dominated by the
  [block_q, block_k] f32 intermediates (s/p — and dp/ds in the backward —
  at 4 MB each, ~16 MB live in the bwd recompute), not the ~256 KB q/k/v
  tiles; shrink block_k first on parts with smaller VMEM than v5e's.
  """
  b, t, n, h = q.shape
  lowering = SelectedLowering(t, n, h, interpret)
  if lowering == "xla":
    # auto-selected interpret mode on a small shape: interpret-mode grid
    # overhead dwarfs the compute, plain XLA is strictly faster. Explicit
    # interpret=True (kernel tests) never takes this branch.
    seg = None
    if segment_ids is not None:
      seg = segment_ids.astype(jnp.int32)
    return _XlaAttention(q, k, v, seg, causal)
  if interpret is None:
    interpret = jax.default_backend() != "tpu"

  def _FitBlock(requested):
    # largest power-of-two block <= requested that divides t
    c = min(requested, t)
    while c > 1 and t % c != 0:
      c //= 2
    return max(c, 1)

  block_q = _FitBlock(block_q)
  block_k = _FitBlock(block_k)
  assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)
  if not interpret and segment_ids is not None and (
      block_k % LANES != 0 or block_q % SUBLANES != 0):
    # the segment-id kv spec puts block_k on the 128-lane minor axis; a
    # shrunken block (t not a multiple of 128) cannot lower on TPU —
    # callers gate on SupportedOnTpu, this is the backstop
    raise ValueError(
        f"segment_ids flash path needs block_q % {SUBLANES} == 0 and "
        f"block_k % {LANES} == 0 on TPU; t={t} gave ({block_q}, {block_k}). "
        "Pad t to a multiple of 128 or use the unfused path.")

  def _Flat(x):
    return x.transpose(0, 2, 1, 3).reshape(b * n, t, h)

  seg = None
  if segment_ids is not None:
    # [b, t]; heads share one copy (the kernels' index maps divide the
    # flattened batch*head index back down, matching _Flat's b-major order)
    seg = segment_ids.astype(jnp.int32)
  out = _FlashCore(_Flat(q), _Flat(k), _Flat(v), seg, block_q, block_k,
                   causal, interpret)
  return out.reshape(b, n, t, h).transpose(0, 2, 1, 3)
