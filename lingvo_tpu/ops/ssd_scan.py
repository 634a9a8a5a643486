"""Chunked gated linear-recurrence scan (state-space duality form).

The training/prefill hot op behind `core/ssm.py`. Semantics per head — a
matrix-valued linear recurrence over time with scalar input-dependent decay:

    S_t = a_t * S_{t-1} + v_t outer b_t        # S: [H, S] state matrix
    y_t = S_t @ c_t                            # readout AFTER update, so the
                                               # diagonal (t attends t) term
                                               # is included

with `a_t = exp(decay_log_t)`, `decay_log_t <= 0`. This is the "state space
duality" (SSD) form: unrolled, y_t = sum_{t'<=t} exp(cum_t - cum_t')
(c_t . b_t') v_t' — i.e. causal linear attention with a multiplicative decay
mask — which is what the chunked lowerings exploit.

Four lowerings of the SAME recurrence:

- `sequential` — `lax.scan` over single tokens through `SequentialStep`.
  `core/ssm.py`'s ExtendStep calls `SequentialStep` directly, so this
  lowering IS the decode path and the two agree bitwise by construction.
- `associative` — `jax.lax.associative_scan` over (a, v outer b) pairs with
  the affine combine (a_l*a_r, a_r*u_l + u_r). Materializes the full
  [T, H, S] state trajectory: the O(T*H*S)-memory textbook reference the
  chunked paths are tested against, not a production path.
- `chunked` — the XLA production path: reshape T into [num_chunks, Q],
  run the quadratic intra-chunk form + O(1)-state inter-chunk carry of
  `_ChunkBody` under `lax.scan`. Linear memory in T, matmul-shaped work.
- `pallas` — a Pallas TPU kernel with grid (B*N, num_chunks); the chunk
  axis is sequential ("arbitrary") with the running state carried in f32
  VMEM scratch across grid steps, exactly like `flash_decode`'s per-page
  scratch carry. Every chunk routes through the SAME `_ChunkBody` as the
  XLA chunked path, so interpret-mode equality holds bitwise — the
  `flash_decode`/`block_decode` twin-lowering pattern.

Numerical contract: all scan math is f32 regardless of input dtype (the
recurrence compounds products over thousands of steps; bf16 state drifts).
Outputs are f32; the caller casts.

Masking contract (the caller — `core/ssm.py` — prepares inputs):
- padded step: decay_log = 0 AND v = 0  ->  S_t = S_{t-1} exactly.
- segment reset: decay_log = RESET_LOG (-60). exp(-60) ~ 9e-27, so any
  leaked history underflows an f32 add against O(1) activations — an
  exact reset in practice — while cumsums inside a chunk stay O(100), so
  within-segment decay differences are NOT absorbed the way a -1e30
  sentinel would absorb them (catastrophic-cancellation trap).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu.ops.flash_attention import (  # single source of truth
    LANES, SUBLANES, _DotF32)

# Segment-boundary decay: see the masking contract in the module docstring.
RESET_LOG = -60.0
# Mask value for "never attend" inside a chunk (exp(_MASK_LOG) == 0.0 in f32).
_MASK_LOG = -1.0e30


def SequentialStep(s, decay_log, b_t, c_t, v_t):
  """One recurrence step. The decode path (`ssm.ExtendStep`) calls this.

  s: [..., H, S] f32 state, decay_log: [...] f32, b_t/c_t: [..., S],
  v_t: [..., H]. Returns (s_new [..., H, S], y [..., H]), both f32.
  """
  s = s.astype(jnp.float32)
  a = jnp.exp(decay_log.astype(jnp.float32))[..., None, None]
  u = (v_t.astype(jnp.float32)[..., :, None]
       * b_t.astype(jnp.float32)[..., None, :])
  s_new = a * s + u
  y = jnp.einsum("...s,...hs->...h", c_t.astype(jnp.float32), s_new)
  return s_new, y


def _SequentialScan(decay_log, b_in, c_in, v, s0):
  """lax.scan over single tokens. Flat inputs: decay_log [R, T],
  b_in/c_in [R, T, S], v [R, T, H], s0 [R, H, S]. R = B*N."""

  def _Step(s, xs):
    dl, bt, ct, vt = xs
    s_new, y = SequentialStep(s, dl, bt, ct, vt)
    return s_new, y

  xs = (decay_log.swapaxes(0, 1), b_in.swapaxes(0, 1),
        c_in.swapaxes(0, 1), v.swapaxes(0, 1))
  s_fin, ys = jax.lax.scan(_Step, s0, xs)
  return ys.swapaxes(0, 1), s_fin


def _AssociativeScan(decay_log, b_in, c_in, v, s0):
  """jax.lax.associative_scan reference. Same flat shapes as above.

  Materializes the [R, T, H, S] state trajectory — reference only.
  """
  a = jnp.exp(decay_log)[..., None, None]              # [R, T, 1, 1]
  u = v[..., :, None] * b_in[..., None, :]             # [R, T, H, S]

  def _Combine(left, right):
    a_l, u_l = left
    a_r, u_r = right
    return a_l * a_r, a_r * u_l + u_r

  a_cum, s_all = jax.lax.associative_scan(_Combine, (a, u), axis=1)
  # Thread the initial state through the cumulative decay.
  s_all = s_all + a_cum * s0[:, None]
  y = jnp.einsum("rts,rths->rth", c_in, s_all)
  return y, s_all[:, -1]


def _ChunkBody(s_in, dl2, b_c, c_c, v_c):
  """One chunk of the recurrence for one (batch, head) pair.

  s_in: [H, S] f32 incoming state, dl2: [Q, 1] f32 log-decay, b_c/c_c:
  [Q, S] f32, v_c: [Q, H] f32. Returns (y [Q, H], s_out [H, S]).

  Both the XLA chunked lowering (vmapped over B*N) and the Pallas kernel
  (per grid step) call exactly this, so the float-op sequence — and the
  bits, in interpret mode — match. Everything stays rank-2: TPU Mosaic
  has no appetite for 1-D vectors, and [Q, 1] broadcasts are free.

  Mosaic lowers neither `cumsum` nor a [Q, 1] <-> [1, Q] transpose, so the
  running sum and both row/column views of it are masked reductions over
  [Q, Q] tiles: a diagonal mask moves a column onto a row exactly (one
  nonzero per sum), a lower-triangular mask accumulates. Matmul operands
  are contracted in place (`_DotF32`) instead of transposed first.
  """
  q = dl2.shape[0]
  row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
  col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
  diag, lower = row == col, row >= col
  dl_row = jnp.sum(jnp.where(diag, dl2, 0.0), axis=0, keepdims=True)  # [1, Q]
  cum = jnp.sum(jnp.where(lower, dl_row, 0.0), axis=1, keepdims=True)  # [Q, 1]
  cum_row = jnp.sum(jnp.where(diag, cum, 0.0), axis=0, keepdims=True)  # [1, Q]
  # Inter-chunk: position t sees s_in through decay exp(cum_t).
  y_inter = _DotF32(c_c * jnp.exp(cum), s_in, (1, 1))      # [Q, H]
  # Intra-chunk quadratic form: exp(cum_t - cum_t') (c_t . b_t'), t' <= t.
  scores = _DotF32(c_c, b_c, (1, 1))                       # [Q, P]
  decay = jnp.exp(jnp.where(lower, cum - cum_row, _MASK_LOG))
  y_intra = _DotF32(scores * decay, v_c, (1, 0))           # [Q, H]
  # State out: decay the incoming state across the whole chunk, add each
  # token's outer-product contribution decayed from its position to the end.
  tot = cum[-1:]                                         # [1, 1]
  w_tail = jnp.exp(tot - cum)                            # [Q, 1]
  s_out = jnp.exp(tot) * s_in + _DotF32(v_c * w_tail, b_c, (0, 0))
  return y_inter + y_intra, s_out


def _PadChunks(decay_log, b_in, c_in, v, chunk_size):
  """Right-pad T to a chunk multiple with identity steps (dl=0, u=0)."""
  t = decay_log.shape[1]
  t_pad = -(-t // chunk_size) * chunk_size
  if t_pad == t:
    return decay_log, b_in, c_in, v, t_pad
  pad = t_pad - t
  decay_log = jnp.pad(decay_log, ((0, 0), (0, pad)))
  b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
  c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))
  v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
  return decay_log, b_in, c_in, v, t_pad


def _ChunkedXla(decay_log, b_in, c_in, v, s0, chunk_size):
  """XLA chunked lowering: lax.scan over chunks of vmapped _ChunkBody."""
  r, t = decay_log.shape
  s_dim, h = b_in.shape[-1], v.shape[-1]
  decay_log, b_in, c_in, v, t_pad = _PadChunks(
      decay_log, b_in, c_in, v, chunk_size)
  nc = t_pad // chunk_size
  # [R, T, ...] -> [NC, R, Q, ...] so the chunk axis leads for lax.scan.
  dl = decay_log.reshape(r, nc, chunk_size, 1).swapaxes(0, 1)
  bb = b_in.reshape(r, nc, chunk_size, s_dim).swapaxes(0, 1)
  cc = c_in.reshape(r, nc, chunk_size, s_dim).swapaxes(0, 1)
  vv = v.reshape(r, nc, chunk_size, h).swapaxes(0, 1)

  def _Scan(s, xs):
    y, s_new = jax.vmap(_ChunkBody)(s, *xs)
    return s_new, y

  s_fin, ys = jax.lax.scan(_Scan, s0, (dl, bb, cc, vv))
  y = ys.swapaxes(0, 1).reshape(r, t_pad, h)[:, :t]
  return y, s_fin


def _ScanKernel(dl_ref, b_ref, c_ref, v_ref, s0_ref, y_ref, sfin_ref,
                s_scr, *, num_chunks):
  """Pallas kernel: grid (R, NC); chunk axis sequential, state in scratch."""
  j = pl.program_id(1)

  @pl.when(j == 0)
  def _Init():
    s_scr[:] = s0_ref[0]

  y, s_new = _ChunkBody(s_scr[:], dl_ref[0, 0], b_ref[0, 0], c_ref[0, 0],
                        v_ref[0, 0])
  y_ref[0, 0] = y
  s_scr[:] = s_new

  @pl.when(j == num_chunks - 1)
  def _Emit():
    sfin_ref[0] = s_scr[:]


def _ChunkedPallas(decay_log, b_in, c_in, v, s0, chunk_size,
                   interpret=False):
  """Pallas twin of _ChunkedXla. Same flat [R, T, ...] contract."""
  r, t = decay_log.shape
  s_dim, h = b_in.shape[-1], v.shape[-1]
  decay_log, b_in, c_in, v, t_pad = _PadChunks(
      decay_log, b_in, c_in, v, chunk_size)
  nc = t_pad // chunk_size
  dl = decay_log.reshape(r, nc, chunk_size, 1)
  bb = b_in.reshape(r, nc, chunk_size, s_dim)
  cc = c_in.reshape(r, nc, chunk_size, s_dim)
  vv = v.reshape(r, nc, chunk_size, h)

  kernel = functools.partial(_ScanKernel, num_chunks=nc)
  y, s_fin = pl.pallas_call(
      kernel,
      grid=(r, nc),
      in_specs=[
          pl.BlockSpec((1, 1, chunk_size, 1), lambda ri, j: (ri, j, 0, 0)),
          pl.BlockSpec((1, 1, chunk_size, s_dim),
                       lambda ri, j: (ri, j, 0, 0)),
          pl.BlockSpec((1, 1, chunk_size, s_dim),
                       lambda ri, j: (ri, j, 0, 0)),
          pl.BlockSpec((1, 1, chunk_size, h), lambda ri, j: (ri, j, 0, 0)),
          pl.BlockSpec((1, h, s_dim), lambda ri, j: (ri, 0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((1, 1, chunk_size, h), lambda ri, j: (ri, j, 0, 0)),
          pl.BlockSpec((1, h, s_dim), lambda ri, j: (ri, 0, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((r, nc, chunk_size, h), jnp.float32),
          jax.ShapeDtypeStruct((r, h, s_dim), jnp.float32),
      ],
      scratch_shapes=[pltpu.VMEM((h, s_dim), jnp.float32)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary")),
      interpret=interpret,
  )(dl, bb, cc, vv, s0)
  return y.reshape(r, t_pad, h)[:, :t], s_fin


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _PallasScan(decay_log, b_in, c_in, v, s0, chunk_size, interpret):
  return _ChunkedPallas(decay_log, b_in, c_in, v, s0, chunk_size,
                        interpret=interpret)


def _PallasScanFwd(decay_log, b_in, c_in, v, s0, chunk_size, interpret):
  out = _PallasScan(decay_log, b_in, c_in, v, s0, chunk_size, interpret)
  return out, (decay_log, b_in, c_in, v, s0)


def _PallasScanBwd(chunk_size, interpret, residuals, cots):
  # The XLA chunked path computes the same floats (shared _ChunkBody), so
  # its VJP is the principled backward for the Pallas forward — the same
  # trick fused_xent uses (recompute-based custom_vjp).
  del interpret
  decay_log, b_in, c_in, v, s0 = residuals
  _, vjp = jax.vjp(
      lambda *args: _ChunkedXla(*args, chunk_size), decay_log, b_in, c_in,
      v, s0)
  return vjp(cots)


_PallasScan.defvjp(_PallasScanFwd, _PallasScanBwd)


def SupportedOnTpu(chunk_size: int, state_dim: int, head_dim: int) -> bool:
  """Whether the Pallas lowering can run on real TPU hardware.

  Conservative, mirroring flash_decode.SupportedOnTpu: the state/head dims
  ride the 128-lane minor axis and the chunk axis rides sublanes.
  """
  return (chunk_size % SUBLANES == 0 and state_dim % LANES == 0
          and head_dim % LANES == 0)


def SsdScan(decay_log, b_in, c_in, v, s0=None, *, chunk_size: int = 64,
            lowering: str = "auto", interpret: bool | None = None):
  """Gated linear-recurrence scan over a batch of sequences.

  decay_log: [B, T, N] f32 log-decay per (step, head), <= 0. Caller encodes
    padding (0 with zeroed v) and segment resets (RESET_LOG) here.
  b_in: [B, T, N, S] input projection ("write keys").
  c_in: [B, T, N, S] output projection ("read keys").
  v:    [B, T, N, H] values.
  s0:   optional [B, N, H, S] f32 initial state (zeros when None).
  lowering: 'auto' (pallas on real TPU when SupportedOnTpu, else chunked),
    'chunked', 'pallas', 'associative', or 'sequential'.
  Returns (y [B, T, N, H] f32, s_final [B, N, H, S] f32).
  """
  assert lowering in ("auto", "chunked", "pallas", "associative",
                      "sequential"), lowering
  b, t, n = decay_log.shape
  s_dim, h = b_in.shape[-1], v.shape[-1]
  on_tpu = jax.default_backend() == "tpu"
  if lowering == "auto":
    lowering = ("pallas" if on_tpu and SupportedOnTpu(chunk_size, s_dim, h)
                else "chunked")
  # Flatten (B, N) into one row axis: every lowering is per-(batch, head).
  f32 = jnp.float32
  dl = decay_log.astype(f32).transpose(0, 2, 1).reshape(b * n, t)
  bb = b_in.astype(f32).transpose(0, 2, 1, 3).reshape(b * n, t, s_dim)
  cc = c_in.astype(f32).transpose(0, 2, 1, 3).reshape(b * n, t, s_dim)
  vv = v.astype(f32).transpose(0, 2, 1, 3).reshape(b * n, t, h)
  if s0 is None:
    s0f = jnp.zeros((b * n, h, s_dim), f32)
  else:
    s0f = s0.astype(f32).reshape(b * n, h, s_dim)

  if lowering == "sequential":
    y, s_fin = _SequentialScan(dl, bb, cc, vv, s0f)
  elif lowering == "associative":
    y, s_fin = _AssociativeScan(dl, bb, cc, vv, s0f)
  elif lowering == "chunked":
    y, s_fin = _ChunkedXla(dl, bb, cc, vv, s0f, chunk_size)
  else:
    if interpret is None:
      interpret = not on_tpu
    y, s_fin = _PallasScan(dl, bb, cc, vv, s0f, chunk_size, interpret)

  y = y.reshape(b, n, t, h).transpose(0, 2, 1, 3)
  s_fin = s_fin.reshape(b, n, h, s_dim)
  return y, s_fin
