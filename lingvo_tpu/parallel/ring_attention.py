"""Ring attention: exact causal attention with the sequence dim sharded.

BEYOND-reference capability (SURVEY.md §5: the reference has no sequence/
context parallelism — only blocked approximations like
`batch_major_attention.py:2656,4008`). Here the sequence axis is a
first-class mesh dim ('seq'): each device holds a T/n slice of Q/K/V; K/V
blocks rotate around the ring with `ppermute` over ICI while each device
accumulates its queries' attention online, overlapping compute with
neighbor transfers.

The per-block compute is the Pallas flash kernel (`ops/flash_attention`),
not a naive einsum: each rotation runs `_FlashForward` on (local Q, visiting
KV block) and the normalized block outputs are merged with their logsumexp
(online softmax across blocks). The backward is a second ring pass built on
`_FlashBackward`: dK/dV accumulators rotate WITH their K/V blocks (arriving
home after a full cycle) while dQ accumulates locally — the whole ring is a
single `jax.custom_vjp`, so remat/grad transforms see one opaque exact-
attention op. Numerics match full attention (f32 accumulators/merges).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from lingvo_tpu.ops import flash_attention
from lingvo_tpu.parallel import mesh as mesh_lib

LANES = flash_attention.LANES


def _FitBlock(requested: int, t: int) -> int:
  c = min(requested, t)
  while c > 1 and t % c != 0:
    c //= 2
  return max(c, 1)


def _BlockFlashFwd(q, k, v, mode, block_q, block_k, interpret):
  """One ring step's attention: q [bn,tq,h] vs one KV block [bn,tk,h].

  mode: 0 = block entirely in the causal future (skip), 1 = diagonal block
  (causal mask), 2 = entirely in the past (full attention).
  Returns (out [bn,tq,h] normalized-within-block, lse [bn,tq] f32;
  lse = -inf where the block contributes nothing).
  """

  def _Skip(q, k, v):
    del k, v
    return (jnp.zeros(q.shape, q.dtype),
            jnp.full(q.shape[:2], -jnp.inf, jnp.float32))

  def _Run(causal):
    def _F(q, k, v):
      out, lse = flash_attention._FlashForward(
          q, k, v, None, block_q, block_k, causal, interpret)
      return out, lse[:, :, 0]
    return _F

  return jax.lax.switch(mode, [_Skip, _Run(True), _Run(False)], q, k, v)


def _MergeLse(o_acc, lse_acc, o_blk, lse_blk):
  """Online-softmax merge of normalized partials via their logsumexps."""
  lse = jnp.logaddexp(lse_acc, lse_blk)                  # [bn, t]
  ninf = jnp.isneginf(lse)
  a_acc = jnp.where(jnp.isneginf(lse_acc) | ninf, 0.0,
                    jnp.exp(lse_acc - lse))
  a_blk = jnp.where(jnp.isneginf(lse_blk) | ninf, 0.0,
                    jnp.exp(lse_blk - lse))
  o = a_acc[..., None] * o_acc + a_blk[..., None] * o_blk.astype(jnp.float32)
  return o, lse


def _BlockFlashBwd(q, k, v, do, out, lse3, mode, block_q, block_k,
                   interpret):
  """Per-(q-shard, kv-block) gradients with GLOBAL lse/out (so p and delta
  are the true global attention quantities). Returns (dq, dk, dv) f32."""

  def _Skip(q, k, v, do, out, lse3):
    del do, out, lse3
    return (jnp.zeros(q.shape, jnp.float32),
            jnp.zeros(k.shape, jnp.float32),
            jnp.zeros(v.shape, jnp.float32))

  def _Run(causal):
    def _F(q, k, v, do, out, lse3):
      dq, dk, dv = flash_attention._FlashBackward(
          q, k, v, None, out, lse3, do, block_q, block_k, causal, interpret)
      return (dq.astype(jnp.float32), dk.astype(jnp.float32),
              dv.astype(jnp.float32))
    return _F

  return jax.lax.switch(mode, [_Skip, _Run(True), _Run(False)],
                        q, k, v, do, out, lse3)


def _Mode(blk_idx, my_idx, causal: bool):
  if not causal:
    return jnp.int32(2)
  return jnp.where(blk_idx == my_idx, 1,
                   jnp.where(blk_idx < my_idx, 2, 0)).astype(jnp.int32)


def _RingFwdLocal(q, k, v, *, axis, num, causal, block_q, block_k,
                  interpret):
  """Per-device forward: q/k/v [bn, t_loc, h] -> (out, lse [bn, t_loc])."""
  my_idx = jax.lax.axis_index(axis)
  perm = [(i, (i + 1) % num) for i in range(num)]
  bn, t_loc, h = q.shape
  o0 = jnp.zeros((bn, t_loc, h), jnp.float32)
  lse0 = jnp.full((bn, t_loc), -jnp.inf, jnp.float32)

  def _Step(_, carry):
    o, lse, kb, vb, bidx = carry
    bo, blse = _BlockFlashFwd(q, kb, vb, _Mode(bidx, my_idx, causal),
                              block_q, block_k, interpret)
    o, lse = _MergeLse(o, lse, bo, blse)
    kb = jax.lax.ppermute(kb, axis, perm)
    vb = jax.lax.ppermute(vb, axis, perm)
    bidx = jax.lax.ppermute(bidx, axis, perm)
    return o, lse, kb, vb, bidx

  o, lse, _, _, _ = jax.lax.fori_loop(
      0, num, _Step, (o0, lse0, k, v, my_idx))
  return o.astype(q.dtype), lse


def _RingBwdLocal(q, k, v, do, out, lse, *, axis, num, causal, block_q,
                  block_k, interpret):
  """Per-device backward ring: dK/dV accumulators travel with their blocks
  and are home again after `num` rotations; dQ accumulates in place."""
  my_idx = jax.lax.axis_index(axis)
  perm = [(i, (i + 1) % num) for i in range(num)]
  bn, t_loc, h = q.shape
  lse3 = jnp.broadcast_to(lse[..., None], (bn, t_loc, LANES))
  dq0 = jnp.zeros((bn, t_loc, h), jnp.float32)

  def _Step(_, carry):
    dq, kb, vb, dkb, dvb, bidx = carry
    dq_c, dk_c, dv_c = _BlockFlashBwd(
        q, kb, vb, do, out, lse3, _Mode(bidx, my_idx, causal),
        block_q, block_k, interpret)
    dq = dq + dq_c
    dkb = dkb + dk_c
    dvb = dvb + dv_c
    kb = jax.lax.ppermute(kb, axis, perm)
    vb = jax.lax.ppermute(vb, axis, perm)
    dkb = jax.lax.ppermute(dkb, axis, perm)
    dvb = jax.lax.ppermute(dvb, axis, perm)
    bidx = jax.lax.ppermute(bidx, axis, perm)
    return dq, kb, vb, dkb, dvb, bidx

  dq, _, _, dk, dv, _ = jax.lax.fori_loop(
      0, num, _Step,
      (dq0, k, v, jnp.zeros_like(dq0), jnp.zeros_like(dq0), my_idx))
  return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _Flat(x):
  b, t, n, h = x.shape
  return x.transpose(0, 2, 1, 3).reshape(b * n, t, h)


def _Unflat(x, b, n):
  bn, t, h = x.shape
  return x.reshape(b, n, t, h).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _RingCore(q, k, v, mesh, seq_axis, causal, block_q, block_k):
  out, _ = _RingCoreFwd(q, k, v, mesh, seq_axis, causal, block_q, block_k)
  return out


def _RingCoreFwd(q, k, v, mesh, seq_axis, causal, block_q, block_k):
  num = mesh.shape[seq_axis]
  interpret = jax.default_backend() != "tpu"
  b, t, n, h = q.shape

  def _Local(q, k, v):
    out, lse = _RingFwdLocal(
        _Flat(q), _Flat(k), _Flat(v), axis=seq_axis, num=num, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return _Unflat(out, b, n), lse.reshape(b, n, -1)

  spec = PartitionSpec(None, seq_axis, None, None)
  lse_spec = PartitionSpec(None, None, seq_axis)
  out, lse = mesh_lib.ShardMap(
      _Local, mesh=mesh, in_specs=(spec, spec, spec),
      out_specs=(spec, lse_spec), check_vma=False)(q, k, v)
  return out, (q, k, v, out, lse)


def _RingCoreBwd(mesh, seq_axis, causal, block_q, block_k, res, g):
  q, k, v, out, lse = res
  num = mesh.shape[seq_axis]
  interpret = jax.default_backend() != "tpu"
  b, t, n, h = q.shape

  def _Local(q, k, v, do, out, lse):
    dq, dk, dv = _RingBwdLocal(
        _Flat(q), _Flat(k), _Flat(v), _Flat(do), _Flat(out),
        lse.reshape(b * n, -1), axis=seq_axis, num=num, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return _Unflat(dq, b, n), _Unflat(dk, b, n), _Unflat(dv, b, n)

  spec = PartitionSpec(None, seq_axis, None, None)
  lse_spec = PartitionSpec(None, None, seq_axis)
  return mesh_lib.ShardMap(
      _Local, mesh=mesh, in_specs=(spec, spec, spec, spec, spec, lse_spec),
      out_specs=(spec, spec, spec), check_vma=False)(q, k, v, g, out, lse)


_RingCore.defvjp(_RingCoreFwd, _RingCoreBwd)


def RingAttention(q, k, v, *, mesh: Mesh, seq_axis: str = mesh_lib.SEQ_AXIS,
                  causal: bool = True, block_q: int = 1024,
                  block_k: int = 1024):
  """q/k/v: [b, T, n, h] GLOBALLY, sharded [b, T/num, n, h] over seq_axis.

  Returns [b, T, n, h] attention output with the same sharding, exactly
  equal to full (flash) attention, differentiable end to end. Scaling by
  1/sqrt(h) happens inside the kernel (don't pre-scale q). Call inside jit
  with q/k/v sharded (or let jit reshard by annotation).
  """
  num = mesh.shape[seq_axis]
  t_loc = q.shape[1] // num
  block_q = _FitBlock(block_q, t_loc)
  block_k = _FitBlock(block_k, t_loc)
  return _RingCore(q, k, v, mesh, seq_axis, causal, block_q, block_k)


def RingAttentionSingleDevice(q, k, v, *, num_shards: int,
                              causal: bool = True, block_q: int = 1024,
                              block_k: int = 1024):
  """The ring decomposition executed serially on ONE device.

  Runs exactly the per-device program each of `num_shards` sp devices would
  run (num_shards q-shards x num_shards KV visits, flash per block, lse
  merges) without the ppermutes: the exactness oracle for tests. With
  ideal ICI overlap, per-device ring step time ~= this / num_shards.
  """
  b, t, n, h = q.shape
  t_loc = t // num_shards
  block_q = _FitBlock(block_q, t_loc)
  block_k = _FitBlock(block_k, t_loc)
  interpret = jax.default_backend() != "tpu"
  qf, kf, vf = _Flat(q), _Flat(k), _Flat(v)
  outs = []
  for me in range(num_shards):
    q_sh = jax.lax.dynamic_slice_in_dim(qf, me * t_loc, t_loc, axis=1)
    o = jnp.zeros(q_sh.shape, jnp.float32)
    lse = jnp.full(q_sh.shape[:2], -jnp.inf, jnp.float32)
    for blk in range(num_shards):
      mode = (1 if blk == me else (2 if blk < me else 0)) if causal else 2
      if mode == 0:
        continue
      k_blk = jax.lax.dynamic_slice_in_dim(kf, blk * t_loc, t_loc, axis=1)
      v_blk = jax.lax.dynamic_slice_in_dim(vf, blk * t_loc, t_loc, axis=1)
      bo, blse = _BlockFlashFwd(q_sh, k_blk, v_blk, jnp.int32(mode),
                                block_q, block_k, interpret)
      o, lse = _MergeLse(o, lse, bo, blse)
    outs.append(o.astype(q.dtype))
  return _Unflat(jnp.concatenate(outs, axis=1), b, n)
