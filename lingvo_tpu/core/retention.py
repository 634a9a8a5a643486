"""Power retention as a sequence mixer of `transformer.BlockSequence`.

`PowerRetention` (Manifest AI, arXiv:2507.04239, degree 2) is attention whose
weight is the square of the score under a learned per-token decay, which has
an exact recurrent form with a state of fixed size (ops/power_retention.py
has the three forms and the kernels). x [.., D]; N query heads over Nkv KV
heads of H, query head a reading KV head a // (N / Nkv):

    q_t[a] = RoPE_t(RMSNorm_H(W_q x_t)[a])      k_t[c] = RoPE_t(RMSNorm_H(W_k x_t)[c])
    v_t[c] = (W_v x_t)[c]                       log g_t[c] = log sigmoid((W_g x_t)[c])
    w_ts   = (q_t[a] . k_s[c] / sqrt(H))^2 * exp(sum_{r=s+1..t} log g_r[c])   s <= t
    y_t[a] = sum_s w_ts v_s[c] / (sum_s w_ts + eps)
    out_t  = W_o concat_a y_t[a]

No bias anywhere. The norms over a head's H dimensions carry a learned scale
(stored as an offset from one) and the layer norms' epsilon; the rotation is
the half-split one of core/layers.RotaryPositionalEmbeddingLayer.

The mixer holds BOTH kinds of serving state: a slot's `S` and `z` (f32,
`InitPagedStates`: leaves of the engine's states, reset where a row starts a
request) and, in pages of the stack's one pool through its own block table,
the K, V and cumulated log-gates of the row's open chunk, the tokens since
the last page boundary. A step folds every page it completes into the state;
the host lets the page go behind the row's cursor (`window = 1`:
serving/kv_cache.KindPages holds a window layer's pages from the page of the
cursor on, which is the open chunk's).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from lingvo_tpu import observe
from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core import base_layer
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import ragged
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.core.py_utils import WeightInit, WeightParams
from lingvo_tpu.ops import power_retention as op


class RetentionPlan(NamedTuple):
  """What a stack of retention layers builds once a step."""
  lists: op.StepPlan    # the kernels' lists (ops/power_retention.py)
  narrow: object        # ragged.LiveWidth (or None), for the row-wise blocks


class PowerRetention(base_layer.BaseLayer):
  """The mixer (module docstring). Speaks `transformer.SharedStateLayer`'s
  mixer contract, owns pages (`kv_owner`) and keeps a slot state
  (`StateBytesPerSlot`)."""

  # what BlockSequence asks a mixer: it caches K and V in pages of its own
  # table, and writes them itself (the step's plan carries no page write)
  kv_owner = True
  writes_by_plan = False
  relaid_weights = True   # [D, N, H] projections: as MultiHeadedAttention's
  # the slot-state leaves a scanned block hands over whole, with the repeat's
  # index (`layer`): sliced a trip they would be copied whole a trip
  stack_states = ("state", "norm")

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim (set by the wrapping layer).")
    p.Define("num_heads", 0, "Query heads N.")
    p.Define("num_kv_heads", 0, "KV heads Nkv (0 = num_heads); one gate each.")
    p.Define("dim_per_head", 0, "Head size H (0 = input_dim / num_heads).")
    p.Define("rope_theta", 1e6, "The rotation's base; 0 = no rotation.")
    p.Define("norm_epsilon", 1e-6, "Epsilon of the norms over a head.")
    p.Define("normalizer_epsilon", 1e-6, "Epsilon beside the sum of weights.")
    p.Define("window", 1, "Keys behind the cursor whose pages a row keeps: "
             "its own page's (KindPages holds the pages from the cursor's).")
    p.Define("lowering", "auto", "'auto' | 'pallas' | 'xla' of the serving "
             "step (ops/power_retention.PackedRetention).")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.num_heads > 0
    d, n = p.input_dim, p.num_heads
    self._nk = nk = p.num_kv_heads or n
    self._h = h = p.dim_per_head or d // n
    assert n % nk == 0 and h % 2 == 0, (n, nk, h)
    assert p.window == 1, "a retention layer keeps its open chunk's page"
    init = p.params_init
    self.CreateVariable("w_query", WeightParams((d, n, h), init, p.dtype))
    self.CreateVariable("w_key", WeightParams((d, nk, h), init, p.dtype))
    self.CreateVariable("w_value", WeightParams((d, nk, h), init, p.dtype))
    self.CreateVariable("w_gate", WeightParams((d, nk), init, p.dtype))
    self.CreateVariable("w_post", WeightParams((d, n, h), init, p.dtype))
    if p.rope_theta:
      self.CreateChild(
          "rotary", layers_lib.RotaryPositionalEmbeddingLayer.Params().Set(
              embedding_dim=h, max_timescale=p.rope_theta))
    # (1 + scale), as layers.RmsNorm stores it
    for name in ("q_norm_scale", "k_norm_scale"):
      self.CreateVariable(name, WeightParams(
          (h,), WeightInit.Constant(0.0), p.dtype))

  # -- what the serving engine asks ------------------------------------------

  def StateBytesPerSlot(self) -> int:
    """S and z of one sequence as stored, f32."""
    return op.StateBytes(self._nk, self._h)

  def StepCounts(self, geometry: ragged.StepGeometry, layers: int) -> list:
    """The engine's counters (ragged.StackStepCounts): A LAYER's, not times
    the layers (op.StepCounts)."""
    del layers
    return [ragged.StepCount(
        ("retention_rows", "retention_folds", "retention_chunk_tokens"),
        functools.partial(op.StepCounts, page=geometry.page_size), True)]

  def StoredFeatureDim(self) -> int:
    return op.StoredDim(self._h)

  def KvBytesPerToken(self, kv_cache_dtype=None) -> int:
    """Bytes a token adds to the pages this layer owns: K, V and a gate."""
    assert kv_cache_dtype in (None, "bfloat16"), kv_cache_dtype
    return self._nk * (2 * self._h * jnp.dtype(self.fprop_dtype).itemsize + 4)

  def KvCacheDtype(self, kv_cache_dtype=None) -> str:
    del kv_cache_dtype
    return str(jnp.dtype(self.fprop_dtype))

  def BlockDecodeEligible(self, page_size: int) -> bool:
    """Whether the Pallas kernels serve this layer on a TPU (else the XLA
    form does, and the engine says 'dense')."""
    if self.p.lowering == "xla":
      return jax.default_backend() != "tpu"
    if jax.default_backend() != "tpu":
      return page_size > 0
    return op.SupportedOnTpu(page_size, self._h)

  def StepPlan(self, rows, b: int, t_pages: int, page_size: int):
    """What the step's rows alone decide, for every retention layer of the
    stack: BlockSequence builds it once, before its scans over layers (b,
    t_pages: the block tables' shape; the width decides nothing here)."""
    del t_pages
    with observe.Scope("attend_plan"):
      return RetentionPlan(op.BuildStepPlan(rows, b, page_size),
                           ragged.BuildLiveWidth(rows))

  # -- the layer's arithmetic ------------------------------------------------

  def _HeadNorm(self, x, scale):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + self.p.norm_epsilon)
            * (1.0 + scale.astype(jnp.float32)))

  def _Project(self, th, x, position):
    """x [..., T, D], position [..., T] -> (q [..., T, N, H] scaled by
    1 / sqrt(H), k [..., T, Nkv, H], both f32; v in the fprop dtype; log_g
    [..., T, Nkv] f32)."""
    p = self.p
    with observe.Scope("qkv_proj"):
      q = jnp.einsum("...d,dnh->...nh", x, th.w_query)
      k = jnp.einsum("...d,dnh->...nh", x, th.w_key)
      v = jnp.einsum("...d,dnh->...nh", x, th.w_value)
      gate = jnp.einsum("...d,dn->...n", x, th.w_gate)
    with observe.Scope("qk_norm"):
      q = self._HeadNorm(q, th.q_norm_scale)
      k = self._HeadNorm(k, th.k_norm_scale)
    with observe.Scope("rope"):
      if p.rope_theta:
        rt = self.ChildTheta(th, "rotary")
        q = self.rotary.FProp(rt, q, position=position)
        k = self.rotary.FProp(rt, k, position=position)
      q = q * (1.0 / math.sqrt(self._h))
    with observe.Scope("retention_gate"):
      log_g = jax.nn.log_sigmoid(gate.astype(jnp.float32))
    return q, k, v, log_g

  def FProp(self, theta, x, shared, paddings=None, segment_ids=None,
            depth=None):
    """x: [B, T, D] -> ([B, T, D], shared): the attention form, whole."""
    del depth
    if segment_ids is not None:
      raise NotImplementedError(
          "PowerRetention.FProp does not separate packed segments")
    th = self.CastTheta(theta)
    position = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    q, k, v, log_g = self._Project(th, x, position)
    if paddings is not None:
      # a padded token neither decays nor counts
      keep = 1.0 - paddings.astype(jnp.float32)
      log_g = log_g * keep[..., None]
      k = k * keep[..., None, None]
    with observe.Scope("retention_chunk"):
      y = op.AttentionForm(q, k, v, log_g, self.p.normalizer_epsilon)
    with observe.Scope("out_proj"):
      out = jnp.einsum("...nh,dnh->...d", y.astype(self.fprop_dtype),
                       th.w_post)
    return out, shared

  # -- continuous-batching serving -------------------------------------------

  def InitPagedStates(self, theta, num_slots: int) -> NestedMap:
    del theta
    assert num_slots > 0, "PowerRetention keeps a state a slot"
    state, norm = op.InitState(num_slots, self._nk, self._h)
    return NestedMap(state=state, norm=norm)

  def PagePool(self, num_pages: int, page_size: int,
               kv_cache_dtype=None) -> NestedMap:
    """The leaves of the pool this layer's pages are of (what
    `transformer.BlockSequence` asks of a mixer that owns pages): K and V,
    and beside them the cumulated log-gate a (KV head, token)."""
    attention_lib.RequireFloatPages(kv_cache_dtype, "the retention kernels")
    pool = attention_lib.KvPagePool(num_pages, page_size, self._nk, self._h,
                                    self.fprop_dtype)
    pool.gate = jnp.zeros((num_pages, self._nk, page_size), jnp.float32)
    return pool

  def RaggedMix(self, theta, x, states, shared, rows, table=None, depth=None,
                plan=None, layer=None):
    """x: [1, T, D] packed tokens (core/ragged.RaggedRows, chains only);
    table: [B, t_pages], this layer's own -> ((y [1, T, N, H] f32,), states,
    shared). The projections run over every row of the pack
    (`relaid_weights`).
    layer: None where `states` are this layer's own ([slots, ...]); the
    repeat's index where they are its block's, stacked ([repeats, slots,
    ...]): read and written in place, a slot of this layer at `layer * slots
    + slot` of the flat stack."""
    del depth
    q, k, v, log_g = self._Project(self.CastTheta(theta), x[0],
                                   rows.pos_ids.astype(jnp.int32))
    y, state, norm, pool = op.PackedRetention(
        q, k, v, log_g, states.state, states.norm, shared.kv_pool, table,
        rows, eps=self.p.normalizer_epsilon,
        plan=plan.lists if isinstance(plan, RetentionPlan) else None,
        lowering=self.p.lowering, layer=layer)
    shared = shared.Copy()
    shared.kv_pool = pool
    return (y[None],), NestedMap(state=state, norm=norm), shared

  def RaggedOut(self, theta, y, depth=None):
    """What follows the kernels, row by row: [1, n, N, H] -> [1, n, D]."""
    del depth
    with observe.Scope("out_proj"):
      return jnp.einsum("...nh,dnh->...d", y.astype(self.fprop_dtype),
                        self.CastTheta(theta).w_post)
