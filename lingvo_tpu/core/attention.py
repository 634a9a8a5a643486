"""Batch-major attention family.

TPU-native re-design of `lingvo/core/batch_major_attention.py` (10k LoC).
Capability surface reproduced: `MultiHeadedAttention` (ref `:481`) with
rotary/relative-bias options, KV-cache incremental decoding, packed-sequence
segment masks; `LocalSelfAttention` sliding-window blocked attention (ref
`:2656`); `ChunkwiseSelfAttention` (ref `:4008`).

Layout is [B, T, N, H] throughout (batch, time, heads, per-head dim) — the
reference's batch-major layout, which XLA tiles well onto the MXU. Logits and
softmax run in float32 regardless of fprop dtype (TPU numerics policy);
everything else stays bf16-friendly. Projections are einsums with mesh-axis
sharding slots: w_q [D, N, H] splits as (data=None, 'model' on N) for
Megatron-style TP — the compiler inserts the collectives (GSPMD), matching
the reference's sharding-by-annotation design (§2.9 of SURVEY.md).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from lingvo_tpu import observe
from lingvo_tpu.core import base_layer
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import py_utils
from lingvo_tpu.core import quant_utils
from lingvo_tpu.core import ragged
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.core.py_utils import WeightInit, WeightParams
from lingvo_tpu.parallel import mesh as mesh_lib
from lingvo_tpu.quant import kv as kv_quant

_NEG_INF = -2.3819763e38  # lowest bf16-safe additive mask value / 100


def CausalMask(t: int, dtype=jnp.float32) -> jax.Array:
  """[1, 1, t, t] additive mask: 0 on/below diagonal, -inf above."""
  mask = jnp.tril(jnp.ones((t, t), jnp.bool_))
  return jnp.where(mask, 0.0, _NEG_INF).astype(dtype)[None, None, :, :]


def PaddingsToMask(paddings: jax.Array, dtype=jnp.float32) -> jax.Array:
  """[b, s] paddings -> [b, 1, 1, s] additive key mask."""
  return (paddings[:, None, None, :] * _NEG_INF).astype(dtype)


def SegmentMask(q_segment_ids: jax.Array, k_segment_ids: jax.Array,
                dtype=jnp.float32) -> jax.Array:
  """Packed-sequence mask: [b, 1, t, s]; cross-segment pairs masked.

  Ref: the segment_ids produced by PackSequences (`pack_ops.cc`) gate
  attention in GShard LMs.
  """
  same = (q_segment_ids[:, :, None] == k_segment_ids[:, None, :])
  return jnp.where(same, 0.0, _NEG_INF).astype(dtype)[:, None, :, :]


def _FlashUnderMesh(q, k, v, segment_ids, causal: bool):
  """The fused flash kernel, split by hand where GSPMD would have to.

  q/k/v: [b, t, n, h], segment_ids: [b, t] or None. Mosaic kernels are not
  partitioned automatically: under an ambient mesh the TPU compiler refuses
  the bare call ("wrap the call in a shard_map"). Attention is independent
  per batch row and per head, so the split is exact: rows over 'data' and
  heads over 'model', each where the axis is in the mesh and divides the
  dim. Every other mesh axis sees a replicated call.
  """
  from lingvo_tpu.ops import flash_attention

  def _Kernel(q, k, v, seg=None):
    return flash_attention.FlashAttention(q, k, v, causal=causal,
                                          segment_ids=seg)

  mesh = mesh_lib.CurrentMesh()
  if mesh is None:
    return _Kernel(q, k, v, segment_ids)

  def _Axis(name, dim):
    return name if (name in mesh.axis_names
                    and dim % mesh.shape[name] == 0) else None

  spec = jax.sharding.PartitionSpec(
      _Axis(mesh_lib.DATA_AXIS, q.shape[0]), None,
      _Axis(mesh_lib.MODEL_AXIS, q.shape[2]), None)
  args, specs = (q, k, v), (spec, spec, spec)
  if segment_ids is not None:
    args += (segment_ids,)
    specs += (jax.sharding.PartitionSpec(spec[0], None),)
  # check_vma off: the kernel does not declare which mesh axes it varies
  # over (the setting ring_attention's and ulysses' shard_maps use)
  return mesh_lib.ShardMap(_Kernel, mesh, in_specs=specs, out_specs=spec,
                           check_vma=False)(*args)


class RaggedPlan(NamedTuple):
  """What a serving step's attention derives from its rows, static shapes
  and a layer's window alone, and so the same in every layer: a stack builds
  it once a step, before its scans over layers (`BuildRaggedPlan`), and hands
  it to each layer's RaggedStep beside `rows`. What a layer adds is its own:
  its table's lookup, its pool's page base."""
  tokens: ragged.TokenView
  blocks: dict    # {ops/ragged_block_attend.PlanKey: AttendPlan}, one for
  #                 every distinct key a kernel of the stack is called at
  writes: object  # ops/diff_attend.WritePlan where a layer of the stack
  #                 writes whole pages, else None
  runs: object    # ops/run_write.Runs: the runs of tokens the step's rows
  #                 add to their pages (MultiHeadedAttention.RaggedStep's
  #                 page write; a layer adds its table's lookup)
  narrow: object = None  # ragged.LiveWidth: whether the step's live tokens
  #                 fit the decode width, for the stack's row-wise blocks
  #                 (ragged.OverLiveRows); None: the pack has one width


def BuildRaggedPlan(keys, rows, b: int, t_pages: int,
                    page_writes: bool = False) -> RaggedPlan:
  """keys: the PlanKey of every attention call of the step (what the
  stack's layers declare, `RaggedPlanKey`); rows: its RaggedRows; block
  tables [b, t_pages]. page_writes: some layer writes through
  ops/diff_attend.WritePages' kernel. None where no layer attends."""
  from lingvo_tpu.ops import diff_attend
  from lingvo_tpu.ops import ragged_block_attend
  from lingvo_tpu.ops import run_write
  if not keys:
    return None     # no layer of the stack attends
  (page_size,) = {k.page_size for k in keys}
  with observe.Scope("attend_plan"):
    tokens = ragged.BuildTokenView(rows, b, t_pages, page_size)
    blocks = {}
    for key in sorted({k for k in keys if k.kernel}):
      tree = (tokens.q_start, rows.anc_lo, rows.anc_hi) if key.tree else ()
      blocks[key] = ragged_block_attend.BuildAttendPlan(
          key, tokens.row, tokens.q_end, *tree, b=b, t_pages=t_pages)
    writes = (diff_attend.BuildWritePlan(rows, b, t_pages, page_size)
              if page_writes else None)
    # a stack whose layers all write another way leaves the list unread,
    # and the compiler drops it
    runs = run_write.BuildWriteRuns(rows, b, t_pages, page_size)
    narrow = ragged.BuildLiveWidth(rows)
  return RaggedPlan(tokens, blocks, writes, runs, narrow)


class PerDimScaleLayer(base_layer.BaseLayer):
  """Learned per-dim query scaling (ref batch_major_attention.PerDimScale)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("dim", 0, "Per-head dim.")
    return p

  def __init__(self, params):
    super().__init__(params)
    self.CreateVariable(
        "per_dim_scale",
        WeightParams((self.p.dim,), WeightInit.Constant(0.0), self.p.dtype))

  def FProp(self, theta, inputs):
    th = self.CastTheta(theta)
    r_softplus_0 = 1.442695041
    scale = r_softplus_0 / math.sqrt(self.p.dim)
    return inputs * (jax.nn.softplus(th.per_dim_scale) * scale).astype(
        inputs.dtype)


def KvPagePool(num_pages: int, page_size: int, num_kv_heads: int,
               dim_per_head: int, dtype, quantized: bool = False,
               tile_heads: int = 1) -> NestedMap:
  """The leaves of a pool of K/V pages: `key` and `value` [num_pages,
  page_size, KV heads, H] and, quantized, their f32 scale sidecars
  [num_pages, KV heads, page_size] (transposed so that the Pallas scale
  block's minor dimension is page_size: lingvo_tpu/quant/kv.py). Every mixer
  that keeps K and V in pages declares them here. tile_heads r > 1: a
  token's row holds r KV heads side by side, [num_pages, page_size, KV heads
  / r, r * H], the same numbers in the same order (ops/ragged_block_attend.
  TileHeads says when and why)."""
  assert num_kv_heads % tile_heads == 0 and not (quantized and tile_heads > 1)
  n, h = num_kv_heads // tile_heads, dim_per_head * tile_heads
  pool = NestedMap(key=jnp.zeros((num_pages, page_size, n, h), dtype),
                   value=jnp.zeros((num_pages, page_size, n, h), dtype))
  if quantized:
    pool.key_scale = jnp.zeros((num_pages, n, page_size), jnp.float32)
    pool.value_scale = jnp.zeros((num_pages, n, page_size), jnp.float32)
  return pool


def RequireFloatPages(kv_cache_dtype, reader: str) -> None:
  """A page-owning mixer of `transformer.BlockSequence` whose `reader` takes
  float pages refuses any other: its pool is in the fprop dtype."""
  if kv_cache_dtype not in (None, "bfloat16"):
    raise NotImplementedError(
        f"kv_cache_dtype {kv_cache_dtype!r}: {reader} reads float pages")


class MultiHeadedAttention(base_layer.BaseLayer):
  """Dot-product multi-headed attention (ref `batch_major_attention.py:481`).

  FProp computes full attention; ExtendStep does one-token incremental decode
  against a KV cache (the Step-API equivalent, all-static shapes for jit).
  """

  # the [D, N, H] projections (heads of 128) are re-laid for the MXU a layer
  # at a time, a copy as large as the weights, whichever width the product
  # runs: narrowed to the rows a step holds (ragged.OverLiveRows) the product
  # costs what the copy costs, and as a conditional's operand the weight is
  # written out twice (brumby14b: `atten` + `layer_scan` 2.3 -> 3.8 ms a step,
  # a chunk step 43.2 -> 47.1 ms; PERF.md section 6, PR 50). So the serving
  # step keeps this layer's projections OUTSIDE its conditionals, over the
  # whole pack as ever, on variables the wrapping layer takes from their
  # stacks as a scan's slice was (transformer._MixThenRows).
  relaid_weights = True

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Query/output model dim.")
    p.Define("source_dim", 0, "Key/value input dim (0 = input_dim).")
    p.Define("hidden_dim", 0, "Total attention hidden dim (N*H).")
    p.Define("num_heads", 1, "Number of heads.")
    p.Define("dim_per_head", 0, "Per-head dim (0 = hidden/num_heads).")
    p.Define("num_kv_heads", 0,
             "Key/value heads (0 = num_heads). Divides num_heads: query head "
             "n reads KV head n // (num_heads // num_kv_heads). The caches "
             "and the page pool hold the KV heads only.")
    p.Define("window", 0,
             "If >0, causal sliding window (left_context semantics: query i "
             "sees keys j with i - window < j <= i). FProp masks; RaggedStep "
             "also starts each query block at the first page its window "
             "reaches, and the serving engine lets go of the pages behind "
             "it (serving/kv_cache.KindPages).")
    p.Define("rope_max_timescale", 1e4,
             "RoPE base (theta) where use_rotary_position_emb.")
    p.Define("use_bias", True, "Bias on projections.")
    p.Define("enable_per_dim_scale", True,
             "Learned per-dim query scale instead of 1/sqrt(H).")
    p.Define("atten_dropout_prob", 0.0, "Attention prob dropout.")
    p.Define("atten_logit_cap", 0.0, "If >0, tanh-cap logits.")
    p.Define("use_rotary_position_emb", False, "Apply RoPE to q/k.")
    p.Define(
        "use_flash_attention", False,
        "Use the fused Pallas flash kernel when eligible (self-attention, "
        "causal-or-full, no paddings/segments/rel-bias/dropout/logit-cap); "
        "falls back to the einsum path otherwise.")
    p.Define(
        "decode_page_size", 0,
        "If >0, ExtendStep reads the KV cache through the length-aware "
        "paged flash-decode kernel (ops/flash_decode.py) in pages of this "
        "many slots, touching only pages up to time_step instead of the "
        "whole max_len cache. 0 = legacy dense path (exact legacy "
        "numerics). Requires max_len % decode_page_size == 0 and no "
        "rel-pos bias / logit cap / prob quantization; ineligible configs "
        "fall back to the dense path.")
    p.Define(
        "kv_cache_dtype", None,
        "Storage dtype for the decode KV caches (dense ExtendStep cache "
        "and the block-table page pool): None/'' = fprop dtype (bit-exact "
        "legacy caches), 'float32'/'bfloat16' = plain storage cast, "
        "'int8' = quantize-on-write with per-token-per-head f32 scale "
        "sidecars and dequantize-on-read (lingvo_tpu/quant/kv.py). "
        "Training FProp never touches this.")
    p.Define("rel_pos_emb_dim", 0,
             "If >0, learned relative position bias buckets (T5-style).")
    p.Define("rel_pos_max_distance", 128, "Relative bucket clip distance.")
    p.Define("qk_norm_epsilon", None,
             "If set, q and k each go through a layers.RmsNorm over a head's "
             "dims at this epsilon (children `q_norm`, `k_norm`: a learned "
             "scale of dim_per_head each, shared by the heads), BEFORE any "
             "rotation. None: no norm, no variable and no op.")
    p.Define("output_gate", False,
             "The attend's output times sigmoid(x W_gate), W_gate [D, N, H] "
             "of its own (a bias as use_bias says), elementwise over the "
             "heads' dims, BEFORE the output projection. False: no gate, no "
             "variable and no op.")
    p.Define("qdomain_weight", None,
             "QDomain params for the q/k/v/post projection weights (ref "
             "batch_major_attention.py:303 TrackQWeight).")
    p.Define("qdomain_softmax", None,
             "QDomain for post-softmax attention probs (ref attention.py:440 "
             "qsoftmax; natural range [0,1] — FixedRangeQDomain(0,1) is the "
             "scan-safe choice). Disables the flash-kernel path: the fused "
             "kernel never materializes probs.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.num_heads > 0
    hidden = p.hidden_dim or p.input_dim
    self._dim_per_head = p.dim_per_head or hidden // p.num_heads
    n, h, d = p.num_heads, self._dim_per_head, p.input_dim
    self._num_kv_heads = p.num_kv_heads or n
    assert n % self._num_kv_heads == 0, (n, self._num_kv_heads)
    assert p.window >= 0, p.window
    sd = p.source_dim or d
    wsdm = p.weight_split_dims_mapping  # e.g. (None, 'model', None)
    for name, in_dim, heads in (
        ("query", d, n), ("key", sd, self._num_kv_heads),
        ("value", sd, self._num_kv_heads)) + (("gate", d, n),) * bool(
            p.output_gate):
      self.CreateVariable(
          f"w_{name}",
          WeightParams((in_dim, heads, h), p.params_init, p.dtype,
                       tensor_split_dims_mapping=wsdm))
      if p.use_bias:
        self.CreateVariable(
            f"b_{name}", WeightParams((heads, h), WeightInit.Constant(0.0),
                                      p.dtype))
    self.CreateVariable(
        "w_post",
        WeightParams((d, n, h), p.params_init, p.dtype,
                     tensor_split_dims_mapping=wsdm))
    if p.use_bias:
      self.CreateVariable(
          "b_post", WeightParams((d,), WeightInit.Constant(0.0), p.dtype))
    if p.qk_norm_epsilon is not None:
      for name in ("q_norm", "k_norm"):
        self.CreateChild(name, layers_lib.RmsNorm.Params().Set(
            input_dim=h, epsilon=p.qk_norm_epsilon))
    if p.enable_per_dim_scale:
      self.CreateChild("per_dim_scale",
                       PerDimScaleLayer.Params().Set(dim=h))
    if p.use_rotary_position_emb:
      self.CreateChild(
          "rotary",
          layers_lib.RotaryPositionalEmbeddingLayer.Params().Set(
              embedding_dim=h, max_timescale=p.rope_max_timescale))
    if p.rel_pos_emb_dim > 0:
      self.CreateVariable(
          "rel_pos_bias",
          WeightParams((p.num_heads, 2 * p.rel_pos_max_distance + 1),
                       WeightInit.Constant(0.0), p.dtype))
    self.CreateChild("atten_dropout",
                     layers_lib.DeterministicDropoutLayer.Params())
    if p.qdomain_weight is not None:
      self.CreateChild("qdomain_weight", p.qdomain_weight.Copy())
    if p.qdomain_softmax is not None:
      self.CreateChild("qdomain_softmax", p.qdomain_softmax.Copy())

  # -- projections -----------------------------------------------------------

  def _QProjWeight(self, theta, w):
    if self.p.qdomain_weight is None:
      return w
    return self.qdomain_weight.QuantizeWeight(
        self.ChildTheta(theta, "qdomain_weight"), w)

  def _QProbs(self, theta, probs):
    """Fake-quantize post-softmax probs (all softmax sites route here)."""
    if self.p.qdomain_softmax is None:
      return probs
    return self.qdomain_softmax.QuantizeAct(
        self.ChildTheta(theta, "qdomain_softmax"), "softmax", probs)

  def _HeadsProj(self, theta, name, x):
    th = self.CastTheta(theta)
    w = th[f"w_{name}"]
    if isinstance(w, quant_utils.Int8Weight):
      # int8-serving theta: [B,T,D] x int8 [D,N,H] on the MXU ('dv' layout,
      # per-(N,H)-channel scales). Fake-quant domains don't compose with
      # the real integer path.
      assert self.p.qdomain_weight is None
      out = w.Einsum(self.ToFPropDtype(x))
    else:
      out = jnp.einsum("BTD,DNH->BTNH", self.ToFPropDtype(x),
                       self._QProjWeight(theta, w))
    if self.p.use_bias:
      out = out + th[f"b_{name}"]
    return out

  def _PostProj(self, theta, ctx):
    th = self.CastTheta(theta)
    w = th.w_post
    if isinstance(w, quant_utils.Int8Weight):
      # [B,T,N,H] contracts (N, H) against int8 [D,N,H] ('vd' layout,
      # per-D-channel scales).
      assert self.p.qdomain_weight is None
      out = w.Einsum(ctx)
    else:
      out = jnp.einsum("BTNH,DNH->BTD", ctx, self._QProjWeight(theta, w))
    if self.p.use_bias:
      out = out + th.b_post
    return out

  def _ScaleQuery(self, theta, q):
    if self.p.enable_per_dim_scale:
      return self.per_dim_scale.FProp(
          self.ChildTheta(theta, "per_dim_scale"), q)
    return q * (1.0 / math.sqrt(self._dim_per_head))

  def _QkNorm(self, theta, q, k):
    """q [.., N, H], k [.., Nkv, H] as projected -> each normed over H
    (`qk_norm_epsilon`), in the dtype they came in; before the rotation."""
    if self.p.qk_norm_epsilon is None:
      return q, k
    with observe.Scope("qk_norm"):
      return (self.q_norm.FProp(self.ChildTheta(theta, "q_norm"), q),
              self.k_norm.FProp(self.ChildTheta(theta, "k_norm"), k))

  def _Gated(self, theta, x, ctx):
    """ctx [B, T, N, H], the attend's output for the layer's input x
    [B, T, D] -> ctx * sigmoid(x W_gate) (`output_gate`): what the output
    projection reads."""
    if not self.p.output_gate:
      return ctx
    with observe.Scope("atten_gate"):
      gate = self._HeadsProj(theta, "gate", x)
      return ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)

  @property
  def kv_group(self) -> int:
    """Query heads a KV head serves (1: plain multi-head attention)."""
    return self.p.num_heads // self._num_kv_heads

  def _RepeatKv(self, x):
    """[B, S, Nkv, H] -> [B, S, N, H] for the dense einsum paths."""
    return x if self.kv_group == 1 else jnp.repeat(x, self.kv_group, axis=2)

  def _RequirePlainKv(self, method: str):
    """The dense decode paths keep plain MHA caches and no window."""
    if self.kv_group > 1 or self.p.window > 0:
      raise NotImplementedError(
          f"{method} serves plain multi-head attention without a window; "
          f"a layer with num_kv_heads={self._num_kv_heads} of "
          f"{self.p.num_heads} heads or window={self.p.window} is served "
          "by RaggedStep (ServingLoop) and trained by FProp")

  def _RequireNoNormNoGate(self, method: str):
    """The head norm and the output gate are built where a model that has
    them runs: FProp, the dense decode contracts and the serving step."""
    if self.p.qk_norm_epsilon is not None or self.p.output_gate:
      raise NotImplementedError(
          f"{method} has no head norm (qk_norm_epsilon) and no output_gate")

  def _RelPosBias(self, theta, t: int, s: int):
    p = self.p
    th = self.CastTheta(theta)
    rel = jnp.arange(s)[None, :] - jnp.arange(t)[:, None]
    rel = jnp.clip(rel, -p.rel_pos_max_distance, p.rel_pos_max_distance)
    idx = rel + p.rel_pos_max_distance
    return th.rel_pos_bias[:, idx][None]  # [1, N, T, S]

  # -- core ------------------------------------------------------------------

  def _Atten(self, theta, q, k, v, atten_mask):
    """q:[B,T,N,H] k,v:[B,S,N,H] mask additive broadcastable [B,N,T,S]."""
    p = self.p
    logits = jnp.einsum("BTNH,BSNH->BNTS", q, k)
    if p.atten_logit_cap > 0:
      logits = p.atten_logit_cap * jnp.tanh(logits / p.atten_logit_cap)
    logits = logits.astype(jnp.float32)
    if p.rel_pos_emb_dim > 0:
      logits = logits + self._RelPosBias(theta, q.shape[1],
                                         k.shape[1]).astype(jnp.float32)
    if atten_mask is not None:
      logits = logits + atten_mask.astype(jnp.float32)
    # Stacked masks can sum below f32 min (-inf -> NaN softmax rows on fully
    # masked queries); clamp keeps rows finite, padding zeroes them later.
    logits = jnp.maximum(logits, _NEG_INF)
    probs = self._QProbs(theta, jax.nn.softmax(logits, axis=-1).astype(
        q.dtype))
    if p.atten_dropout_prob > 0:
      probs = self.atten_dropout.FProp(
          self.ChildTheta(theta, "atten_dropout"), probs,
          keep_prob=1.0 - p.atten_dropout_prob)
    return jnp.einsum("BNTS,BSNH->BTNH", probs, v), probs

  def _FlashEligible(self, key_vec, atten_mask, needs_seg, t):
    """Self-attention with only causal/padding/segment masking can run the
    fused kernel (paddings/segment_ids fold into the kernel's segment mask;
    arbitrary additive atten_mask cannot). On real TPU the segment path
    further requires t % 128 == 0 (Mosaic lane alignment) — shorter inputs
    fall back to the einsum path."""
    p = self.p
    if not (p.use_flash_attention and key_vec is None
            and atten_mask is None and
            p.rel_pos_emb_dim == 0 and p.atten_logit_cap == 0 and
            p.atten_dropout_prob == 0 and p.qdomain_softmax is None and
            p.window == 0 and self.kv_group == 1 and
            t % 16 == 0):
      return False
    if jax.default_backend() == "tpu":
      from lingvo_tpu.ops import flash_attention
      return flash_attention.SupportedOnTpu(t, with_segments=needs_seg)
    return True

  def FProp(self, theta, query_vec, key_vec=None, value_vec=None,
            paddings=None, atten_mask=None, segment_ids=None, causal=False):
    """Returns ([B,T,D] output, [B,N,T,S] probs or None on the flash path).

    atten_mask: optional additive mask (e.g. CausalMask). paddings are key
    paddings [B,S]. segment_ids: [B,T] packed-input ids for both q and k
    (self-attention) — adds a SegmentMask. `causal=True` is an alternative
    to passing CausalMask that lets the fused flash kernel run.
    """
    use_flash = self._FlashEligible(
        key_vec, atten_mask, paddings is not None or segment_ids is not None,
        query_vec.shape[1])
    key_vec = query_vec if key_vec is None else key_vec
    value_vec = key_vec if value_vec is None else value_vec
    with observe.Scope("qkv_proj"):
      q = self._HeadsProj(theta, "query", query_vec)
      k = self._HeadsProj(theta, "key", key_vec)
      v = self._HeadsProj(theta, "value", value_vec)
    q, k = self._QkNorm(theta, q, k)
    with observe.Scope("rope"):
      if self.p.use_rotary_position_emb:
        rt = self.ChildTheta(theta, "rotary")
        q = self.rotary.FProp(rt, q)
        k = self.rotary.FProp(rt, k)
      q = self._ScaleQuery(theta, q)
    if use_flash:
      # paddings/segment_ids both become the kernel's segment mask: padding
      # gets segment 0 (packed inputs already carry 0 there; enforce it so
      # pad keys never leak into real queries)
      seg = segment_ids
      if paddings is not None:
        base = segment_ids if segment_ids is not None else jnp.ones_like(
            paddings, jnp.int32)
        seg = jnp.where(paddings > 0.5, 0, base).astype(jnp.int32)
      # the kernel scales by 1/sqrt(h) internally; q already carries the
      # (learned) query scale, so cancel the kernel's factor.
      h = self._dim_per_head
      ctx = _FlashUnderMesh(q * math.sqrt(h), k, v, seg, causal)
      if paddings is not None:
        # strict path parity: flash pad queries attend only pad keys while
        # the einsum path lets them attend real keys — both garbage, but a
        # downstream consumer mixing across time without re-masking would
        # see different numerics depending on the engaged path. Zero them.
        ctx = py_utils.ApplyPadding(paddings, ctx)
      ctx = self._Gated(theta, query_vec, ctx)
      with observe.Scope("out_proj"):
        return self._PostProj(theta, ctx), None
    mask = atten_mask
    if causal:
      cm = CausalMask(query_vec.shape[1])
      mask = cm if mask is None else mask + cm
    if self.p.window > 0:
      t, s_len = query_vec.shape[1], key_vec.shape[1]
      behind = (jnp.arange(t)[:, None] - jnp.arange(s_len)[None, :]
                >= self.p.window)
      wm = jnp.where(behind, _NEG_INF, 0.0)[None, None]
      mask = wm if mask is None else mask + wm
    if paddings is not None:
      pm = PaddingsToMask(paddings)
      mask = pm if mask is None else mask + pm
    if segment_ids is not None:
      sm = SegmentMask(segment_ids, segment_ids)
      mask = sm if mask is None else mask + sm
    ctx, probs = self._Atten(theta, q, self._RepeatKv(k), self._RepeatKv(v),
                             mask)
    ctx = self._Gated(theta, query_vec, ctx)
    with observe.Scope("out_proj"):
      return self._PostProj(theta, ctx), probs

  # -- chunk streaming (ref conformer streaming / stream_step_test_base) -----

  def InitStreamStates(self, batch_size: int, left_context: int) -> NestedMap:
    """Sliding-window streaming state: the last left_context-1 source frames'
    K/V (cached PRE-rotary — rotary attention depends only on relative
    position, so each chunk re-rotates with local positions) + paddings."""
    self._RequirePlainKv("InitStreamStates")
    n, h = self.p.num_heads, self._dim_per_head
    ctx = max(left_context - 1, 0)
    dtype = self.fprop_dtype
    return NestedMap(
        key=jnp.zeros((batch_size, ctx, n, h), dtype),
        value=jnp.zeros((batch_size, ctx, n, h), dtype),
        paddings=jnp.ones((batch_size, ctx), jnp.float32),
        left_context=left_context)

  def StreamStep(self, theta, inputs, paddings, cached_states):
    """One chunk of causal sliding-window attention.

    inputs [B, C, D], paddings [B, C] -> (out [B, C, D], new states).
    Equivalent to offline LocalSelfAttention(left_context, right_context=0)
    consumed chunk by chunk (asserted by streaming-equivalence tests).
    """
    p = self.p
    assert p.rel_pos_emb_dim <= 0, (
        "StreamStep computes chunk-local query indices; the T5 relative "
        "bias would use wrong buckets (needs a ctx_len offset)")
    left = cached_states.left_context
    ctx_len = cached_states.key.shape[1]
    b, c, _ = inputs.shape
    q = self._HeadsProj(theta, "query", inputs)
    k_new = self._HeadsProj(theta, "key", inputs)
    v_new = self._HeadsProj(theta, "value", inputs)
    k_cat = jnp.concatenate(
        [cached_states.key, k_new.astype(cached_states.key.dtype)], axis=1)
    v_cat = jnp.concatenate(
        [cached_states.value, v_new.astype(cached_states.value.dtype)],
        axis=1)
    pad_cat = jnp.concatenate([cached_states.paddings, paddings], axis=1)
    self._RequireNoNormNoGate("StreamStep")
    if p.use_rotary_position_emb:
      rt = self.ChildTheta(theta, "rotary")
      s = ctx_len + c
      pos_k = jnp.arange(s, dtype=jnp.float32)[None]
      pos_q = pos_k[:, ctx_len:]
      q = self.rotary.FProp(rt, q, position=pos_q)
      k_rot = self.rotary.FProp(rt, k_cat, position=pos_k)
    else:
      k_rot = k_cat
    q = self._ScaleQuery(theta, q)
    # window mask: query i (global ctx_len+i) sees j with
    # 0 <= (ctx_len+i) - j <= left-1
    qpos = ctx_len + jnp.arange(c)[:, None]
    jpos = jnp.arange(ctx_len + c)[None, :]
    visible = (qpos >= jpos) & (qpos - jpos <= left - 1)
    mask = jnp.where(visible, 0.0, _NEG_INF)[None, None]
    mask = mask + PaddingsToMask(pad_cat)
    ctx_vec, _ = self._Atten(theta, q, k_rot, v_cat, mask)
    out = self._PostProj(theta, ctx_vec)
    out = py_utils.ApplyPadding(paddings, out)
    keep = ctx_len  # buffer length stays fixed
    new_states = NestedMap(
        key=k_cat[:, c:] if keep else k_cat[:, :0],
        value=v_cat[:, c:] if keep else v_cat[:, :0],
        paddings=pad_cat[:, c:] if keep else pad_cat[:, :0],
        left_context=left)
    return out, new_states

  # -- incremental decode ----------------------------------------------------

  def _KvDtype(self, kv_cache_dtype=None):
    """(cache storage dtype, quantized?) — an explicit override beats the
    layer param; None/'' on both means the legacy fprop-dtype cache."""
    return kv_quant.ResolveKvCacheDtype(
        kv_cache_dtype or self.p.kv_cache_dtype, self.fprop_dtype)

  def KvCacheDtype(self, kv_cache_dtype=None) -> str:
    """The effective cache storage dtype name (telemetry)."""
    return str(self._KvDtype(kv_cache_dtype)[0])

  def KvBytesPerToken(self, kv_cache_dtype=None) -> int:
    """K + V bytes per cached token in this layer, scale sidecars included."""
    return kv_quant.KvBytesPerToken(self._num_kv_heads, self._dim_per_head,
                                    kv_cache_dtype or self.p.kv_cache_dtype,
                                    self.fprop_dtype)

  def InitStates(self, theta, batch_size: int, max_len: int) -> NestedMap:
    self._RequirePlainKv("InitStates (ExtendStep, Prefill)")
    n, h = self.p.num_heads, self._dim_per_head
    dtype, quantized = self._KvDtype()
    states = NestedMap(
        key=jnp.zeros((batch_size, max_len, n, h), dtype),
        value=jnp.zeros((batch_size, max_len, n, h), dtype),
        time_step=jnp.zeros((), jnp.int32))
    if quantized:
      # per-token-per-head f32 scales; unwritten slots stay (0, scale 0) ->
      # dequantize to exact zeros, and are masked anyway.
      states.key_scale = jnp.zeros((batch_size, max_len, n), jnp.float32)
      states.value_scale = jnp.zeros((batch_size, max_len, n), jnp.float32)
    return states

  def PagedDecodeEligible(self, max_len: int) -> bool:
    """The paged flash-decode kernel handles plain masked softmax attention
    only; rel-pos bias, logit caps, attention dropout, and prob quantization
    stay dense — as do shapes the Pallas kernel can't tile on real TPU."""
    p = self.p
    from lingvo_tpu.ops import flash_decode
    if jax.default_backend() == "tpu" and not flash_decode.SupportedOnTpu(
        p.decode_page_size, self._dim_per_head):
      return False
    return (flash_decode.SupportedShape(max_len, p.decode_page_size)
            and p.rel_pos_emb_dim == 0 and p.atten_logit_cap == 0
            and p.atten_dropout_prob == 0.0 and p.qdomain_softmax is None)

  def ExtendStep(self, theta, query_vec, cached_states: NestedMap,
                 paddings=None):
    """query_vec: [B, 1, D]; returns ([B, 1, D], updated states)."""
    t = cached_states.time_step
    q = self._HeadsProj(theta, "query", query_vec)
    k_new = self._HeadsProj(theta, "key", query_vec)
    v_new = self._HeadsProj(theta, "value", query_vec)
    q, k_new = self._QkNorm(theta, q, k_new)
    if self.p.use_rotary_position_emb:
      rt = self.ChildTheta(theta, "rotary")
      pos = t.astype(jnp.float32)[None, None]
      q = self.rotary.FProp(rt, q, position=pos)
      k_new = self.rotary.FProp(rt, k_new, position=pos)
    q = self._ScaleQuery(theta, q)
    quantized = "key_scale" in cached_states
    if quantized:
      k_new, k_s = kv_quant.QuantizeKv(k_new)              # int8, [B,1,N]
      v_new, v_s = kv_quant.QuantizeKv(v_new)
      key_scale = jax.lax.dynamic_update_slice_in_dim(
          cached_states.key_scale, k_s, t, axis=1)
      value_scale = jax.lax.dynamic_update_slice_in_dim(
          cached_states.value_scale, v_s, t, axis=1)
    key_cache = jax.lax.dynamic_update_slice_in_dim(
        cached_states.key, k_new.astype(cached_states.key.dtype), t, axis=1)
    value_cache = jax.lax.dynamic_update_slice_in_dim(
        cached_states.value, v_new.astype(cached_states.value.dtype), t,
        axis=1)
    max_len = key_cache.shape[1]
    if self.PagedDecodeEligible(max_len) and not quantized:
      # length-aware paged read: only cache pages up to time_step are
      # touched (O(t) per step instead of O(max_len)); q carries the
      # learned scale already, the kernel applies none.
      from lingvo_tpu.ops import flash_decode
      ctx = flash_decode.FlashDecode(
          q, key_cache, value_cache, t,
          page_size=self.p.decode_page_size, cache_paddings=paddings)
    else:
      # mask out future (and unwritten) positions; quantized caches
      # dequantize-on-read and run the dense einsum path (the contiguous
      # flash_decode kernel has no scale plumbing — the block-table kernel
      # in PagedStep is the quantized hot path).
      k_read, v_read = key_cache, value_cache
      if quantized:
        k_read = kv_quant.DequantKv(key_cache, key_scale)
        v_read = kv_quant.DequantKv(value_cache, value_scale)
      pos_ids = jnp.arange(max_len)[None, None, None, :]
      mask = jnp.where(pos_ids <= t, 0.0, _NEG_INF)
      if paddings is not None:
        mask = mask + PaddingsToMask(paddings)
      ctx, _ = self._Atten(theta, q, k_read, v_read, mask)
    new_states = NestedMap(
        key=key_cache, value=value_cache, time_step=t + 1)
    if quantized:
      new_states.key_scale = key_scale
      new_states.value_scale = value_scale
    ctx = self._Gated(theta, query_vec, ctx)
    return self._PostProj(theta, ctx), new_states

  def Prefill(self, theta, query_vec, cached_states: NestedMap,
              paddings=None, live_len: int | None = None):
    """Chunked prefill: one full-attention pass over a whole prompt chunk.

    query_vec: [B, C, D] occupying cache slots [time_step, time_step + C);
    K/V for all C positions land in the cache in ONE dynamic_update_slice
    (vs C sequential ExtendStep calls). Returns ([B, C, D], states). The
    written cache is bit-identical to the per-token path (projections and
    rotary are elementwise-per-position); outputs match to float tolerance
    (the [C, S] context matmul blocks differently than C matvecs).

    live_len: optional STATIC bound with time_step + C <= live_len; the
    attention read touches only cache slots [0, live_len) instead of the
    whole max_len cache (the decode tail is unwritten and masked anyway —
    skipping it only removes exact-zero softmax contributions). Callers
    with static chunk offsets (gshard_decode) pass start + C.
    """
    assert self.p.rel_pos_emb_dim <= 0, (
        "Prefill computes chunk-local query indices; the T5 relative bias "
        "would use wrong buckets (needs a time_step offset)")
    t = cached_states.time_step
    c = query_vec.shape[1]
    q = self._HeadsProj(theta, "query", query_vec)
    k_new = self._HeadsProj(theta, "key", query_vec)
    v_new = self._HeadsProj(theta, "value", query_vec)
    q, k_new = self._QkNorm(theta, q, k_new)
    if self.p.use_rotary_position_emb:
      rt = self.ChildTheta(theta, "rotary")
      pos = (t + jnp.arange(c, dtype=jnp.int32)).astype(jnp.float32)[None, :]
      q = self.rotary.FProp(rt, q, position=pos)
      k_new = self.rotary.FProp(rt, k_new, position=pos)
    q = self._ScaleQuery(theta, q)
    quantized = "key_scale" in cached_states
    if quantized:
      k_new, k_s = kv_quant.QuantizeKv(k_new)              # int8, [B,C,N]
      v_new, v_s = kv_quant.QuantizeKv(v_new)
      key_scale = jax.lax.dynamic_update_slice_in_dim(
          cached_states.key_scale, k_s, t, axis=1)
      value_scale = jax.lax.dynamic_update_slice_in_dim(
          cached_states.value_scale, v_s, t, axis=1)
    key_cache = jax.lax.dynamic_update_slice_in_dim(
        cached_states.key, k_new.astype(cached_states.key.dtype), t, axis=1)
    value_cache = jax.lax.dynamic_update_slice_in_dim(
        cached_states.value, v_new.astype(cached_states.value.dtype), t,
        axis=1)
    live = key_cache.shape[1] if live_len is None else live_len
    k_read, v_read = key_cache[:, :live], value_cache[:, :live]
    if quantized:
      k_read = kv_quant.DequantKv(k_read, key_scale[:, :live])
      v_read = kv_quant.DequantKv(v_read, value_scale[:, :live])
    # query i (global slot t+i) sees slot s iff s <= t+i (causal within the
    # chunk + everything already cached); unwritten tail slots masked.
    slot = jnp.arange(live)[None, None, None, :]
    qpos = t + jnp.arange(c)[None, None, :, None]
    mask = jnp.where(slot <= qpos, 0.0, _NEG_INF)
    if paddings is not None:
      mask = mask + PaddingsToMask(paddings[:, :live])
    ctx, _ = self._Atten(theta, q, k_read, v_read, mask)
    new_states = NestedMap(
        key=key_cache, value=value_cache, time_step=t + c)
    if quantized:
      new_states.key_scale = key_scale
      new_states.value_scale = value_scale
    ctx = self._Gated(theta, query_vec, ctx)
    return self._PostProj(theta, ctx), new_states

  # -- block-table paged decode (serving engine) -----------------------------

  def InitPagedStates(self, theta, num_pages: int, page_size: int,
                      num_slots: int = 0,
                      kv_cache_dtype: str | None = None) -> NestedMap:
    """Global KV page pool [num_pages, page_size, N, H] shared by all
    sequences; which pages belong to whom lives host-side in the serving
    engine's block tables, so there is no time_step here (per-sequence
    lengths ride each PagedStep call). The engine reserves the LAST page as
    the trash page that padding-token writes scatter into — allocate with
    one extra page and never hand page num_pages-1 to the allocator.
    num_slots is the engine slot count, consumed by O(1)-state mixers
    (ssm.GatedSSMLayer) and ignored here. kv_cache_dtype overrides the
    layer's p.kv_cache_dtype; 'int8' adds the [num_pages, N, page_size]
    f32 scale sidecars (transposed so the Pallas scale block's minor dim
    is page_size — see lingvo_tpu/quant/kv.py)."""
    del theta, num_slots
    n, h = self._num_kv_heads, self._dim_per_head
    dtype, quantized = self._KvDtype(kv_cache_dtype)
    if quantized and self.kv_group > 1:
      raise NotImplementedError(
          f"int8 KV pages under num_kv_heads={n} of {self.p.num_heads} "
          "heads: the grouped ragged kernel reads float pages")
    return KvPagePool(num_pages, page_size, n, h, dtype, quantized)

  def RaggedQueryBlock(self, page_size: int, kv_cache_dtype=None) -> int:
    """Queries of one row that the ragged kernel runs against a page
    together (ops/ragged_block_attend.QueryBlock at this layer's shapes):
    what the engine's block-fill counters divide by."""
    from lingvo_tpu.ops import ragged_block_attend
    return ragged_block_attend.QueryBlock(
        self._num_kv_heads, self._dim_per_head, page_size, self.fprop_dtype,
        self._KvDtype(kv_cache_dtype)[0],
        grouped=ragged_block_attend.Grouped(self.p.num_heads,
                                            self._num_kv_heads))

  def StepCounts(self, geometry: ragged.StepGeometry, layers: int) -> list:
    """The engine's counters (ragged.StackStepCounts): block fill, by runs."""
    from lingvo_tpu.ops import ragged_block_attend
    del layers
    group = self.kv_group
    laid = ragged_block_attend.GroupLanes(group) if group > 1 else 1
    return [ragged.BlockFillCount(
        self.RaggedQueryBlock(geometry.page_size, geometry.kv_cache_dtype),
        laid, group), ragged.RunWriteCount(geometry.page_size)]

  def _RaggedEligible(self, cached_states) -> bool:
    """Whether RaggedStep over these paged states calls RaggedAttend (else
    its gather-dense fallback)."""
    page_size = cached_states.key.shape[-3]
    if "key_scale" in cached_states:
      return self.QuantizedDecodeEligible(page_size)
    return self.BlockDecodeEligible(page_size)

  def RaggedPlanKey(self, cached_states):
    """The ops/ragged_block_attend.PlanKey of this layer's RaggedStep over
    these paged states (its own, or stacked over a repeat axis): what a
    stack hands `BuildRaggedPlan`, and what RaggedAttend looks its
    descriptors up by."""
    from lingvo_tpu.ops import ragged_block_attend
    return ragged_block_attend.AttendPlanKey(
        self.p.num_heads, self._num_kv_heads, self._dim_per_head,
        cached_states.key.shape[-3], self.fprop_dtype, cached_states.key.dtype,
        window=self.p.window,
        lowering="auto" if self._RaggedEligible(cached_states) else "xla")

  def BlockDecodeEligible(self, page_size: int) -> bool:
    """Same gate family as PagedDecodeEligible, for the block-table kernel:
    plain masked-softmax attention only. Ineligible configs run PagedStep's
    gather-dense fallback (exact, just not paged-fast) — the engine surfaces
    that in its stats so a dense run never masquerades as paged."""
    p = self.p
    if jax.default_backend() == "tpu":
      from lingvo_tpu.ops import block_decode
      # what tiles the lanes is a token's row of the pool: a head, or the
      # heads it holds side by side (`_PoolTileHeads`)
      if not block_decode.SupportedOnTpu(
          page_size, self._dim_per_head * self._PoolTileHeads()):
        return False
    return (page_size > 0 and p.rel_pos_emb_dim == 0
            and p.atten_logit_cap == 0 and p.atten_dropout_prob == 0.0
            and p.qdomain_softmax is None)

  def _PoolTileHeads(self) -> int:
    """KV heads a token's row of this layer's pool holds side by side (1:
    the pool is [pages, P, KV heads, H]); PooledAttention says otherwise."""
    return 1

  def QuantizedDecodeEligible(self, page_size: int) -> bool:
    """Whether the int8 block-table kernels can serve this layer: the
    BlockDecodeEligible gate plus the int8-aware TPU tiling check. An
    int8 pool that fails this gate still decodes correctly — PagedStep
    gathers, dequantizes, and runs the dense einsum path — but the engine
    reports the step as 'dense' so the fallback is never silent."""
    p = self.p
    if jax.default_backend() == "tpu":
      from lingvo_tpu.ops import block_decode
      if not block_decode.SupportedOnTpu(page_size, self._dim_per_head,
                                         kv_dtype="int8"):
        return False
    return (page_size > 0 and p.rel_pos_emb_dim == 0
            and p.atten_logit_cap == 0 and p.atten_dropout_prob == 0.0
            and p.qdomain_softmax is None)

  def PagedStep(self, theta, query_vec, cached_states: NestedMap,
                block_tables, q_pos, in_len):
    """One continuous-batching step against the block-table page pool.

    query_vec: [B, C, D] — row b's tokens for global slots
    [q_pos[b], q_pos[b] + in_len[b]); queries past in_len[b] are padding
    (their pool writes go to the trash page, their outputs are garbage the
    engine discards). C == 1 is the steady-state decode step; C > 1 is a
    chunked-prefill step (decode rows riding a mixed step use in_len == 1).
    block_tables: [B, t_pages] int32 physical page ids (allocator-owned;
    rows own disjoint pages, so valid writes never collide). q_pos/in_len:
    [B] int32. Returns ([B, C, D], updated states). Unlike ExtendStep the
    layout is LEFT-aligned with no cache_paddings: rotary attention depends
    only on relative position, so numerics match the right-aligned dense
    path (asserted by the engine parity tests).
    """
    self._RequirePlainKv("PagedStep")
    from lingvo_tpu.ops import block_decode
    p = self.p
    assert p.rel_pos_emb_dim <= 0, (
        "PagedStep computes positions from q_pos; the T5 relative bias "
        "would use wrong buckets")
    k_pool, v_pool = cached_states.key, cached_states.value
    np_total, page_size = k_pool.shape[0], k_pool.shape[1]
    t_pages = block_tables.shape[1]
    b, c, _ = query_vec.shape
    q_pos = q_pos.astype(jnp.int32)
    in_len = in_len.astype(jnp.int32)
    q = self._HeadsProj(theta, "query", query_vec)
    k_new = self._HeadsProj(theta, "key", query_vec)
    v_new = self._HeadsProj(theta, "value", query_vec)
    pos_i = q_pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]  # [B, C]
    q, k_new = self._QkNorm(theta, q, k_new)
    if p.use_rotary_position_emb:
      rt = self.ChildTheta(theta, "rotary")
      pos = pos_i.astype(jnp.float32)
      q = self.rotary.FProp(rt, q, position=pos)
      k_new = self.rotary.FProp(rt, k_new, position=pos)
    q = self._ScaleQuery(theta, q)
    # scatter the chunk's K/V through the block table BEFORE the attention
    # read (chunk self-attention needs them); padding queries write to the
    # trash page (pool page np_total - 1, never in any block table)
    valid = jnp.arange(c, dtype=jnp.int32)[None] < in_len[:, None]  # [B, C]
    logical = jnp.clip(pos_i // page_size, 0, t_pages - 1)
    phys = jnp.take_along_axis(
        jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1),
        logical, axis=1)                                           # [B, C]
    phys = jnp.where(valid, phys, np_total - 1)
    off = jnp.where(valid, pos_i % page_size,
                    jnp.arange(c, dtype=jnp.int32)[None] % page_size)
    quantized = "key_scale" in cached_states
    k_scale = v_scale = None
    if quantized:
      # quantize-on-write: each token row gets its own per-head scale, so
      # the scatter below is the ONLY write this token's page ever sees —
      # no page-level re-quantization. Sidecar layout [NP, N, P]: the two
      # advanced indices (phys, off) around the head slice broadcast to
      # the front, so the update shape is [B, C, N] == the scale shape.
      k_new, k_s = kv_quant.QuantizeKv(k_new)              # int8, [B,C,N]
      v_new, v_s = kv_quant.QuantizeKv(v_new)
      with observe.Scope("kv_write"):
        k_scale = cached_states.key_scale.at[phys, :, off].set(k_s)
        v_scale = cached_states.value_scale.at[phys, :, off].set(v_s)
    with observe.Scope("kv_write"):
      k_pool = k_pool.at[phys, off].set(k_new.astype(k_pool.dtype))
      v_pool = v_pool.at[phys, off].set(v_new.astype(v_pool.dtype))
    new_states = NestedMap(key=k_pool, value=v_pool)
    if quantized:
      new_states.key_scale = k_scale
      new_states.value_scale = v_scale
    eligible = (self.QuantizedDecodeEligible(page_size) if quantized
                else self.BlockDecodeEligible(page_size))
    if eligible:
      if c == 1:
        ctx = block_decode.BlockDecode(
            q, k_pool, v_pool, block_tables, q_pos + in_len,
            page_size=page_size, k_scale=k_scale, v_scale=v_scale)
      else:
        ctx = block_decode.BlockPrefill(
            q, k_pool, v_pool, block_tables, q_pos, in_len,
            page_size=page_size, k_scale=k_scale, v_scale=v_scale)
    else:
      # gather-dense fallback: materialize the row's logical cache view and
      # run the einsum path (handles logit cap / dropout / prob quant).
      # Slots <= q_pos + c are by construction inside the row's live prefix
      # (owned pages); everything past is stale/foreign and masked.
      k_dense = block_decode.GatherPages(k_pool, block_tables)
      v_dense = block_decode.GatherPages(v_pool, block_tables)
      if quantized:
        k_dense = kv_quant.DequantKv(
            k_dense, block_decode.GatherScales(k_scale, block_tables))
        v_dense = kv_quant.DequantKv(
            v_dense, block_decode.GatherScales(v_scale, block_tables))
      slot = jnp.arange(t_pages * page_size)[None, None, None, :]
      mask = jnp.where(slot <= pos_i[:, None, :, None], 0.0, _NEG_INF)
      ctx, _ = self._Atten(theta, q, k_dense, v_dense, mask)
    ctx = self._Gated(theta, query_vec, ctx)
    return self._PostProj(theta, ctx), new_states

  def RaggedStep(self, theta, query_vec, cached_states: NestedMap,
                 block_tables, rows, layer=None, plan=None):
    """`RaggedMix` and then `RaggedOut`: the whole layer (a wrapping layer
    calls the two itself: TransformerAttentionLayer)."""
    ctx, new_states = self.RaggedMix(theta, query_vec, cached_states,
                                     block_tables, rows, layer=layer,
                                     plan=plan)
    return self.RaggedOut(theta, ctx), new_states

  def RaggedOut(self, theta, ctx):
    """What follows the attend, row by row: ctx [1, n, N, H] -> [1, n, D]."""
    with observe.Scope("out_proj"):
      return self._PostProj(theta, ctx)

  def RaggedMix(self, theta, query_vec, cached_states: NestedMap,
                block_tables, rows, layer=None, plan=None):
    """One PACKED continuous-batching step (core/ragged.py RaggedRows), up to
    the attend's output: -> (ctx [1, T, N, H], updated states).

    query_vec: [1, T, D] — all rows' tokens flattened on one token axis;
    token t belongs to slot rows.row_of[t] and lands at global kv slot
    rows.pos[t] through that row's block table. Decode rows contribute one
    token, prefill chunks and spec-verify windows several — the single
    program the engine compiles instead of three (decode / mixed /
    verify). Padding tokens (rows.valid == False) write no K or V (an int8
    pool's scales alone scatter a token, padding's to the trash page) and
    emit garbage the engine discards. Same numerics per token as PagedStep:
    the ragged op twins
    (ops/ragged_block_attend.py) carry the bitwise proof at the op level.

    The page write is by RUNS (ops/run_write.py): a row's tokens of the
    step are one contiguous span of the packed axis bound for consecutive
    slots, so they move a page they touch at a time, as a few copies of
    static widths (a decode row one token, a whole page one copy), K and V
    in one call whose program does not depend on T or on the count of runs.
    The step's list is the plan's (`plan.runs`); the layer adds its table's
    lookup and its pool's page base.

    block_tables: [B, t_pages]. A window layer's table may hold stale
    entries behind a row's window, which no query block reaches.

    layer: None when the pool is this layer's alone ([NP, P, N, H]); a
    scalar index when every leaf arrives stacked over a repeat axis
    ([L, NP, ...], RepeatedTransformerLayer's scan carry). The stack is
    then read and written as ONE pool of L * NP pages with this layer's at
    page base layer * NP, so no op slices or re-assembles a layer's pool.

    plan: the step's RaggedPlan (a stack builds it once, before its scan
    over layers); None: the layer derives its token view and its runs here
    and the kernel's call its descriptors.

    The projections run over every row of the pack, whatever the step
    holds (`relaid_weights`); the write and the attend run the step's runs
    and pairs.
    """
    from lingvo_tpu.ops import block_decode
    from lingvo_tpu.ops import ragged_block_attend
    from lingvo_tpu.ops import run_write
    p = self.p
    assert p.rel_pos_emb_dim <= 0, (
        "RaggedStep computes positions from rows.pos; the T5 relative "
        "bias would use wrong buckets")
    np_total, page_size = cached_states.key.shape[-4:-2]  # this layer's pages
    base = 0
    if layer is not None:
      # [L, NP, ...] -> [L * NP, ...] (a bitcast, undone on the way out):
      # this layer's pages are [base, base + np_total) of the flat pool
      num_layers = cached_states.key.shape[0]
      base = jnp.asarray(layer, jnp.int32) * np_total
      cached_states = cached_states.Transform(
          lambda x: x.reshape((-1,) + x.shape[2:]))
    k_pool, v_pool = cached_states.key, cached_states.value
    valid = rows.valid
    tokens = (plan.tokens if plan is not None else ragged.BuildTokenView(
        rows, *block_tables.shape, page_size))
    row, q_start = tokens.row, tokens.q_start                      # [T]
    # Tree rows decouple the KV SLOT (pos, DFS-ordered, collision-free)
    # from the LOGICAL position (pos_ids = q_pos + depth) a token embeds
    # at; on chain rows pos_ids == pos bitwise.
    rot_pos = rows.pos_ids.astype(jnp.int32)
    with observe.Scope("qkv_proj"):
      q = self._HeadsProj(theta, "query", query_vec)               # [1,T,N,H]
      k_new = self._HeadsProj(theta, "key", query_vec)
      v_new = self._HeadsProj(theta, "value", query_vec)
    q, k_new = self._QkNorm(theta, q, k_new)
    with observe.Scope("rope"):
      if p.use_rotary_position_emb:
        rt = self.ChildTheta(theta, "rotary")
        posf = rot_pos[None].astype(jnp.float32)
        q = self.rotary.FProp(rt, q, position=posf)
        k_new = self.rotary.FProp(rt, k_new, position=posf)
      q = self._ScaleQuery(theta, q)
    # each row's new K/V land through ITS row's block table before the read
    # (later tokens of the same prefill chunk attend to earlier ones), by
    # RUNS (ops/run_write.py): a row's tokens of the step are one span of
    # the packed axis bound for consecutive slots, cut where a page ends, and
    # a run moves as a few copies. Padding tokens are in no run and land
    # nowhere.
    # A table entry is clipped to the layer's range BEFORE the base is
    # added, so no write and no read can reach another layer's pages
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
    runs = (plan.runs if plan is not None else run_write.BuildWriteRuns(
        rows, *block_tables.shape, page_size))
    eligible = self._RaggedEligible(cached_states)
    quantized = "key_scale" in cached_states
    k_scale = v_scale = None
    if quantized:
      k_new, k_s = kv_quant.QuantizeKv(k_new)              # int8, [1,T,N]
      v_new, v_s = kv_quant.QuantizeKv(v_new)
      # the scales' sidecar [NP, N, P] has a token on its lanes, where no
      # run is contiguous: a scatter a token, padding to the trash page
      # (this layer's page np_total - 1)
      phys = jnp.where(valid, tables[row, tokens.logical],
                       np_total - 1) + base                        # [T]
      with observe.Scope("kv_write"):
        k_scale = cached_states.key_scale.at[phys, :, tokens.off].set(k_s[0])
        v_scale = cached_states.value_scale.at[phys, :, tokens.off].set(
            v_s[0])
    with observe.Scope("kv_write"):
      # a token's K and V as the pool's row holds them: a KV head a row, or
      # the heads it lays side by side (the same numbers in the same order)
      k_pool, v_pool = run_write.WriteRuns(
          k_pool, v_pool,
          k_new[0].astype(k_pool.dtype).reshape((-1,) + k_pool.shape[2:]),
          v_new[0].astype(v_pool.dtype).reshape((-1,) + v_pool.shape[2:]),
          tables[runs.row, runs.logical] + base, runs)
    tables = tables + base
    new_states = NestedMap(key=k_pool, value=v_pool)
    if quantized:
      new_states.key_scale = k_scale
      new_states.value_scale = v_scale
    if layer is not None:
      new_states = new_states.Transform(
          lambda x: x.reshape((num_layers, -1) + x.shape[1:]))
    if eligible:
      # token t attends over its row's slots [0, pos[t]]; q_end = 0 marks
      # padding (the ragged op emits exact zeros there)
      with observe.Scope("ragged_attend"):
        ctx = ragged_block_attend.RaggedAttend(
            q[0], k_pool, v_pool, tables, row, tokens.q_end,
            page_size=page_size, k_scale=k_scale, v_scale=v_scale,
            q_start=q_start, anc_lo=rows.anc_lo, anc_hi=rows.anc_hi,
            window=p.window,
            plan=None if plan is None else plan.blocks)[None]
    else:
      self._RequirePlainKv("RaggedStep's gather-dense fallback")
      # gather-dense fallback at token granularity: each token is a batch
      # row of one query over its row's materialized cache view (handles
      # logit cap / dropout / prob quant exactly like PagedStep's)
      k_dense = block_decode.GatherPages(k_pool, tables)
      v_dense = block_decode.GatherPages(v_pool, tables)
      k_dense, v_dense = (x.reshape(x.shape[:-2] + k_new.shape[2:])
                          for x in (k_dense, v_dense))
      if quantized:
        k_dense = kv_quant.DequantKv(
            k_dense, block_decode.GatherScales(k_scale, tables))
        v_dense = kv_quant.DequantKv(
            v_dense, block_decode.GatherScales(v_scale, tables))
      slot = jnp.arange(
          block_tables.shape[1] * page_size)[None, None, None, :]
      # padding tokens see slot 0 only (garbage, but never an all-masked
      # softmax row)
      horizon = jnp.where(valid, rows.pos.astype(jnp.int32), 0)
      ok = ragged_block_attend._AncestorOk(
          slot, slot - q_start[:, None, None, None],
          rows.anc_lo[:, None, None, None], rows.anc_hi[:, None, None, None])
      # padding tokens keep their slot-0 escape hatch regardless of mask
      ok = ok | ~valid[:, None, None, None]
      mask = jnp.where(
          (slot <= horizon[:, None, None, None]) & ok, 0.0, _NEG_INF)
      ctx, _ = self._Atten(theta, q[0][:, None], k_dense[row],
                           v_dense[row], mask)
      ctx = ctx[:, 0][None]
    # the gate's product runs over the whole pack beside the other
    # projections (`relaid_weights`), so the gated context is what leaves
    return self._Gated(theta, query_vec, ctx), new_states


class LocalSelfAttention(MultiHeadedAttention):
  """Blocked sliding-window self-attention (ref
  `batch_major_attention.py:2656`).

  Each block of W queries attends to keys in [left_context, right_context]
  around it, materializing only [B, #blocks, W, (prev+cur+next)*W] logits —
  O(T*W) memory instead of O(T^2). Requires left/right context <= block_size.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("block_size", 64, "Query block width W.")
    p.Define("left_context", 64,
             "How many past positions each query sees (incl. itself - 1).")
    p.Define("right_context", 0, "Future positions visible (0 = causal).")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.left_context <= p.block_size + 1, "left_context > block_size+1"
    assert p.right_context <= p.block_size, "right_context > block_size"

  def _AddRelPositionBias(self, theta, qb, kb, rel, logits):
    """Hook for relative-position logit bias (LocalSelfAttentionXL).

    qb: [B, L, W, N, H] (query pre-scaled); kb: [B, L, 3W, N, H];
    rel: [W, 3W] int relative positions; logits: [B, L, N, W, 3W].
    """
    del qb, kb, rel
    return logits

  def FProp(self, theta, query_vec, key_vec=None, value_vec=None,
            paddings=None, atten_mask=None, segment_ids=None, causal=False):
    p = self.p
    del key_vec, value_vec  # self-attention only
    # causality is inherent to the window config (right_context=0); the
    # kwarg exists for signature compatibility with the base class.
    del causal
    if atten_mask is not None:
      raise NotImplementedError(
          "LocalSelfAttention cannot apply a dense [T, T] atten_mask to its "
          "windowed logits; use segment_ids (packed inputs) or paddings.")
    self._RequireNoNormNoGate("LocalSelfAttention")
    b, t, d = query_vec.shape
    w = p.block_size
    num_blocks = -(-t // w)
    pad_t = num_blocks * w - t
    x = jnp.pad(query_vec, ((0, 0), (0, pad_t), (0, 0)))
    pads = jnp.ones((b, num_blocks * w), jnp.float32)
    if paddings is None:
      pads = pads.at[:, :t].set(0.0)
    else:
      pads = pads.at[:, :t].set(paddings)

    q = self._HeadsProj(theta, "query", x)
    k = self._HeadsProj(theta, "key", x)
    v = self._HeadsProj(theta, "value", x)
    if p.use_rotary_position_emb:
      rt = self.ChildTheta(theta, "rotary")
      q = self.rotary.FProp(rt, q)
      k = self.rotary.FProp(rt, k)
    q = self._ScaleQuery(theta, q)
    n, h = p.num_heads, self._dim_per_head

    def _Blocked(arr):
      return arr.reshape(b, num_blocks, w, n, h)

    def _WithNeighbors(arr):
      """[B, nb, 3W, N, H]: prev | cur | next blocks as key context."""
      blocked = _Blocked(arr)
      prev = jnp.pad(blocked, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
      nxt = jnp.pad(blocked, ((0, 0), (0, 1), (0, 0), (0, 0), (0, 0)))[:, 1:]
      return jnp.concatenate([prev, blocked, nxt], axis=2)

    qb = _Blocked(q)
    kb = _WithNeighbors(k)
    vb = _WithNeighbors(v)
    logits = jnp.einsum("BLQNH,BLKNH->BLNQK", qb, kb).astype(jnp.float32)

    # Relative position of key col to query row within the 3W context:
    # key absolute offset = col - W + block_start; query = row + block_start.
    rel = (jnp.arange(3 * w)[None, :] - w) - jnp.arange(w)[:, None]
    logits = self._AddRelPositionBias(theta, qb, kb, rel, logits)
    visible = (rel >= -p.left_context + 1) & (rel <= p.right_context)
    logits = jnp.where(visible[None, None, None, :, :], logits, _NEG_INF)

    # key paddings within each 3W window
    pads_blocked = pads.reshape(b, num_blocks, w)
    pads_prev = jnp.pad(pads_blocked, ((0, 0), (1, 0), (0, 0)),
                        constant_values=1.0)[:, :-1]
    pads_next = jnp.pad(pads_blocked, ((0, 0), (0, 1), (0, 0)),
                        constant_values=1.0)[:, 1:]
    kpads = jnp.concatenate([pads_prev, pads_blocked, pads_next], axis=2)
    logits = logits + (kpads[:, :, None, None, :] * _NEG_INF)
    if segment_ids is not None:
      # Packed inputs: queries must not see keys of a different segment even
      # inside the window. Padded positions get segment -1 (matches nothing
      # unpadded; padding is masked above anyway).
      seg = jnp.pad(segment_ids.astype(jnp.int32), ((0, 0), (0, pad_t)),
                    constant_values=-1)
      seg_blocked = seg.reshape(b, num_blocks, w)
      seg_prev = jnp.pad(seg_blocked, ((0, 0), (1, 0), (0, 0)),
                         constant_values=-1)[:, :-1]
      seg_next = jnp.pad(seg_blocked, ((0, 0), (0, 1), (0, 0)),
                         constant_values=-1)[:, 1:]
      kseg = jnp.concatenate([seg_prev, seg_blocked, seg_next], axis=2)
      same = seg_blocked[:, :, :, None] == kseg[:, :, None, :]  # [B,L,Q,K]
      logits = jnp.where(same[:, :, None, :, :], logits, _NEG_INF)
    logits = jnp.maximum(logits, _NEG_INF)

    probs = self._QProbs(theta, jax.nn.softmax(logits, axis=-1).astype(
        q.dtype))
    if p.atten_dropout_prob > 0:
      probs = self.atten_dropout.FProp(
          self.ChildTheta(theta, "atten_dropout"), probs,
          keep_prob=1.0 - p.atten_dropout_prob)
    ctx = jnp.einsum("BLNQK,BLKNH->BLQNH", probs, vb)
    ctx = ctx.reshape(b, num_blocks * w, n, h)[:, :t]
    out = self._PostProj(theta, ctx)
    if paddings is not None:
      out = py_utils.ApplyPadding(paddings, out)
    return out, probs


class ChunkwiseSelfAttention(MultiHeadedAttention):
  """Chunked self-attention: full attention within fixed chunks, none across
  (ref `batch_major_attention.py:4008`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("chunk_size", 64, "Chunk width.")
    p.Define("causal", True, "Causal masking within chunks.")
    return p

  def FProp(self, theta, query_vec, key_vec=None, value_vec=None,
            paddings=None, atten_mask=None, segment_ids=None, causal=False):
    p = self.p
    del causal  # governed by p.causal (within-chunk masking)
    if atten_mask is not None:
      raise NotImplementedError(
          "ChunkwiseSelfAttention cannot apply a dense [T, T] atten_mask to "
          "its chunked logits; use segment_ids (packed inputs) or paddings.")
    self._RequireNoNormNoGate("ChunkwiseSelfAttention")
    b, t, d = query_vec.shape
    c = p.chunk_size
    num_chunks = -(-t // c)
    pad_t = num_chunks * c - t
    x = jnp.pad(query_vec, ((0, 0), (0, pad_t), (0, 0)))
    pads = jnp.ones((b, num_chunks * c), jnp.float32)
    pads = pads.at[:, :t].set(
        paddings if paddings is not None else jnp.zeros((b, t)))

    q = self._HeadsProj(theta, "query", x)
    k = self._HeadsProj(theta, "key", x)
    v = self._HeadsProj(theta, "value", x)
    if p.use_rotary_position_emb:
      rt = self.ChildTheta(theta, "rotary")
      q = self.rotary.FProp(rt, q)
      k = self.rotary.FProp(rt, k)
    q = self._ScaleQuery(theta, q)
    n, h = p.num_heads, self._dim_per_head

    def _Chunked(arr):
      return arr.reshape(b, num_chunks, c, n, h)

    qc, kc, vc = _Chunked(q), _Chunked(k), _Chunked(v)
    logits = jnp.einsum("BLQNH,BLKNH->BLNQK", qc, kc).astype(jnp.float32)
    if p.causal:
      causal = jnp.tril(jnp.ones((c, c), jnp.bool_))
      logits = jnp.where(causal[None, None, None], logits, _NEG_INF)
    pads_c = pads.reshape(b, num_chunks, c)
    logits = logits + pads_c[:, :, None, None, :] * _NEG_INF
    if segment_ids is not None:
      seg = jnp.pad(segment_ids.astype(jnp.int32), ((0, 0), (0, pad_t)),
                    constant_values=-1)
      seg_c = seg.reshape(b, num_chunks, c)
      same = seg_c[:, :, :, None] == seg_c[:, :, None, :]     # [B,L,Q,K]
      logits = jnp.where(same[:, :, None, :, :], logits, _NEG_INF)
    logits = jnp.maximum(logits, _NEG_INF)
    probs = self._QProbs(theta, jax.nn.softmax(logits, -1).astype(q.dtype))
    ctx = jnp.einsum("BLNQK,BLKNH->BLQNH", probs, vc)
    ctx = ctx.reshape(b, num_chunks * c, n, h)[:, :t]
    out = self._PostProj(theta, ctx)
    if paddings is not None:
      out = py_utils.ApplyPadding(paddings, out)
    return out, probs


_PAIR_NORM_EPSILON = 1e-5       # differential attention's RMSNorm a pair


class PooledAttention(MultiHeadedAttention):
  """MultiHeadedAttention (grouped-query or not) as a page-owning mixer of
  `transformer.BlockSequence`: the layer's arithmetic, weights and kernels
  are the base class's; what changes is whose pages it reads and writes.
  Its K and V live in the stack's ONE pool (`shared.kv_pool`, [pages, P,
  num_kv_heads, H]) through its own block table, and the step's RaggedPlan
  carries its query-block descriptors, as for DifferentialAttention. It
  speaks the mixer contract (transformer.SharedStateLayer), causal, with no
  state of a slot's own. Window, rotation, head norm and output gate are the
  base class's Params: `models/lm/layers.py` builds it over everything with
  no position ('gqa') or within a window and rotated ('gqa_window')."""

  # what BlockSequence asks a mixer: it projects and caches K and V in pages
  # of its own table, through the base class's own `kv_write` (the step's
  # runs; the plan carries no whole-page write for it)
  kv_owner = True
  writes_by_plan = False

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("score_scale", None,
             "The factor on q . k before the softmax; None = the base "
             "class's (dim_per_head ** -0.5, or the learned per-dim scale).")
    return p

  @property
  def _h(self) -> int:
    return self._dim_per_head

  def _ScaleQuery(self, theta, q):
    if self.p.score_scale is None:
      return super()._ScaleQuery(theta, q)
    return q * self.p.score_scale

  def FProp(self, theta, x, shared, paddings=None, segment_ids=None,
            depth=None):
    del depth
    out, _ = super().FProp(theta, x, paddings=paddings,
                           segment_ids=segment_ids, causal=True)
    return out, shared

  def InitPagedStates(self, theta, num_slots: int) -> NestedMap:
    """Nothing a slot: its pages are the stack's one pool's."""
    del theta, num_slots
    return NestedMap()

  def PagePool(self, num_pages: int, page_size: int,
               kv_cache_dtype=None) -> NestedMap:
    """The leaves of the pool this layer's pages are of (what
    `transformer.BlockSequence` asks of a mixer that owns pages): the base
    class's K and V, in the fprop dtype."""
    RequireFloatPages(kv_cache_dtype, "PooledAttention in a BlockSequence")
    return KvPagePool(num_pages, page_size, self._num_kv_heads,
                      self._dim_per_head, self.fprop_dtype,
                      tile_heads=self._PoolTileHeads())

  def _PoolTileHeads(self) -> int:
    from lingvo_tpu.ops import ragged_block_attend
    return ragged_block_attend.TileHeads(
        self.p.num_heads, self._num_kv_heads, self._dim_per_head)

  def RaggedMix(self, theta, x, states, shared, rows, table=None, depth=0,
                plan=None):
    """x: [1, T, D] packed tokens; table: [B, t_pages], this layer's own
    -> ((ctx,), states, shared): the mixer contract's first half."""
    del depth
    ctx, pool = super().RaggedMix(theta, x, shared.kv_pool, table, rows,
                                  plan=plan)
    shared = shared.Copy()
    shared.kv_pool = pool
    return (ctx,), states, shared

  def RaggedOut(self, theta, ctx, depth=None):
    del depth
    return super().RaggedOut(theta, ctx)


class DifferentialAttention(base_layer.BaseLayer):
  """Differential attention (arXiv:2410.05258) for `transformer.
  BlockSequence`, with K and V of its own or another layer's.

  x [.., D]; `num_heads` query heads of H in pairs (2j, 2j + 1),
  `num_kv_heads` K heads of H in pairs (2i, 2i + 1) with i = j // (query
  pairs / K pairs), V read as one head of 2H a K pair, V_i = [v_2i; v_2i+1]:

      a1 = softmax(q_2j k_2i^T / sqrt(H)),  a2 = softmax(q_2j+1 k_2i+1^T / sqrt(H))
      o_j = (1 - lambda_init) * RMSNorm_2H((a1 - lambda * a2) V_i)
      out = W_o concat_j o_j
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
      lambda_init = 0.8 - 0.6 * exp(-0.3 * depth)

  both softmaxes causal, and within `window` keys (the query's own
  included) where the layer has one. No bias, no position encoding.
  `depth` is the layer's index in the whole stack, handed to every call (a
  scanned block's layers differ in nothing else).

  kv_owner False: the layer has no K and V projection, writes no page and
  reads the pages of the owning layer before it, through that layer's block
  table (`shared.kv_pool`, `table`); in a whole-sequence forward it reads
  that layer's K and V (`shared.key`, `shared.value`), which an owner with
  `export_kv` hands on.

  Serving: one pool of pages for all layers of the stack (`shared.kv_pool`,
  [pages, P, num_kv_heads, H]); an owner writes its tokens' K and V through
  its own table before it reads (`kv_write`), and the read is
  ops/diff_attend.DiffAttend.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim (set by the wrapping layer).")
    p.Define("num_heads", 0, "Query heads (an even number).")
    p.Define("num_kv_heads", 0, "K heads of dim_per_head (an even number).")
    p.Define("dim_per_head", 0, "H (0 = input_dim / num_heads).")
    p.Define("window", 0, "Keys a query sees, its own included; 0 = all.")
    p.Define("kv_owner", True, "The layer projects and caches K and V.")
    p.Define("export_kv", False,
             "An owner hands its K and V to the layers after it in a "
             "whole-sequence forward (shared.key / shared.value).")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.num_heads % 2 == 0 and p.num_kv_heads % 2 == 0
    assert (p.num_heads // 2) % (p.num_kv_heads // 2) == 0, (
        p.num_heads, p.num_kv_heads)
    d, n, nk = p.input_dim, p.num_heads, p.num_kv_heads
    self._h = h = p.dim_per_head or d // n
    init = p.params_init
    self.CreateVariable("w_query", WeightParams((d, n, h), init, p.dtype))
    if p.kv_owner:
      self.CreateVariable("w_key", WeightParams((d, nk, h), init, p.dtype))
      self.CreateVariable("w_value", WeightParams((d, nk, h), init, p.dtype))
    self.CreateVariable("w_post", WeightParams(
        (d, n // 2, 2 * h), init, p.dtype))
    for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
      self.CreateVariable(name, WeightParams(
          (h,), WeightInit.Gaussian(0.1), p.dtype))
    # (1 + scale), as layers.LayerNorm stores it
    self.CreateVariable("subln_scale", WeightParams(
        (2 * h,), WeightInit.Constant(0.0), p.dtype))

  @property
  def kv_owner(self) -> bool:
    return self.p.kv_owner

  @property
  def writes_by_plan(self) -> bool:
    """An owner writes its pages through ops/diff_attend.WritePages, which
    takes the step's `RaggedPlan.writes`."""
    return self.p.kv_owner

  def KvBytesPerToken(self, kv_cache_dtype=None) -> int:
    """Bytes one token adds to the pages this layer OWNS."""
    assert kv_cache_dtype in (None, "bfloat16"), kv_cache_dtype
    if not self.p.kv_owner:
      return 0
    return 2 * self.p.num_kv_heads * self._h * jnp.dtype(
        self.fprop_dtype).itemsize

  def KvCacheDtype(self, kv_cache_dtype=None) -> str:
    del kv_cache_dtype
    return str(jnp.dtype(self.fprop_dtype))

  def _Grouped(self):
    """The attend op's view (ops/diff_attend.py): queries of 2H over K pairs
    as heads of 2H."""
    return (self.p.num_heads, self.p.num_kv_heads // 2, 2 * self._h)

  def RaggedQueryBlock(self, page_size: int, kv_cache_dtype=None) -> int:
    from lingvo_tpu.ops import ragged_block_attend
    n, n_kv, h = self._Grouped()
    return ragged_block_attend.QueryBlock(
        n_kv, h, page_size, self.fprop_dtype, self.fprop_dtype,
        grouped=ragged_block_attend.Grouped(n, n_kv))

  def StepCounts(self, geometry: ragged.StepGeometry, layers: int) -> list:
    """The block fill; the page write (`writes_by_plan`) is the stack's count."""
    from lingvo_tpu.ops import ragged_block_attend
    del layers
    n, n_kv, _ = self._Grouped()
    return [ragged.BlockFillCount(
        self.RaggedQueryBlock(geometry.page_size, geometry.kv_cache_dtype),
        ragged_block_attend.GroupLanes(n // n_kv), n // n_kv)]

  def BlockDecodeEligible(self, page_size: int) -> bool:
    """Whether the Pallas kernel serves this layer on a TPU (else the XLA
    twin does, and the engine says 'dense')."""
    if jax.default_backend() != "tpu":
      return page_size > 0
    from lingvo_tpu.ops import diff_attend
    return diff_attend.SupportedOnTpu(page_size, self._h)

  # -- the layer's arithmetic ------------------------------------------------

  def _Lambda(self, th, depth):
    f32 = lambda v: v.astype(jnp.float32)
    init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))
    lam = (jnp.exp(jnp.sum(f32(th.lambda_q1) * f32(th.lambda_k1)))
           - jnp.exp(jnp.sum(f32(th.lambda_q2) * f32(th.lambda_k2))) + init)
    return lam, init

  def _Query(self, th, x):
    q = jnp.einsum("...d,dnh->...nh", x, th.w_query)
    return q * (self._h ** -0.5)

  def _KeyValue(self, th, x):
    return (jnp.einsum("...d,dnh->...nh", x, th.w_key),
            jnp.einsum("...d,dnh->...nh", x, th.w_value))

  def _Finish(self, th, diff, lam_init):
    """diff: (a1 - lambda a2) V, [.., pairs, 2H] -> [.., D]."""
    o = diff.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + _PAIR_NORM_EPSILON)
    o = o * (1.0 + th.subln_scale.astype(jnp.float32)) * (1.0 - lam_init)
    return jnp.einsum("...jh,djh->...d", o.astype(self.fprop_dtype), th.w_post)

  def FProp(self, theta, x, shared, paddings=None, segment_ids=None,
            depth=0):
    """x: [B, T, D] -> ([B, T, D], shared): the whole-sequence forward, with
    the [T, T] scores in memory."""
    p = self.p
    th = self.CastTheta(theta)
    b, t, _ = x.shape
    n, nk, h = p.num_heads, p.num_kv_heads, self._h
    with observe.Scope("qkv_proj"):
      q = self._Query(th, x)                                     # [B,T,N,H]
    if p.kv_owner:
      with observe.Scope("qkv_proj"):
        k, v = self._KeyValue(th, x)
      if p.export_kv:
        shared = shared.Copy()
        shared.key, shared.value = k, v
    else:
      k, v = shared.key, shared.value
    group = (n // 2) // (nk // 2)               # query pairs a K pair
    head = jnp.arange(n)
    k_of = 2 * (head // 2 // group) + head % 2  # a query head's K head
    s = jnp.einsum("btnh,bsnh->bnts", q, k[:, :, k_of]).astype(jnp.float32)
    i = jnp.arange(t)
    seen = i[None, :] <= i[:, None]
    if p.window:
      seen &= i[None, :] > i[:, None] - p.window
    seen = seen[None, None]
    if segment_ids is not None:
      seen = seen & (segment_ids[:, None, :, None]
                     == segment_ids[:, None, None, :])
    if paddings is not None:
      seen = seen & (paddings[:, None, None, :] < 0.5)
    a = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    wide = v.reshape(b, t, nk // 2, 2 * h)[:, :, head // (2 * group)]
    o = jnp.einsum("bnts,bsnh->btnh", a.astype(v.dtype), wide)
    o = o.astype(jnp.float32).reshape(b, t, n // 2, 2, 2 * h)
    lam, lam_init = self._Lambda(th, depth)
    with observe.Scope("out_proj"):
      out = self._Finish(th, o[:, :, :, 0] - lam * o[:, :, :, 1], lam_init)
    if paddings is not None:
      out = py_utils.ApplyPadding(paddings, out)
    return out, shared

  def InitPagedStates(self, theta, num_slots: int) -> NestedMap:
    """Nothing a slot: its pages are the stack's one pool's."""
    del theta, num_slots
    return NestedMap()

  def PagePool(self, num_pages: int, page_size: int,
               kv_cache_dtype=None) -> NestedMap:
    """The leaves of the pool an owner's pages are of
    (PooledAttention.PagePool): K and V heads of H, as projected."""
    RequireFloatPages(kv_cache_dtype, "the differential attend kernel")
    return KvPagePool(num_pages, page_size, self.p.num_kv_heads, self._h,
                      self.fprop_dtype)

  def RaggedPlanKey(self, pool):
    """The ops/ragged_block_attend.PlanKey of this layer's RaggedStep over
    the stack's pool (`shared.kv_pool`)."""
    from lingvo_tpu.ops import diff_attend
    page_size = pool.key.shape[1]
    return diff_attend.DiffPlanKey(
        self.p.num_heads, self.p.num_kv_heads, self._h, page_size,
        self.fprop_dtype, pool.key.dtype, window=self.p.window,
        lowering="auto" if self.BlockDecodeEligible(page_size) else "xla")

  def RaggedMix(self, theta, x, states, shared, rows, table=None, depth=0,
                plan=None):
    """x: [1, T, D] packed tokens; table: [B, t_pages], this layer's own
    block table or, where it owns no pages, the owning layer's; plan: the
    step's RaggedPlan, or None (MultiHeadedAttention.RaggedMix)
    -> (((a1 - lambda a2) V [1, T, pairs, 2H],), states, shared). The
    projections run over the rows the step holds (ragged.OverLiveRows)."""
    from lingvo_tpu.ops import diff_attend
    p = self.p
    pool = shared.kv_pool
    np_total, page_size = pool.key.shape[:2]
    tokens = (plan.tokens if plan is not None else ragged.BuildTokenView(
        rows, *table.shape, page_size))
    tables = jnp.clip(table.astype(jnp.int32), 0, np_total - 1)

    def _Project(x):
      th = self.CastTheta(theta)
      with observe.Scope("qkv_proj"):
        q = self._Query(th, x)                                   # [n, N, H]
        return (q,) + (self._KeyValue(th, x) if p.kv_owner else ())

    q, *kv = ragged.OverLiveRows(_Project, plan, x[0], axis=0)
    lowering = "auto" if self.BlockDecodeEligible(page_size) else "xla"
    if p.kv_owner:
      # a token's K and V land through its row's table before the read
      k_new, v_new = kv
      with observe.Scope("kv_write"):
        key, value = diff_attend.WritePages(
            pool.key, pool.value, k_new, v_new, tables, rows,
            lowering=lowering, plan=None if plan is None else plan.writes)
      pool = NestedMap(key=key, value=value)
      shared = shared.Copy()
      shared.kv_pool = pool
    lam, _ = self._Lambda(self.CastTheta(theta), depth)
    with observe.Scope("diff_attend"):
      diff = diff_attend.DiffAttend(
          q, pool.key, pool.value, tables, tokens.row, tokens.q_end, lam,
          page_size=page_size, window=p.window, lowering=lowering,
          plan=None if plan is None else plan.blocks)
    return (diff[None],), states, shared

  def RaggedOut(self, theta, diff, depth=0):
    """What follows the attend, row by row: the pair norm and the output
    projection, [1, n, pairs, 2H] -> [1, n, D]."""
    th = self.CastTheta(theta)
    with observe.Scope("out_proj"):
      return self._Finish(th, diff, self._Lambda(th, depth)[1])
