"""Multi-head latent attention (MLA), DeepSeek-V3's published form: queries
and keys/values through low-rank latents, a rotary part shared by all heads,
and a paged cache that keeps ONE latent row a token instead of K and V by
heads.

With `x` the layer's normed input and heads `i` = 1..N:

  c_q = RMSNorm(x W_qa)                        [q_lora_rank]
  [q_nope_i | q_rope_i] = c_q W_qb,i           [nope | rope]
  [c_kv | k_r] = x W_kva,  c_kv <- RMSNorm(c_kv)   [kv_lora_rank | rope]
  q_rope_i, k_r rotated (interleaved pairs, yarn frequencies; ONE k_r for
  all heads)
  [k_nope_i | v_i] = c_kv W_kvb,i              [nope | v_head_dim]
  s_i(t, s) = a(t) scale (q_nope_i(t) . k_nope_i(s) + q_rope_i(t) . k_r(s))
  o_i = sum_s softmax_s(s_i) v_i(s),  y = [o_1 .. o_N] W_o

`scale = (nope + rope)^-0.5 m^2`, `m = 0.1 mscale_all_dim ln(factor) + 1`
(1 without yarn); `a(t) = 1 + llama_4_scaling_beta ln(1 + floor(t /
original_max_position))` on the query. No bias anywhere.

`FProp` computes that (the EXPANDED form: K and V by heads rebuilt from the
latent rows), for training and as the served form's twin. The serving step
(`RaggedMix` / `RaggedOut`) runs the ABSORBED form, which is the same
mathematics with `W_kvb` moved across the attend:

  q_lat_i = q_nope_i (W_kvb,i^K)^T             [kv_lora_rank]
  s_i = a scale (q_lat_i . c_kv(s) + q_rope_i . k_r(s))
  ctx_i = sum_s p_i c_kv(s)                    [kv_lora_rank]
  o_i = ctx_i W_kvb,i^V

so N query heads attend over one row a token, `[c_kv | k_r]` after the norm
and the rotation, whose first `kv_lora_rank` values are also the value
(ops/latent_attend.py). That row is what the page pool keeps: one leaf
`latent` `[pages, page, row]`. Where `kv_lora_rank` fills whole 128-lane
tiles the row is STORED padded to whole tiles (320 -> 384, zeros behind it:
the chip lays a 320-wide array out as 384 lanes anyway, and Mosaic copies
whole tiles only), a layout as core/moe._StoredWidth is: every product is
the same to the bit.

The serving engine's contracts: `InitPagedStates` / `PagePool` declare the
leaf, `KvBytesPerToken` prices it as stored, `RaggedPlanKey` names the
attend's descriptors in the step's plan, the page write is by the step's
runs (ops/run_write.WriteRowRuns). What this mixer does not have it says by name: int8
pages, a window, and the dense decode contracts (`ragged_only`: a draft
source's pass runs PagedStep).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from lingvo_tpu import observe
from lingvo_tpu.core import base_layer
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import ragged
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.core.py_utils import WeightParams

_LANES = 128


def StoredRow(kv_lora_rank: int, rope_dim: int) -> int:
  """The width a token's latent row is STORED at: whole 128-lane tiles where
  the latent itself fills whole tiles (module docstring), else as it is."""
  row = kv_lora_rank + rope_dim
  return -(-row // _LANES) * _LANES if kv_lora_rank % _LANES == 0 else row


def YarnInvFreq(dim: int, theta: float, factor: float, original_max: int,
                beta_fast: float, beta_slow: float) -> np.ndarray:
  """The `dim / 2` rotary frequencies under yarn: a pair whose wavelength
  makes more than `beta_fast` turns inside `original_max` positions keeps
  its frequency, one that makes fewer than `beta_slow` has it divided by
  `factor`, a linear ramp over the pair's index between the two. factor 1:
  the plain frequencies."""
  freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
  if factor == 1.0:
    return freq.astype(np.float32)

  def _Pair(turns):
    # the pair's index at which `turns` rotations fit the original window
    return dim * math.log(original_max / (turns * 2 * math.pi)) / (
        2 * math.log(theta))

  low = max(math.floor(_Pair(beta_fast)), 0)
  high = min(math.ceil(_Pair(beta_slow)), dim - 1)
  ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                 / max(high - low, 1e-3), 0.0, 1.0)
  return (freq / factor * ramp + freq * (1.0 - ramp)).astype(np.float32)


def RotateInterleaved(x, pos, inv_freq):
  """x [..., R] with pairs (x[2j], x[2j + 1]), pos broadcastable to x's
  leading dimensions -> x with pair j turned by pos * inv_freq[j], in f32,
  cast back."""
  ang = jnp.asarray(pos, jnp.float32)[..., None] * inv_freq
  cos, sin = jnp.cos(ang), jnp.sin(ang)
  x32 = x.astype(jnp.float32)
  even, odd = x32[..., 0::2], x32[..., 1::2]
  out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
  return out.reshape(x.shape).astype(x.dtype)


class MultiHeadLatentAttention(base_layer.BaseLayer):
  """MLA as a `TransformerAttentionLayer` mixer (`atten_tpl`)."""

  # [.., N, H] projections, as MultiHeadedAttention's: kept outside the
  # step's conditionals on variables taken from their stacks whole
  relaid_weights = True
  # no InitStates / ExtendStep / Prefill / PagedStep: the packed step alone
  ragged_only = True

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim.")
    p.Define("hidden_dim", 0, "Unused (the wrapping layer sets it).")
    p.Define("num_heads", 1, "Query heads.")
    p.Define("dim_per_head", 0,
             "The query-key head size; 0 or qk_nope_head_dim + "
             "qk_rope_head_dim.")
    p.Define("q_lora_rank", 0, "Width of the query latent c_q.")
    p.Define("kv_lora_rank", 0, "Width of the key-value latent c_kv.")
    p.Define("qk_nope_head_dim", 0, "A head's part without position.")
    p.Define("qk_rope_head_dim", 0, "A head's rotary part (even).")
    p.Define("v_head_dim", 0, "A head's value size.")
    p.Define("norm_epsilon", 1e-6, "Epsilon of the two latent RMSNorms.")
    p.Define("use_rotary_position_emb", True, "Always: MLA rotates.")
    p.Define("rope_max_timescale", 1e4, "RoPE base (theta).")
    p.Define("rope_factor", 1.0, "Yarn's factor; 1 = plain frequencies.")
    p.Define("rope_original_max_position", 8192,
             "Yarn's original_max_position_embeddings, also the period of "
             "the query's position scale a(t).")
    p.Define("rope_beta_fast", 32.0, "Yarn's beta_fast.")
    p.Define("rope_beta_slow", 1.0, "Yarn's beta_slow.")
    p.Define("rope_mscale_all_dim", 1.0,
             "Yarn's mscale_all_dim: the softmax scale is times "
             "(0.1 * this * ln(rope_factor) + 1)^2.")
    p.Define("llama_4_scaling_beta", 0.0,
             "beta of a(t) = 1 + beta * ln(1 + floor(t / "
             "rope_original_max_position)) on the query; 0 = none.")
    p.Define("window", 0, "Must be 0: no window over a latent pool.")
    p.Define("kv_cache_dtype", None,
             "None or the fprop dtype's name: the latent pool is float.")
    p.Define("atten_dropout_prob", 0.0, "Must be 0.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    d, n = p.input_dim, p.num_heads
    self._nope, self._rope, self._v = (
        p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim)
    self._rank = p.kv_lora_rank
    assert d > 0 and n > 0 and p.q_lora_rank > 0 and self._rank > 0
    assert self._nope > 0 and self._v > 0
    assert self._rope > 0 and self._rope % 2 == 0, self._rope
    assert p.dim_per_head in (0, self._nope + self._rope), p.dim_per_head
    assert p.window == 0, "MultiHeadLatentAttention has no window"
    assert p.atten_dropout_prob == 0.0
    self._row = StoredRow(self._rank, self._rope)
    init = p.params_init
    for name, shape in (
        ("w_qa", (d, p.q_lora_rank)),
        ("w_qb", (p.q_lora_rank, n, self._nope + self._rope)),
        ("w_kva", (d, self._rank + self._rope)),
        ("w_kvb", (self._rank, n, self._nope + self._v)),
        ("w_post", (d, n, self._v))):
      self.CreateVariable(name, WeightParams(shape, init, p.dtype))
    norm = layers_lib.RmsNorm.Params().Set(epsilon=p.norm_epsilon)
    self.CreateChild("q_ln", norm.Copy().Set(input_dim=p.q_lora_rank))
    self.CreateChild("kv_ln", norm.Copy().Set(input_dim=self._rank))
    self._inv_freq = YarnInvFreq(
        self._rope, p.rope_max_timescale, p.rope_factor,
        p.rope_original_max_position, p.rope_beta_fast, p.rope_beta_slow)
    m = 0.1 * p.rope_mscale_all_dim * math.log(p.rope_factor) + 1.0
    self._scale = (self._nope + self._rope) ** -0.5 * m * m

  # -- what both forms share -------------------------------------------------

  def _QueryScale(self, pos):
    """a(t) * scale at positions `pos` (any shape), f32."""
    p = self.p
    if not p.llama_4_scaling_beta:
      return jnp.full(jnp.shape(pos), self._scale, jnp.float32)
    periods = jnp.floor(jnp.asarray(pos, jnp.float32)
                        / p.rope_original_max_position)
    return self._scale * (1.0 + p.llama_4_scaling_beta
                          * jnp.log1p(periods))

  def _Project(self, theta, x, pos):
    """x [B, T, D], pos [B, T] -> (q_nope [B, T, N, nope], q_rope [B, T, N,
    rope] rotated, c_kv [B, T, rank] normed, k_r [B, T, rope] rotated)."""
    th = self.CastTheta(theta)
    x = self.ToFPropDtype(x)
    with observe.Scope("qkv_proj"):
      c_q = self.q_ln.FProp(theta.q_ln, jnp.einsum("btd,dr->btr", x, th.w_qa))
      q = jnp.einsum("btr,rnh->btnh", c_q, th.w_qb)
      kv = jnp.einsum("btd,dr->btr", x, th.w_kva)
      c_kv = self.kv_ln.FProp(theta.kv_ln, kv[..., :self._rank])
    with observe.Scope("rope"):
      q_rope = RotateInterleaved(q[..., self._nope:], pos[..., None],
                                 self._inv_freq)
      k_r = RotateInterleaved(kv[..., self._rank:], pos, self._inv_freq)
    return q[..., :self._nope], q_rope, c_kv, k_r

  def _PostProj(self, theta, o):
    th = self.CastTheta(theta)
    return jnp.einsum("btnh,dnh->btd", o, th.w_post)

  # -- the expanded form -----------------------------------------------------

  def FProp(self, theta, query_vec, key_vec=None, value_vec=None,
            paddings=None, atten_mask=None, segment_ids=None,
            causal: bool = False):
    """Self-attention over [B, T, D] at positions 0..T-1 -> ([B, T, D],
    None): K and V by heads rebuilt from every token's latent row through
    W_kvb. paddings [B, T] / segment_ids [B, T] / atten_mask (additive,
    broadcastable to [B, N, T, T]) mask as MultiHeadedAttention's do."""
    assert key_vec is None and value_vec is None, "self-attention only"
    th = self.CastTheta(theta)
    b, t, _ = query_vec.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    q_nope, q_rope, c_kv, k_r = self._Project(theta, query_vec, pos)
    kv = jnp.einsum("bsr,rnh->bsnh", c_kv, th.w_kvb)
    k_nope, v = kv[..., :self._nope], kv[..., self._nope:]
    s = (jnp.einsum("btnh,bsnh->bnts", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("btnh,bsh->bnts", q_rope, k_r,
                      preferred_element_type=jnp.float32))
    s = s * self._QueryScale(pos)[:, None, :, None]
    keep = jnp.ones((b, 1, t, t), bool)
    if causal:
      keep &= jnp.tril(jnp.ones((t, t), bool))[None, None]
    if paddings is not None:
      keep &= (paddings < 0.5)[:, None, None, :]
    if segment_ids is not None:
      keep &= (segment_ids[:, None, :, None] == segment_ids[:, None, None, :])
    s = jnp.where(keep, s, -1e30)
    if atten_mask is not None:
      s = s + atten_mask.astype(jnp.float32)
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bnts,bsnh->btnh", probs, v)
    return self._PostProj(theta, o), None

  # -- the paged cache -------------------------------------------------------

  def _PoolDtype(self, kv_cache_dtype=None):
    name = kv_cache_dtype or self.p.kv_cache_dtype
    if name and jnp.dtype(name) != jnp.dtype(self.fprop_dtype):
      raise NotImplementedError(
          f"kv_cache_dtype {name!r}: MultiHeadLatentAttention's latent pool "
          f"is in the fprop dtype ({jnp.dtype(self.fprop_dtype).name}); the "
          "latent attend kernel reads float rows and no scale sidecar")
    return jnp.dtype(self.fprop_dtype)

  def KvCacheDtype(self, kv_cache_dtype=None) -> str:
    return str(self._PoolDtype(kv_cache_dtype))

  def KvBytesPerToken(self, kv_cache_dtype=None) -> int:
    """Bytes the pool holds a token in this layer: the row AS STORED
    (`kv_lora_rank + qk_rope_head_dim` values, padded to whole lane tiles
    where StoredRow pads)."""
    return self._row * self._PoolDtype(kv_cache_dtype).itemsize

  def PagePool(self, num_pages: int, page_size: int,
               kv_cache_dtype=None) -> NestedMap:
    """The pool's one leaf: `latent` [num_pages, page_size, stored row]."""
    return NestedMap(latent=jnp.zeros(
        (num_pages, page_size, self._row), self._PoolDtype(kv_cache_dtype)))

  def InitPagedStates(self, theta, num_pages: int, page_size: int,
                      num_slots: int = 0, kv_cache_dtype=None) -> NestedMap:
    del theta, num_slots
    return self.PagePool(num_pages, page_size, kv_cache_dtype)

  def BlockDecodeEligible(self, page_size: int) -> bool:
    """The pool is read through ops/latent_attend.py at any page size (its
    kernel on a TPU where the shapes tile, its XLA twin elsewhere): never
    the dense fallback the engine's `paged_path` warns of."""
    del page_size
    return True

  def _Lowering(self, page_size: int) -> str:
    from lingvo_tpu.ops import latent_attend
    return ("auto" if latent_attend.SupportedOnTpu(page_size, self._rank)
            else "xla")

  def RaggedPlanKey(self, cached_states):
    from lingvo_tpu.ops import latent_attend
    page_size = cached_states.latent.shape[-2]
    return latent_attend.PlanKey(self.p.num_heads, page_size,
                                 lowering=self._Lowering(page_size))

  def RaggedQueryBlock(self, page_size: int, kv_cache_dtype=None) -> int:
    from lingvo_tpu.ops import latent_attend
    del page_size, kv_cache_dtype
    return latent_attend.QueryBlock(self.p.num_heads)

  def StepCounts(self, geometry: ragged.StepGeometry, layers: int) -> list:
    """The engine's counters of this mixer's work: the block fill (a token's
    heads, padded to whole tiles) and the page write, by the step's runs."""
    from lingvo_tpu.ops import latent_attend
    del layers
    heads = self.p.num_heads
    return [ragged.BlockFillCount(self.RaggedQueryBlock(geometry.page_size),
                                  latent_attend.Lanes(heads), heads),
            ragged.RunWriteCount(geometry.page_size)]

  # -- the serving step: the absorbed form -----------------------------------

  def RaggedStep(self, theta, query_vec, cached_states, block_tables, rows,
                 layer=None, plan=None):
    ctx, new_states = self.RaggedMix(theta, query_vec, cached_states,
                                     block_tables, rows, layer=layer,
                                     plan=plan)
    return self.RaggedOut(theta, ctx), new_states

  def RaggedOut(self, theta, o):
    """o [1, T, N, v_head_dim] -> [1, T, D]."""
    with observe.Scope("out_proj"):
      return self._PostProj(theta, o)

  def RaggedMix(self, theta, query_vec, cached_states: NestedMap,
                block_tables, rows, layer=None, plan=None):
    """One packed step (core/ragged.py RaggedRows) up to the heads' outputs:
    query_vec [1, T, D] -> (o [1, T, N, v_head_dim], updated states).
    cached_states.latent [NP, P, row], or [L, NP, P, row] with `layer` this
    layer's index there: the stack is then read and written as ONE pool of
    L * NP pages, this layer's at page base layer * NP
    (MultiHeadedAttention.RaggedMix). Each token's row is written through
    its row's block table before the read; padding tokens write nothing and
    emit zeros."""
    from lingvo_tpu.ops import latent_attend
    from lingvo_tpu.ops import run_write
    th = self.CastTheta(theta)
    pool = cached_states.latent
    np_total, page_size, row_w = pool.shape[-3:]
    base = 0
    if layer is not None:
      num_layers = pool.shape[0]
      base = jnp.asarray(layer, jnp.int32) * np_total
      pool = pool.reshape((-1,) + pool.shape[2:])
    tokens = (plan.tokens if plan is not None else ragged.BuildTokenView(
        rows, *block_tables.shape, page_size))
    pos = rows.pos_ids.astype(jnp.int32)[None]                 # [1, T]
    q_nope, q_rope, c_kv, k_r = self._Project(theta, query_vec, pos)
    # the stored row's columns behind its values (StoredRow) hold zeros, in
    # the pool and in the query alike
    widen = lambda x: jnp.pad(
        x, ((0, 0),) * (x.ndim - 1) + ((0, row_w - self._rank - self._rope),))
    with observe.Scope("mla_absorb"):
      # a(t) * scale on the query's two parts (the absorption is linear), in
      # f32 over [T, N, nope + rope] and not over the widened row
      scale = self._QueryScale(pos)[..., None, None]
      q_nope, q_rope = ((x.astype(jnp.float32) * scale).astype(x.dtype)
                        for x in (q_nope, q_rope))
      q_lat = jnp.einsum("btnh,rnh->btnr", q_nope,
                         th.w_kvb[..., :self._nope])
      q = widen(jnp.concatenate([q_lat, q_rope], -1))
    new_rows = widen(jnp.concatenate([c_kv, k_r], -1))[0].astype(pool.dtype)
    # a table entry is clipped to the layer's range BEFORE the base is added
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
    runs = (plan.runs if plan is not None else run_write.BuildWriteRuns(
        rows, *block_tables.shape, page_size))
    with observe.Scope("kv_write"):
      pool = run_write.WriteRowRuns(
          pool, new_rows, tables[runs.row, runs.logical] + base, runs)
    with observe.Scope("mla_attend"):
      ctx = latent_attend.LatentAttend(
          q[0], pool, tables + base, tokens.row, tokens.q_end,
          page_size=page_size, value_dim=self._rank,
          q_start=tokens.q_start, anc_lo=rows.anc_lo, anc_hi=rows.anc_hi,
          lowering=self._Lowering(page_size),
          plan=None if plan is None else plan.blocks)[None]
    with observe.Scope("mla_absorb"):
      o = jnp.einsum("btnr,rnh->btnh", ctx, th.w_kvb[..., self._nope:])
    if layer is not None:
      pool = pool.reshape((num_layers, -1) + pool.shape[1:])
    return o, NestedMap(latent=pool)
