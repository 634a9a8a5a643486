"""Gated state-space-duality sequence mixer with an O(1) decode state.

`GatedSSMLayer` is a drop-in alternative to `attention.MultiHeadedAttention`
behind `transformer.TransformerAttentionLayer`: same FProp signature, same
`InitStates`/`ExtendStep`/`Prefill` incremental-decode contract, same
`InitPagedStates`/`PagedStep` serving contract — so hybrid stacks decode
through GShardDecode and the continuous-batching engine unchanged. The
difference is the cache: instead of a `[B, T, N, H]` KV cache that grows
with sequence length, the decode state is a fixed `[B, N, H, S]` matrix per
sequence — O(1) in T, which is the whole point (PAPERS.md: "Compiler-First
State Space Duality and Portable O(1) Autoregressive Caching").

Per head n, the mixer is a gated linear recurrence in SSD form
(Mamba-2 / gated-linear-attention family):

    b_t = x_t W_b      [S]   write key        c_t = x_t W_c   [S] read key
    v_t = x_t W_v      [H]   value            g_t = x_t W_g   [H] gate
    a_t = exp(-softplus(x_t w_dt + b_dt) * exp(A_log))        scalar decay
    S_t = a_t S_{t-1} + v_t outer b_t                         [H, S] state
    y_t = S_t c_t + d_skip * v_t
    out_t = W_post . RMSNorm_head(y_t * silu(g_t))

Training/prefill lowers through `ops/ssd_scan.SsdScan` (chunked XLA or the
bitwise-equal Pallas twin); single-token decode is `ssd_scan.SequentialStep`
— literally the same float ops the `sequential` lowering scans over, so the
decode path and the sequential reference agree bitwise by construction.

Numerics: projections/gating run in fprop dtype-friendly f32 (scan state is
always f32 — the recurrence compounds over thousands of steps); the final
output projection casts back to fprop dtype.

Not supported (asserted, not silently wrong): cross-attention inputs,
additive `atten_mask`s, and non-causal (`causal=False`) FProp — a linear
recurrence is causal by nature.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lingvo_tpu import observe
from lingvo_tpu.core import base_layer
from lingvo_tpu.core import py_utils
from lingvo_tpu.core import ragged
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.core.py_utils import WeightInit, WeightParams
from lingvo_tpu.ops import ssd_scan


class GatedSSMLayer(base_layer.BaseLayer):
  """Gated SSD mixer; plug-compatible with MultiHeadedAttention."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim (set by the wrapping layer).")
    p.Define("hidden_dim", 0, "Total mixer hidden dim (N*H); 0 = input_dim.")
    p.Define("num_heads", 1, "Number of heads.")
    p.Define("dim_per_head", 0, "Per-head value dim H (0 = hidden/heads).")
    p.Define("state_dim", 64, "Per-head state width S (the O(1) cache is "
             "[N, H, S] floats per sequence).")
    p.Define("use_bias", True, "Bias on the value/gate/output projections.")
    p.Define("chunk_size", 64, "Scan chunk width Q for the chunked/Pallas "
             "lowerings (training + prefill).")
    p.Define(
        "scan_lowering", "auto",
        "ops/ssd_scan lowering for multi-token calls: 'auto' (Pallas on "
        "real TPU when SupportedOnTpu, chunked XLA elsewhere), 'chunked', "
        "'pallas', 'associative', or 'sequential'.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.num_heads > 0
    hidden = p.hidden_dim or p.input_dim
    self._dim_per_head = p.dim_per_head or hidden // p.num_heads
    n, h, s, d = p.num_heads, self._dim_per_head, p.state_dim, p.input_dim
    assert s > 0
    wsdm = p.weight_split_dims_mapping  # e.g. (None, 'model', None)
    wsdm2 = tuple(wsdm[:2]) if wsdm else None
    for name, width in (("v", h), ("b", s), ("c", s), ("gate", h)):
      self.CreateVariable(
          f"w_{name}",
          WeightParams((d, n, width), p.params_init, p.dtype,
                       tensor_split_dims_mapping=wsdm))
    if p.use_bias:
      for name, width in (("v", h), ("gate", h)):
        self.CreateVariable(
            f"b_{name}",
            WeightParams((n, width), WeightInit.Constant(0.0), p.dtype))
    # Input-dependent decay: a = exp(-softplus(x w_dt + b_dt) * exp(a_log)).
    # b_dt = -2 puts softplus ~0.13, i.e. a ~0.88/step at init — history
    # survives ~tens of steps; a_log tunes the per-head timescale.
    self.CreateVariable(
        "w_dt",
        WeightParams((d, n), p.params_init, p.dtype,
                     tensor_split_dims_mapping=wsdm2))
    self.CreateVariable(
        "b_dt", WeightParams((n,), WeightInit.Constant(-2.0), p.dtype))
    self.CreateVariable(
        "a_log", WeightParams((n,), WeightInit.Constant(0.0), p.dtype))
    self.CreateVariable(
        "d_skip", WeightParams((n,), WeightInit.Constant(1.0), p.dtype))
    # Per-head RMS norm on the gated scan output ((1 + scale) convention,
    # matching layers.LayerNorm).
    self.CreateVariable(
        "norm_scale",
        WeightParams((n, h), WeightInit.Constant(0.0), p.dtype))
    self.CreateVariable(
        "w_post",
        WeightParams((d, n, h), p.params_init, p.dtype,
                     tensor_split_dims_mapping=wsdm))
    if p.use_bias:
      self.CreateVariable(
          "b_post", WeightParams((d,), WeightInit.Constant(0.0), p.dtype))

  # -- projections -----------------------------------------------------------

  def _Project(self, theta, x):
    """x: [B, T, D] -> (decay_log, b, c, v, gate), all f32.

    decay_log [B, T, N]; b/c [B, T, N, S]; v/gate [B, T, N, H].
    """
    th = self.CastTheta(theta)
    v = jnp.einsum("btd,dnh->btnh", x, th.w_v)
    gate = jnp.einsum("btd,dnh->btnh", x, th.w_gate)
    if self.p.use_bias:
      v = v + th.b_v
      gate = gate + th.b_gate
    b = jnp.einsum("btd,dns->btns", x, th.w_b).astype(jnp.float32)
    c = jnp.einsum("btd,dns->btns", x, th.w_c).astype(jnp.float32)
    dt_raw = (jnp.einsum("btd,dn->btn", x, th.w_dt).astype(jnp.float32)
              + th.b_dt.astype(jnp.float32))
    rate = jnp.exp(th.a_log.astype(jnp.float32))
    decay_log = -jax.nn.softplus(dt_raw) * rate
    return decay_log, b, c, v.astype(jnp.float32), gate.astype(jnp.float32)

  def _Finish(self, theta, y, v, gate):
    """Skip + gate + per-head RMS norm + output projection.

    y/v/gate: [B, T, N, H] f32 -> [B, T, D] in fprop dtype.
    """
    th = self.CastTheta(theta)
    y = y + th.d_skip.astype(jnp.float32)[:, None] * v
    y = y * jax.nn.silu(gate)
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + 1e-6)
    y = y * (1.0 + th.norm_scale.astype(jnp.float32))
    out = jnp.einsum("btnh,dnh->btd", y.astype(self.fprop_dtype), th.w_post)
    if self.p.use_bias:
      out = out + th.b_post
    return out

  @staticmethod
  def _MaskScanInputs(decay_log, v, paddings=None, segment_ids=None):
    """Apply the ssd_scan masking contract.

    Padded steps become exact identity (decay_log = 0, v = 0); segment
    starts become resets (decay_log = RESET_LOG). Resets are applied first
    so a padded step can never resurrect cross-segment state (packed inputs
    only pad at the tail, where nothing reads the state anyway).
    """
    if segment_ids is not None:
      prev = jnp.concatenate([segment_ids[:, :1], segment_ids[:, :-1]],
                             axis=1)
      is_reset = (segment_ids != prev)[..., None]           # [B, T, 1]
      decay_log = jnp.where(is_reset, ssd_scan.RESET_LOG, decay_log)
    if paddings is not None:
      valid = (1.0 - paddings.astype(jnp.float32))          # [B, T]
      decay_log = decay_log * valid[..., None]
      v = v * valid[..., None, None]
    return decay_log, v

  # -- training / full-sequence ----------------------------------------------

  def FProp(self, theta, query_vec, key_vec=None, value_vec=None,
            paddings=None, atten_mask=None, segment_ids=None, causal=False):
    """Returns ([B, T, D] output, None) — probs slot kept for API parity."""
    if key_vec is not None or value_vec is not None:
      raise NotImplementedError(
          "GatedSSMLayer is a self-mixer; cross-attention layers must keep "
          "MultiHeadedAttention")
    if atten_mask is not None:
      raise NotImplementedError(
          "GatedSSMLayer cannot apply additive attention masks; use "
          "paddings/segment_ids")
    if not causal:
      raise ValueError(
          "GatedSSMLayer is causal by construction; bidirectional stacks "
          "(causal=False) must keep attention")
    decay_log, b, c, v, gate = self._Project(theta, query_vec)
    decay_log, v = self._MaskScanInputs(decay_log, v, paddings, segment_ids)
    y, _ = ssd_scan.SsdScan(
        decay_log, b, c, v, chunk_size=self.p.chunk_size,
        lowering=self.p.scan_lowering)
    out = self._Finish(theta, y, v, gate)
    if paddings is not None:
      out = py_utils.ApplyPadding(paddings, out)
    return out, None

  # -- incremental decode ----------------------------------------------------

  def InitStates(self, theta, batch_size: int, max_len: int) -> NestedMap:
    """O(1) decode state: [B, N, H, S] f32, independent of max_len."""
    del theta, max_len
    n, h, s = self.p.num_heads, self._dim_per_head, self.p.state_dim
    return NestedMap(
        state=jnp.zeros((batch_size, n, h, s), jnp.float32),
        time_step=jnp.zeros((), jnp.int32))

  def StateBytesPerSlot(self) -> int:
    """Decode-state bytes per sequence (f32 state matrix)."""
    return self.p.num_heads * self._dim_per_head * self.p.state_dim * 4

  def ExtendStep(self, theta, query_vec, cached_states: NestedMap,
                 paddings=None):
    """query_vec: [B, 1, D]; returns ([B, 1, D], updated states).

    Routes the recurrence through ssd_scan.SequentialStep — the exact float
    ops of the 'sequential' lowering — so an ExtendStep chain and a
    sequential-lowering FProp agree bitwise on the state trajectory.
    """
    t = cached_states.time_step
    decay_log, b, c, v, gate = self._Project(theta, query_vec)
    if paddings is not None:
      pad_t = jax.lax.dynamic_slice_in_dim(paddings, t, 1, axis=1)  # [B, 1]
      decay_log, v = self._MaskScanInputs(decay_log, v, pad_t)
    s_new, y = ssd_scan.SequentialStep(
        cached_states.state, decay_log[:, 0], b[:, 0], c[:, 0], v[:, 0])
    out = self._Finish(theta, y[:, None], v, gate)
    return out, NestedMap(state=s_new, time_step=t + 1)

  def Prefill(self, theta, query_vec, cached_states: NestedMap,
              paddings=None, live_len: int | None = None):
    """Whole-chunk state priming: [B, C, D] for slots [t, t + C).

    A prefill starting at t=0 that covers the whole sequence is bitwise
    identical to FProp (same projections, same scan, zero initial state);
    live_len is irrelevant here — the state is O(1) regardless of length.
    """
    del live_len
    t = cached_states.time_step
    c_len = query_vec.shape[1]
    decay_log, b, c, v, gate = self._Project(theta, query_vec)
    if paddings is not None:
      pad_c = jax.lax.dynamic_slice_in_dim(paddings, t, c_len, axis=1)
      decay_log, v = self._MaskScanInputs(decay_log, v, pad_c)
    y, s_new = ssd_scan.SsdScan(
        decay_log, b, c, v, s0=cached_states.state,
        chunk_size=self.p.chunk_size, lowering=self.p.scan_lowering)
    out = self._Finish(theta, y, v, gate)
    return out, NestedMap(state=s_new, time_step=t + c_len)

  # -- continuous-batching serving -------------------------------------------

  def InitPagedStates(self, theta, num_pages: int, page_size: int,
                      num_slots: int = 0,
                      kv_cache_dtype: str | None = None) -> NestedMap:
    """One fixed [N, H, S] state per engine slot — no page pool share.

    The serving engine passes num_slots = its slot count; attention layers
    ignore it and SSM layers ignore the page-pool geometry. There is no
    time_step: per-row positions ride each PagedStep call (q_pos).
    kv_cache_dtype is accepted for stack-level threading and ignored —
    quantized SSM state slots are a documented follow-on."""
    del theta, num_pages, page_size, kv_cache_dtype
    assert num_slots > 0, (
        "GatedSSMLayer.InitPagedStates needs the engine slot count "
        "(InitPagedDecodeState(..., num_slots=max_slots))")
    n, h, s = self.p.num_heads, self._dim_per_head, self.p.state_dim
    return NestedMap(state=jnp.zeros((num_slots, n, h, s), jnp.float32))

  def PagedStep(self, theta, query_vec, cached_states: NestedMap,
                block_tables, q_pos, in_len, collect_col_states: bool = False,
                col_parent=None):
    """One continuous-batching step; query_vec [B, C, D], B = engine slots.

    block_tables is ignored — the O(1) state needs no pages. Slot re-use is
    handled device-side: a row starting a fresh request arrives with
    q_pos == 0 and its state resets to zero, so stale state from an evicted
    or finished occupant can never leak (the attention analogue is the
    engine masking via block tables). Rows past in_len are identity steps.

    collect_col_states (speculative-decoding verify steps): additionally
    return the state AFTER every column as `col_states` [B, C, N, H, S], so
    the engine can roll the slot back to the last ACCEPTED column when a
    draft suffix is rejected — the snapshot-and-restore half of KV-cursor
    rollback, for state that (unlike KV pages) is destructively folded.
    The columns are advanced through ssd_scan.SequentialStep, the exact
    float ops of the C == 1 decode path, so a verify step's per-column
    state trajectory (and output) is bitwise identical to feeding the same
    tokens one step at a time — the greedy-identity bar of spec decoding.

    col_parent (tree speculation, requires collect_col_states): [B, C]
    int32 parent COLUMN of each packed column (-1 = the row's incoming
    state). A column's recurrence then starts from its parent's trajectory
    entry instead of the packed predecessor's, which is what makes sibling
    branches independent continuations of their shared ancestor. Chain
    rows ship col_parent[:, j] == j - 1, gathering exactly the value the
    plain scan carries — the trajectory stays bitwise identical.
    """
    del block_tables
    b, c_len, _ = query_vec.shape
    q_pos = q_pos.astype(jnp.int32)
    in_len = in_len.astype(jnp.int32)
    state = jnp.where((q_pos == 0)[:, None, None, None], 0.0,
                      cached_states.state)
    decay_log, b_proj, c_proj, v, gate = self._Project(theta, query_vec)
    # paddings convention: 1.0 = invalid step.
    invalid = (jnp.arange(c_len, dtype=jnp.int32)[None]
               >= in_len[:, None]).astype(jnp.float32)
    decay_log, v = self._MaskScanInputs(decay_log, v, invalid)
    if collect_col_states:
      xs = tuple(jnp.moveaxis(t, 1, 0)
                 for t in (decay_log, b_proj, c_proj, v))
      if col_parent is not None:
        parent = jnp.clip(col_parent.astype(jnp.int32), -1, c_len - 1)

        def _TreeCol(traj, xs):
          j, dl, bb, cc, vv = xs
          pj = jax.lax.dynamic_index_in_dim(parent, j, axis=1,
                                            keepdims=False)       # [B]
          s_par = jnp.take_along_axis(
              traj, jnp.clip(pj, 0, None)[:, None, None, None, None],
              axis=1)[:, 0]
          s_in = jnp.where((pj < 0)[:, None, None, None], state, s_par)
          s_next, y_t = ssd_scan.SequentialStep(s_in, dl, bb, cc, vv)
          traj = jax.lax.dynamic_update_slice_in_dim(
              traj, s_next[:, None], j, axis=1)
          return traj, y_t

        traj0 = jnp.zeros((b, c_len) + state.shape[1:], jnp.float32)
        traj, ys = jax.lax.scan(
            _TreeCol, traj0,
            (jnp.arange(c_len, dtype=jnp.int32),) + xs)
        y = jnp.moveaxis(ys, 0, 1)
        out = self._Finish(theta, y, v, gate)
        return out, NestedMap(state=traj[:, -1], col_states=traj)

      def _Col(s, xs):
        dl, bb, cc, vv = xs
        s_next, y_t = ssd_scan.SequentialStep(s, dl, bb, cc, vv)
        return s_next, (y_t, s_next)

      s_new, (ys, cols) = jax.lax.scan(_Col, state, xs)
      y = jnp.moveaxis(ys, 0, 1)
      out = self._Finish(theta, y, v, gate)
      return out, NestedMap(state=s_new,
                            col_states=jnp.moveaxis(cols, 0, 1))
    if c_len == 1:
      s_new, y = ssd_scan.SequentialStep(
          state, decay_log[:, 0], b_proj[:, 0], c_proj[:, 0], v[:, 0])
      y = y[:, None]
    else:
      y, s_new = ssd_scan.SsdScan(
          decay_log, b_proj, c_proj, v, s0=state,
          chunk_size=min(self.p.chunk_size, c_len),
          lowering=self.p.scan_lowering)
    out = self._Finish(theta, y, v, gate)
    return out, NestedMap(state=s_new)

  def RaggedStep(self, theta, query_vec, cached_states: NestedMap,
                 block_tables, rows, collect_col_states: bool = False,
                 layer=None):
    """Packed-token step (core/ragged.py RaggedRows): query_vec [1, T, D].

    The O(1) recurrence is inherently per-row, so the ragged step is the
    EXISTING PagedStep on a row view of the pack: gather each slot's chunk
    off the token axis through rows.row_cols ([B, wmax, D]), run the
    per-row-length scan (rows.row_len masks the tail as identity steps —
    including whole rows with 0 tokens this step), scatter outputs back to
    token order. rows.row_q_pos carries the slot-reuse reset trigger
    (q_pos == 0), which is why 0-token live rows ride with their true
    sequence position, never 0.

    layer: as in MultiHeadedAttention.RaggedStep — with an index the slot
    state arrives stacked [L, B, N, H, S]; this layer's part is cut out and
    written back in place (`col_states` stays this layer's own).
    """
    del block_tables
    x_rows = query_vec[0][rows.row_cols]             # [B, wmax, D]
    wmax = x_rows.shape[1]
    stack = cached_states.state
    if layer is not None:
      cached_states = NestedMap(state=jax.lax.dynamic_index_in_dim(
          stack, layer, axis=0, keepdims=False))
    out_rows, new_states = self.PagedStep(
        theta, x_rows, cached_states, None, rows.row_q_pos, rows.row_len,
        collect_col_states=collect_col_states,
        col_parent=rows.col_parent if collect_col_states else None)
    if layer is not None:
      new_states.state = jax.lax.dynamic_update_index_in_dim(
          stack, new_states.state, layer, axis=0)
    row = jnp.clip(rows.row_of.astype(jnp.int32), 0, x_rows.shape[0] - 1)
    col = jnp.clip(rows.col_of.astype(jnp.int32), 0, wmax - 1)
    return out_rows[row, col][None], new_states


_DT_MIN, _DT_MAX = 1e-3, 1e-1     # step sizes at initialisation (Mamba-1)


def _InverseSoftplus(x):
  return x + jnp.log(-jnp.expm1(-x))


def _PackedConv(u32, held_tail, conv_w, rows):
  """The causal depthwise convolution's sum over the packed token axis
  (bias and activation are the caller's). u32: [T, C] f32, this step's
  inputs; held_tail: [B, K - 1, C], every slot's last K - 1 inputs; conv_w:
  [K, C]. -> sum [T, C] f32, in two parts:

  - the step's own tokens: `w[K-1-back] * u32[t - back]` where the token's
    row has that token (`col >= back`), zero where it has not: elementwise
    over shifted views of the one operand, which XLA fuses into one pass;
  - the tails: a token reads its slot's tail only while `col < K - 1`, at
    most K - 1 tokens a row. Their share, `sum_{back > col} w[K-1-back] *
    tail[row, K-1-back+col]`, is a dense [K - 1, B, C] expression over
    `_FreshTail`, laid at those tokens' places (`rows.row_cols`) by a one-hot
    product [T, (K - 1) B] x [(K - 1) B, C] that XLA fuses into the same
    pass (exact in f32: one term a token, at the highest precision). No array
    of T rows is gathered from the tails. The tokens it reaches are the
    engine's `conv_tail_tokens` (the layers' `StepCounts`).

  A token reads tokens BEFORE it on the packed axis and its row's tail alone,
  so the sum over a prefix of the axis (u32, `rows.row_of` and `rows.col_of`
  cut to it; the per-row fields whole: a tail's place beyond the prefix
  meets no token) is that prefix of the sum."""
  k = conv_w.shape[0]
  t = u32.shape[0]
  w = conv_w.astype(jnp.float32)
  has = rows.col_of.astype(jnp.int32)[:, None] >= np.arange(1, k)  # [T, K-1]
  conv = w[k - 1] * u32
  for back in range(1, k):
    # the operand moved down by `back` rows: one `lax.pad`, the far edge cut
    here = jax.lax.pad(u32, np.float32(0), ((back, -back, 0), (0, 0, 0)))
    conv += jnp.where(has[:, back - 1:back], w[k - 1 - back] * here, 0.0)
  # the token at column j of a row reads its tail's entries j .. K - 2: the
  # shares column by column, [n, B, C], over the tails laid entry by entry
  n = min(k - 1, rows.row_cols.shape[1])
  tail = jnp.pad(jnp.swapaxes(_FreshTail(held_tail, rows), 0, 1),
                 ((0, k - 2), (0, 0), (0, 0)))                  # [2K-3, B, C]
  share = w[0] * tail[:n]
  for i in range(1, k - 1):
    share += w[i] * tail[i:i + n]
  # zero where no token reads it: a slot the step does not hold may keep
  # anything, and its place in `row_cols` is then some token's (+ 0.0)
  reads = np.arange(n)[:, None] < rows.row_len.astype(jnp.int32)[None]
  share = jnp.where(reads[..., None], share, 0.0)
  at = rows.row_cols[:, :n].astype(jnp.int32).T                 # [n, B]
  hot = np.arange(t, dtype=np.int32)[:, None] == at.reshape(1, -1)
  return conv + jnp.dot(hot.astype(jnp.float32),
                        share.reshape(-1, share.shape[-1]),
                        precision=jax.lax.Precision.HIGHEST)


def _ConvTailTokens(k: int, layers: int) -> ragged.StepCount:
  """The engine's counter of what `_PackedConv`'s second part reaches: the
  tokens of a step that read their slot's tail (a row's first K - 1), times
  the layers; over the step's live tokens, the share of the operand the
  tails' path touches."""
  return ragged.StepCount(("conv_tail_tokens",), lambda row_q_pos, row_len: (
      layers * int(np.minimum(row_len, k - 1).sum()),), True)


def _FreshTail(held_tail, rows):
  """Every slot's last K - 1 inputs as the step reads them: zero where a row
  starts a request."""
  return jnp.where((rows.row_q_pos == 0)[:, None, None], 0.0, held_tail)


def _PackedConvTail(u, tail, rows):
  """Every row's last K - 1 inputs after the step: the tail `_PackedConv`
  read and this step's tokens u [T, C] together. -> [B, K - 1, C] f32. A row
  of n < K - 1 tokens keeps its tail's last K - 1 - n entries, moved up by n:
  a select between the tail's K - 1 shifts, no gather along its own axis."""
  k = tail.shape[1] + 1
  t = u.shape[0]
  n = rows.row_len.astype(jnp.int32)[:, None]                    # [B, 1]
  at = n - (k - 1) + np.arange(k - 1, dtype=np.int32)[None]      # in the row
  cols = jnp.take_along_axis(
      rows.row_cols, jnp.clip(at, 0, rows.row_cols.shape[1] - 1), axis=1)
  old = tail
  for moved in range(1, k - 1):
    up = jax.lax.pad(tail, np.zeros((), tail.dtype),
                     ((0, 0, 0), (-moved, moved, 0), (0, 0, 0)))
    old = jnp.where((n == moved)[..., None], up, old)
  return jnp.where((at >= 0)[..., None],
                   u[jnp.clip(cols, 0, t - 1)].astype(jnp.float32), old)


class Mamba1Layer(base_layer.BaseLayer):
  """Mamba-1 mixer (arXiv:2312.00752) for `transformer.BlockSequence`.

  x [.., D], E = expand * D channels, N state indices, R the step size's
  rank, K the convolution's width:

      [u; z] = x W_in                                       D -> 2E
      c_t = silu(b_conv + sum_{k<K} w_conv[k] * u_{t-K+1+k})  depthwise, causal
      [r; B; C] = c W_x                                     E -> R + 2N
      delta = softplus(r W_dt + b_dt)                       [E]
      s_t = exp(delta_t (x) A) * s_{t-1} + (delta_t * c_t) (x) B_t,
            A = -exp(a_log)                                 [N, E], f32
      y_t = s_t C_t + d_skip * c_t
      out = (y_t * silu(z_t)) W_out

  What a sequence carries from token to token is `s` and the convolution's
  last K - 1 inputs `u`. Serving keeps both a slot (`InitPagedStates`:
  `scan` [slots, N, E] and `conv` [slots, K - 1, E], f32), zeroes them where
  a row starts a request (`rows.row_q_pos == 0`) and carries them from one
  chunk of a prompt to the next; they are leaves of the engine's states, so
  the engine's slot gather / scatter (spill, restore) moves them with the
  rest. The scan runs on the packed token axis (ops/selective_scan.py).

  export_memory: the layer also hands `y_t` (before the gate) to the layers
  after it, as `shared.memory` (a gated memory unit multiplies by it).

  The published initialisation, so that state neither dies in ten tokens
  nor saturates: A = -(1 .. N) on every channel, step sizes log-uniform in
  [0.001, 0.1] (`b_dt` their inverse softplus), d_skip = 1.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim (set by the wrapping layer).")
    p.Define("expand", 2, "Channels E over the model dim.")
    p.Define("state_dim", 16, "State indices N a channel.")
    p.Define("conv_width", 4, "Taps K of the causal depthwise convolution.")
    p.Define("dt_rank", 0, "Rank R of the step size (0 = ceil(D / 16)).")
    p.Define("export_memory", False,
             "Hand y_t (before the gate) on as shared.memory.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.conv_width > 1
    d = p.input_dim
    self._e = e = p.expand * d
    self._r = r = p.dt_rank or -(-d // 16)
    n, k = p.state_dim, p.conv_width
    init = p.params_init
    self.CreateVariable("w_in", WeightParams((d, 2 * e), init, p.dtype))
    self.CreateVariable("conv_w", WeightParams(
        (k, e), WeightInit.Uniform(k ** -0.5), p.dtype))
    self.CreateVariable("conv_b", WeightParams(
        (e,), WeightInit.Constant(0.0), p.dtype))
    self.CreateVariable("w_x", WeightParams((e, r + 2 * n), init, p.dtype))
    self.CreateVariable("w_dt", WeightParams(
        (r, e), WeightInit.Uniform(r ** -0.5), p.dtype))
    # overwritten at instantiation with the published initialisation
    self.CreateVariable("b_dt", WeightParams(
        (e,), WeightInit.Uniform(1.0), p.dtype))
    self.CreateVariable("a_log", WeightParams(
        (n, e), WeightInit.Constant(0.0), p.dtype))
    self.CreateVariable("d_skip", WeightParams(
        (e,), WeightInit.Constant(1.0), p.dtype))
    self.CreateVariable("w_out", WeightParams((e, d), init, p.dtype))

  def InstantiateVariables(self, key):
    p = self.p
    theta = super().InstantiateVariables(key)
    n = p.state_dim
    theta.a_log = jnp.broadcast_to(
        jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
        (n, self._e)).astype(p.dtype)
    # b_dt drew uniform in [-1, 1]: to a log-uniform step size, and its
    # inverse softplus
    u = 0.5 * (theta.b_dt.astype(jnp.float32) + 1.0)
    dt = jnp.exp(u * (jnp.log(_DT_MAX) - jnp.log(_DT_MIN)) + jnp.log(_DT_MIN))
    theta.b_dt = _InverseSoftplus(dt).astype(p.dtype)
    return theta

  def StateBytesPerSlot(self) -> int:
    """Scan state and convolution tail of one sequence, f32."""
    return 4 * self._e * (self.p.state_dim + self.p.conv_width - 1)

  # -- the layer's arithmetic ------------------------------------------------

  def _InProj(self, th, x):
    uz = jnp.einsum("...d,de->...e", x, th.w_in)
    return uz[..., :self._e], uz[..., self._e:]

  def _ScanInputs(self, th, conv):
    """conv: the convolution's sum [.., E] f32 (bias not yet added) ->
    (c, delta [.., E], B, C [.., N]), f32."""
    n, r = self.p.state_dim, self._r
    c = jax.nn.silu(conv + th.conv_b.astype(jnp.float32))
    proj = jnp.einsum("...e,ef->...f", c.astype(self.fprop_dtype), th.w_x,
                      preferred_element_type=jnp.float32)
    dt = jnp.einsum("...r,re->...e", proj[..., :r].astype(self.fprop_dtype),
                    th.w_dt, preferred_element_type=jnp.float32)
    delta = jax.nn.softplus(dt + th.b_dt.astype(jnp.float32))
    return c, delta, proj[..., r:r + n], proj[..., r + n:]

  def _Export(self, y, shared):
    """`shared` with y (before the gate) as its memory, where the layer
    exports one."""
    if not self.p.export_memory:
      return shared
    shared = shared.Copy()
    shared.memory = y.astype(self.fprop_dtype)
    return shared

  def _Finish(self, th, y, z):
    gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(self.fprop_dtype)
    return jnp.einsum("...e,ed->...d", gated, th.w_out)

  # -- whole sequences -------------------------------------------------------

  def FProp(self, theta, x, shared, paddings=None, segment_ids=None,
            depth=None):
    """x: [B, T, D] -> ([B, T, D], shared). A plain scan over T: the
    whole-sequence forward is what tests and a reference-sized forward
    use, not a training path that was tuned."""
    del depth
    if segment_ids is not None:
      raise NotImplementedError(
          "Mamba1Layer.FProp does not reset state between packed segments")
    th = self.CastTheta(theta)
    k = self.p.conv_width
    with observe.Scope("ssm_in_proj"):
      u, z = self._InProj(th, x)
    with observe.Scope("ssm_conv"):
      u32 = u.astype(jnp.float32)
      t = x.shape[1]
      padded = jnp.pad(u32, ((0, 0), (k - 1, 0), (0, 0)))
      w = th.conv_w.astype(jnp.float32)
      conv = sum(w[i] * padded[:, i:i + t] for i in range(k))
    with observe.Scope("ssm_params"):
      c, delta, b_t, c_t = self._ScanInputs(th, conv)
    if paddings is not None:
      delta = delta * (1.0 - paddings.astype(jnp.float32))[..., None]
    a = -jnp.exp(th.a_log.astype(jnp.float32))

    def _Token(s, xs):
      d, cc, bb, rd = xs
      s = jnp.exp(d[:, None, :] * a) * s + (d * cc)[:, None, :] * bb[:, :, None]
      return s, jnp.sum(s * rd[:, :, None], axis=1)

    s0 = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
    with observe.Scope("ssm_scan"):
      _, ys = jax.lax.scan(_Token, s0, tuple(
          jnp.moveaxis(v, 1, 0) for v in (delta, c, b_t, c_t)))
    y = jnp.moveaxis(ys, 0, 1) + th.d_skip.astype(jnp.float32) * c
    shared = self._Export(y, shared)
    with observe.Scope("ssm_out_proj"):
      out = self._Finish(th, y, z)
    if paddings is not None:
      out = py_utils.ApplyPadding(paddings, out)
    return out, shared

  # -- continuous-batching serving -------------------------------------------

  def InitPagedStates(self, theta, num_slots: int) -> NestedMap:
    del theta
    assert num_slots > 0, "Mamba1Layer keeps a state a slot"
    p = self.p
    return NestedMap(
        scan=jnp.zeros((num_slots, p.state_dim, self._e), jnp.float32),
        conv=jnp.zeros((num_slots, p.conv_width - 1, self._e), jnp.float32))

  def RaggedMix(self, theta, x, states, shared, rows, table=None,
                depth=None, plan=None):
    """x: [1, T, D] packed tokens (core/ragged.RaggedRows, chains only) ->
    ((y, z [1, T, E]), new states, shared). What precedes the scan is a
    token's own or reads the tokens before it (`_PackedConv`), and runs over
    the rows the step holds (ragged.OverLiveRows, one branch)."""
    del table, depth
    from lingvo_tpu.ops import selective_scan

    def _ScanOperands(x, row_of, col_of):
      th = self.CastTheta(theta)
      with observe.Scope("ssm_in_proj"):
        u, z = self._InProj(th, x)                              # [n, E]
      with observe.Scope("ssm_conv"):
        conv = _PackedConv(u.astype(jnp.float32), states.conv, th.conv_w,
                           rows._replace(row_of=row_of, col_of=col_of))
      with observe.Scope("ssm_params"):
        return (u, z) + self._ScanInputs(th, conv)

    u, z, c, delta, b_t, c_t = ragged.OverLiveRows(
        _ScanOperands, plan, x[0], rows.row_of, rows.col_of, axis=0)
    th = self.CastTheta(theta)
    y, scan = selective_scan.SelectiveScan(
        delta, c, b_t, c_t, -jnp.exp(th.a_log.astype(jnp.float32)),
        th.d_skip, states.scan, rows)
    with observe.Scope("ssm_conv"):
      new_tail = _PackedConvTail(u, _FreshTail(states.conv, rows), rows)
    return ((y[None], z[None]), NestedMap(scan=scan, conv=new_tail),
            self._Export(y[None], shared))

  def RaggedOut(self, theta, y, z, depth=None):
    """What follows the scan, row by row: the gate and the output
    projection, [1, n, E] -> [1, n, D]."""
    del depth
    with observe.Scope("ssm_out_proj"):
      return self._Finish(self.CastTheta(theta), y, z)


class GatedMemoryUnit(base_layer.BaseLayer):
  """out = W_2(silu(W_1 x) * m_t), `m_t` the memory an earlier layer exported
  for the same token (`shared.memory`, Mamba1Layer.export_memory): the mixer
  of the cross-decoder's odd layers in arXiv:2507.06607. It keeps no state."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim (set by the wrapping layer).")
    p.Define("memory_dim", 0, "Width of the memory it gates.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.memory_dim > 0
    self.CreateVariable("w_1", WeightParams(
        (p.input_dim, p.memory_dim), p.params_init, p.dtype))
    self.CreateVariable("w_2", WeightParams(
        (p.memory_dim, p.input_dim), p.params_init, p.dtype))

  def FProp(self, theta, x, shared, paddings=None, segment_ids=None,
            depth=None):
    del paddings, segment_ids, depth
    th = self.CastTheta(theta)
    with observe.Scope("gmu"):
      gate = jax.nn.silu(jnp.einsum("...d,de->...e", x, th.w_1))
      return jnp.einsum("...e,ed->...d", gate * shared.memory, th.w_2), shared

  def InitPagedStates(self, theta, num_slots: int) -> NestedMap:
    del theta, num_slots
    return NestedMap()

  def RaggedMix(self, theta, x, states, shared, rows, table=None,
                depth=None, plan=None):
    """Nothing mixes tokens here: the whole layer is `RaggedOut`'s."""
    del theta, rows, table, depth, plan
    return (x, shared.memory), states, shared

  def RaggedOut(self, theta, x, memory, depth=None):
    return self.FProp(theta, x, NestedMap(memory=memory), depth=depth)[0]


class Mamba2Layer(base_layer.BaseLayer):
  """Mamba-2 mixer (arXiv:2405.21060, the scalar-decay SSD form with its
  convolution and gated norm as published) for `transformer.BlockSequence`.

  x [.., D]; Hm heads of P channels, E = Hm * P; G groups of N state
  indices, a group's B and C shared by its Hm / G heads; K taps;
  C = E + 2 G N channels through the convolution (x, B and C together):

      [z; xBC; dt] = x W_in                                D -> E + C + Hm
      xBC_t = silu(b_conv + sum_{k<K} w_conv[k] * xBC_{t-K+1+k})  depthwise
      [u; B; C] = xBC            u [Hm, P], B and C [G, N]
      delta_t = softplus(dt_t + dt_bias)                   [Hm]
      S_t[h] = exp(delta_t[h] A[h]) S_{t-1}[h] + delta_t[h] u_t[h] (x) B_t[g(h)]
               A = -exp(a_log), ONE decay a head           [Hm, P, N], f32
      y_t[h] = S_t[h] C_t[g(h)] + d_skip[h] u_t[h]
      y = RMSNorm_groups(y * silu(z)) * (1 + norm_scale)   the gate BEFORE the
               norm; the mean square over each of the G groups of E / G
               channels
      out = y W_out                                        E -> D

  What a sequence carries from token to token is S and the last K - 1 rows
  of the un-convolved xBC. Serving keeps both a slot (`InitPagedStates`:
  `scan` [slots, Hm, P, N] and `conv` [slots, K - 1, C], f32), zeroes them
  where a row starts a request (`rows.row_q_pos == 0`) and carries them from
  one chunk of a prompt to the next; they are leaves of the engine's states,
  so the engine's slot gather / scatter (spill, restore) moves them with the
  rest. The scan runs on the packed token axis (ops/packed_ssd_scan.py): its
  pass over the slots' states is a kernel wherever a group's Hm / G x P
  channels are whole lane tiles (`packed_ssd_scan.SupportedOnTpu`), a program
  a (slot, tile of at most 512 channels), so eight groups of 512 and ONE
  group of 8,192 run the same kernel; a program holds its [512, N] block of
  the state three times (in, hand-over, out), about 3 MB of VMEM with the
  pipeline's second buffers.

  The published initialisation, so that state neither dies nor saturates:
  A uniform in [1, 16] a head, step sizes log-uniform in [0.001, 0.1]
  (`dt_bias` their inverse softplus), d_skip = 1.
  """

  # the slot-state leaf a scanned block hands over whole, [repeats, slots,
  # Hm, P, N], with the repeat's index (`layer`): the row pass reads and
  # writes its layer's where they lie; sliced a trip and stacked back they
  # would be copied twice a layer (268 MB each at 64 slots of 8,192 x 128)
  stack_states = ("scan",)

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim (set by the wrapping layer).")
    p.Define("num_heads", 0, "Heads Hm, one decay each.")
    p.Define("head_dim", 64, "Channels P a head (E = num_heads * head_dim).")
    p.Define("num_groups", 1, "Groups G that share B and C; divides Hm.")
    p.Define("state_dim", 128, "State indices N.")
    p.Define("conv_width", 4, "Taps K of the causal depthwise convolution.")
    p.Define("norm_epsilon", 1e-5, "The gated RMSNorm's epsilon.")
    p.Define("chunk_size", 64, "Packed tokens a chunk of the serving scan.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.conv_width > 1 and p.num_heads > 0
    assert p.num_heads % p.num_groups == 0, (p.num_heads, p.num_groups)
    d, hm = p.input_dim, p.num_heads
    self._e = e = hm * p.head_dim
    self._c = c = e + 2 * p.num_groups * p.state_dim
    k = p.conv_width
    init = p.params_init
    self.CreateVariable("w_in", WeightParams((d, e + c + hm), init, p.dtype))
    self.CreateVariable("conv_w", WeightParams(
        (k, c), WeightInit.Uniform(k ** -0.5), p.dtype))
    self.CreateVariable("conv_b", WeightParams(
        (c,), WeightInit.Constant(0.0), p.dtype))
    # both overwritten at instantiation with the published initialisation
    self.CreateVariable("dt_bias", WeightParams(
        (hm,), WeightInit.Uniform(1.0), p.dtype))
    self.CreateVariable("a_log", WeightParams(
        (hm,), WeightInit.Uniform(1.0), p.dtype))
    self.CreateVariable("d_skip", WeightParams(
        (hm,), WeightInit.Constant(1.0), p.dtype))
    # (1 + scale), as layers.RmsNorm stores it
    self.CreateVariable("norm_scale", WeightParams(
        (e,), WeightInit.Constant(0.0), p.dtype))
    self.CreateVariable("w_out", WeightParams((e, d), init, p.dtype))

  def InstantiateVariables(self, key):
    p = self.p
    theta = super().InstantiateVariables(key)
    # both drew uniform in [-1, 1]: to A uniform in [1, 16], and to a
    # log-uniform step size and its inverse softplus
    u = 0.5 * (theta.a_log.astype(jnp.float32) + 1.0)
    theta.a_log = jnp.log(1.0 + 15.0 * u).astype(p.dtype)
    u = 0.5 * (theta.dt_bias.astype(jnp.float32) + 1.0)
    dt = jnp.exp(u * (jnp.log(_DT_MAX) - jnp.log(_DT_MIN)) + jnp.log(_DT_MIN))
    theta.dt_bias = _InverseSoftplus(dt).astype(p.dtype)
    return theta

  def StateBytesPerSlot(self) -> int:
    """Scan state and convolution tail of one sequence, f32."""
    p = self.p
    return 4 * (self._e * p.state_dim + (p.conv_width - 1) * self._c)

  def StepCounts(self, geometry: ragged.StepGeometry, layers: int) -> list:
    """The engine's counters (ragged.StackStepCounts): the live rows (each reads
    and writes its whole state), the one-token rows and the tokens that read a
    convolution tail, times the layers."""
    del geometry
    return [ragged.StepCount(
        ("ssd_state_rows", "ssd_narrow_rows"), lambda row_q_pos, row_len: (
            layers * int((row_len > 0).sum()),
            layers * int((row_len == 1).sum())), True),
            _ConvTailTokens(self.p.conv_width, layers)]

  # -- the layer's arithmetic ------------------------------------------------

  def _InProj(self, th, x):
    """x [.., D] -> (z [.., E], xBC [.., C], dt [.., Hm])."""
    e, c = self._e, self._c
    proj = jnp.einsum("...d,df->...f", x, th.w_in)
    return proj[..., :e], proj[..., e:e + c], proj[..., e + c:]

  def _ScanInputs(self, th, conv, dt):
    """conv: the convolution's sum [.., C] f32 (bias not yet added); dt
    [.., Hm] -> (u [.., Hm, P], delta [.., Hm], B, C [.., G, N]), f32."""
    p = self.p
    e, gn = self._e, p.num_groups * p.state_dim
    xbc = jax.nn.silu(conv + th.conv_b.astype(jnp.float32))
    lead = xbc.shape[:-1]
    u = xbc[..., :e].reshape(lead + (p.num_heads, p.head_dim))
    b = xbc[..., e:e + gn].reshape(lead + (p.num_groups, p.state_dim))
    c = xbc[..., e + gn:].reshape(lead + (p.num_groups, p.state_dim))
    delta = jax.nn.softplus(dt.astype(jnp.float32)
                            + th.dt_bias.astype(jnp.float32))
    return u, delta, b, c

  def _GateNorm(self, th, y, z):
    """y [.., Hm, P] f32, z [.., E] -> [.., E] in the fprop dtype."""
    p = self.p
    groups = p.num_groups
    lead = z.shape[:-1]
    gated = y.reshape(lead + (self._e,)) * jax.nn.silu(z.astype(jnp.float32))
    by_group = gated.reshape(lead + (groups, self._e // groups))
    ms = jnp.mean(jnp.square(by_group), axis=-1, keepdims=True)
    normed = (by_group * jax.lax.rsqrt(ms + p.norm_epsilon)).reshape(
        lead + (self._e,))
    return (normed * (1.0 + th.norm_scale.astype(jnp.float32))).astype(
        self.fprop_dtype)

  # -- whole sequences -------------------------------------------------------

  def FProp(self, theta, x, shared, paddings=None, segment_ids=None,
            depth=None):
    """x: [B, T, D] -> ([B, T, D], shared). A plain scan over T: what tests
    and a reference-sized forward use, not a training path that was tuned."""
    del depth
    if segment_ids is not None:
      raise NotImplementedError(
          "Mamba2Layer.FProp does not reset state between packed segments")
    p = self.p
    th = self.CastTheta(theta)
    k = p.conv_width
    t = x.shape[1]
    with observe.Scope("ssd_in_proj"):
      z, xbc, dt = self._InProj(th, x)
    with observe.Scope("ssd_conv"):
      padded = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
      w = th.conv_w.astype(jnp.float32)
      conv = sum(w[i] * padded[:, i:i + t] for i in range(k))
      u, delta, b_t, c_t = self._ScanInputs(th, conv, dt)
    if paddings is not None:
      delta = delta * (1.0 - paddings.astype(jnp.float32))[..., None]
    a = -jnp.exp(th.a_log.astype(jnp.float32))
    r = p.num_heads // p.num_groups

    def _Token(s, xs):
      d, uu, bb, cc = xs                    # [B,Hm] [B,Hm,P] [B,G,N] [B,G,N]
      bb, cc = jnp.repeat(bb, r, axis=1), jnp.repeat(cc, r, axis=1)
      s = (jnp.exp(d * a)[..., None, None] * s
           + (d[..., None] * uu)[..., None] * bb[:, :, None, :])
      return s, jnp.sum(s * cc[:, :, None, :], axis=-1)

    s0 = jnp.zeros((x.shape[0], p.num_heads, p.head_dim, p.state_dim),
                   jnp.float32)
    with observe.Scope("ssd_scan"):
      _, ys = jax.lax.scan(_Token, s0, tuple(
          jnp.moveaxis(v, 1, 0) for v in (delta, u, b_t, c_t)))
      y = (jnp.moveaxis(ys, 0, 1)
           + th.d_skip.astype(jnp.float32)[:, None] * u)
    with observe.Scope("ssd_gate_norm"):
      gated = self._GateNorm(th, y, z)
    with observe.Scope("ssd_out_proj"):
      out = jnp.einsum("...e,ed->...d", gated, th.w_out)
    if paddings is not None:
      out = py_utils.ApplyPadding(paddings, out)
    return out, shared

  # -- continuous-batching serving -------------------------------------------

  def InitPagedStates(self, theta, num_slots: int) -> NestedMap:
    del theta
    assert num_slots > 0, "Mamba2Layer keeps a state a slot"
    p = self.p
    return NestedMap(
        scan=jnp.zeros((num_slots, p.num_heads, p.head_dim, p.state_dim),
                       jnp.float32),
        conv=jnp.zeros((num_slots, p.conv_width - 1, self._c), jnp.float32))

  def RaggedMix(self, theta, x, states, shared, rows, table=None,
                depth=None, plan=None, layer=None):
    """x: [1, T, D] packed tokens (core/ragged.RaggedRows, chains only) ->
    ((y [1, T, Hm, P] f32, z [1, T, E]), new states, shared). What precedes
    the scan runs over the rows the step holds, as Mamba1Layer's does.
    layer: in a scanned block (`stack_states`), this layer's index in
    `states.scan` [repeats, slots, Hm, P, N]; the new `scan` is the whole
    stack."""
    del table, depth
    from lingvo_tpu.ops import packed_ssd_scan

    def _ScanOperands(x, row_of, col_of):
      th = self.CastTheta(theta)
      with observe.Scope("ssd_in_proj"):
        z, xbc, dt = self._InProj(th, x)
      with observe.Scope("ssd_conv"):
        conv = _PackedConv(xbc.astype(jnp.float32), states.conv, th.conv_w,
                           rows._replace(row_of=row_of, col_of=col_of))
        return (z, xbc) + self._ScanInputs(th, conv, dt)

    z, xbc, u, delta, b_t, c_t = ragged.OverLiveRows(
        _ScanOperands, plan, x[0], rows.row_of, rows.col_of, axis=0)
    with observe.Scope("ssd_conv"):
      new_tail = _PackedConvTail(xbc, _FreshTail(states.conv, rows), rows)
    th = self.CastTheta(theta)
    y, scan = packed_ssd_scan.PackedSsdScan(
        u, delta, -jnp.exp(th.a_log.astype(jnp.float32)), b_t, c_t,
        th.d_skip, states.scan, rows, chunk_size=self.p.chunk_size,
        layer=layer)
    return (y[None], z[None]), NestedMap(scan=scan, conv=new_tail), shared

  def RaggedOut(self, theta, y, z, depth=None):
    """What follows the scan, row by row: the gated norm and the output
    projection, -> [1, n, D]."""
    del depth
    th = self.CastTheta(theta)
    with observe.Scope("ssd_gate_norm"):
      gated = self._GateNorm(th, y, z)
    with observe.Scope("ssd_out_proj"):
      return jnp.einsum("...e,ed->...d", gated, th.w_out)


class ShortConvLayer(base_layer.BaseLayer):
  """A gated short convolution and nothing else (the LFM2 family's `conv`
  operator) for `transformer.BlockSequence`: no scan, no pages, no
  activation, no bias.

  x [.., D]; K taps over the D channels, depthwise and causal:

      [B; C; X] = x W_in                                   D -> 3 D, that order
      u = B * X                                            the gate going in
      c_t = sum_{k<K} w_conv[k] * u_{t-K+1+k}              depthwise
      y = C * c                                            the gate coming out
      out = y W_out                                        D -> D

  What a sequence carries from token to token is the last K - 1 rows of u.
  Serving keeps them a slot (`InitPagedStates`: `conv` [slots, K - 1, D],
  f32), zeroed where a row starts a request and carried from one chunk of a
  prompt to the next, exactly as the Mamba layers keep their tail: the
  packed sum and the tail are theirs (`_PackedConv`, `_PackedConvTail`,
  `_FreshTail`), with gates on both sides where they have a bias and a
  silu. The leaf is the engine's to move with the rest of a slot's state
  (spill, restore, hand-off). The gates and the sum run in f32; the two
  projections in the fprop dtype.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim (set by the wrapping layer).")
    p.Define("conv_width", 3, "Taps K of the causal depthwise convolution.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.conv_width > 1
    d, k = p.input_dim, p.conv_width
    self.CreateVariable("w_in", WeightParams((d, 3 * d), p.params_init,
                                             p.dtype))
    self.CreateVariable("conv_w", WeightParams(
        (k, d), WeightInit.Uniform(k ** -0.5), p.dtype))
    self.CreateVariable("w_out", WeightParams((d, d), p.params_init, p.dtype))

  def StateBytesPerSlot(self) -> int:
    """The convolution tail of one sequence, f32."""
    return 4 * (self.p.conv_width - 1) * self.p.input_dim

  def StepCounts(self, geometry: ragged.StepGeometry, layers: int) -> list:
    """The engine's counters (ragged.StackStepCounts): live rows x layers,
    and the tokens that read a tail."""
    del geometry
    return [ragged.StepCount(("conv_tail_rows",), lambda row_q_pos, row_len: (
        layers * int((row_len > 0).sum()),), True),
            _ConvTailTokens(self.p.conv_width, layers)]

  def _Gates(self, th, x):
    """x [.., D] -> (u = B * X [.., D] f32, C [.., D] f32)."""
    b, c, xx = jnp.split(
        jnp.einsum("...d,df->...f", x, th.w_in).astype(jnp.float32), 3,
        axis=-1)
    return b * xx, c

  def FProp(self, theta, x, shared, paddings=None, segment_ids=None,
            depth=None):
    """x: [B, T, D] -> ([B, T, D], shared)."""
    del depth
    if segment_ids is not None:
      raise NotImplementedError(
          "ShortConvLayer.FProp does not cut its taps between packed segments")
    th = self.CastTheta(theta)
    k, t = self.p.conv_width, x.shape[1]
    with observe.Scope("short_conv"):
      u, c = self._Gates(th, x)
      if paddings is not None:
        u = u * (1.0 - paddings.astype(jnp.float32))[..., None]
      with observe.Scope("short_conv_taps"):
        padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
        w = th.conv_w.astype(jnp.float32)
        conv = sum(w[i] * padded[:, i:i + t] for i in range(k))
      out = jnp.einsum("...e,ed->...d", (c * conv).astype(self.fprop_dtype),
                       th.w_out)
    if paddings is not None:
      out = py_utils.ApplyPadding(paddings, out)
    return out, shared

  # -- continuous-batching serving -------------------------------------------

  def InitPagedStates(self, theta, num_slots: int) -> NestedMap:
    del theta
    assert num_slots > 0, "ShortConvLayer keeps a tail a slot"
    p = self.p
    return NestedMap(conv=jnp.zeros(
        (num_slots, p.conv_width - 1, p.input_dim), jnp.float32))

  def RaggedMix(self, theta, x, states, shared, rows, table=None,
                depth=None, plan=None):
    """x: [1, T, D] packed tokens (core/ragged.RaggedRows, chains only) ->
    ((y [1, T, D],), new states, shared): everything up to the output
    projection, over the rows the step holds."""
    del table, depth

    def _Gated(x, row_of, col_of):
      th = self.CastTheta(theta)
      u, c = self._Gates(th, x)
      with observe.Scope("short_conv_taps"):
        conv = _PackedConv(u, states.conv, th.conv_w,
                           rows._replace(row_of=row_of, col_of=col_of))
      return u, (c * conv).astype(self.fprop_dtype)

    with observe.Scope("short_conv"):
      u, y = ragged.OverLiveRows(_Gated, plan, x[0], rows.row_of,
                                 rows.col_of, axis=0)
      with observe.Scope("short_conv_taps"):
        new_tail = _PackedConvTail(u, _FreshTail(states.conv, rows), rows)
    return (y[None],), NestedMap(conv=new_tail), shared

  def RaggedOut(self, theta, y, depth=None):
    """What follows the taps, row by row: the output projection."""
    del depth
    with observe.Scope("short_conv"):
      return jnp.einsum("...e,ed->...d", y, self.CastTheta(theta).w_out)
