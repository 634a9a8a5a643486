"""Transformer layers and stacks.

Re-designs the transformer composition layer of the reference
(`batch_major_attention.py`: `TransformerAttentionLayer:5226`,
`TransformerLayer:6265`, `StackedTransformerLayers:7116`,
`RepeatedTransformerLayer:6976`).

The repeated stack is the TPU-native star: N identical layers become ONE
layer with weights stacked on a leading axis, executed with `lax.scan` —
constant compile time in depth, and under GSPMD the stacked weight's leading
axis can also serve as the pipeline stage axis (ref
`gshard_layers.LayerwiseShardablePipelinedLayer:180`; see parallel/pipeline).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from lingvo_tpu import observe
from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core import base_layer
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import py_utils
from lingvo_tpu.core import ragged
from lingvo_tpu.core.nested_map import NestedMap


class TransformerFeedForwardLayer(base_layer.BaseLayer):
  """Pre-LN FFN with residual (ref TransformerFeedForwardLayer)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim.")
    p.Define("hidden_dim", 0, "Inner dim.")
    p.Define("activation", "RELU", "Inner activation.")
    p.Define("use_gated_activation", False, "GLU-style gating (e.g. SwiGLU).")
    p.Define("residual_dropout_prob", 0.0, "Dropout on the residual add.")
    p.Define("relu_dropout_prob", 0.0, "Dropout after the inner activation.")
    p.Define("norm_tpl", layers_lib.LayerNorm.Params(), "Norm template.")
    p.Define("add_skip_connection", True, "Residual connection.")
    p.Define("residual_scale", 1.0,
             "Factor on the block's output before it is added to its input "
             "(1: none, and no op).")
    p.Define("has_bias", True, "Biases on the projections.")
    p.Define("post_norm_tpl", None,
             "A norm on the block's OUTPUT, before the residual add; None: "
             "none, no variable and no op.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.input_dim > 0 and p.hidden_dim > 0
    self.CreateChild("ln", p.norm_tpl.Copy().Set(input_dim=p.input_dim))
    if p.post_norm_tpl is not None:
      self.CreateChild("post_ln",
                       p.post_norm_tpl.Copy().Set(input_dim=p.input_dim))
    wsdm_in = p.weight_split_dims_mapping  # (None, 'model') typical
    wsdm_out = tuple(reversed(wsdm_in)) if wsdm_in else None
    self.CreateChild(
        "ffn_in",
        layers_lib.ProjectionLayer.Params().Set(
            input_dim=p.input_dim, output_dim=p.hidden_dim,
            activation="NONE", has_bias=p.has_bias,
            weight_split_dims_mapping=wsdm_in))
    if p.use_gated_activation:
      self.CreateChild(
          "ffn_gate",
          layers_lib.ProjectionLayer.Params().Set(
              input_dim=p.input_dim, output_dim=p.hidden_dim,
              activation="NONE", has_bias=p.has_bias,
              weight_split_dims_mapping=wsdm_in))
    self.CreateChild(
        "ffn_out",
        layers_lib.ProjectionLayer.Params().Set(
            input_dim=p.hidden_dim, output_dim=p.input_dim,
            activation="NONE", has_bias=p.has_bias,
            weight_split_dims_mapping=wsdm_out))
    self.CreateChild("dropout", layers_lib.DeterministicDropoutLayer.Params())

  def FProp(self, theta, inputs, paddings=None):
    p = self.p
    from lingvo_tpu.core import activations
    # named scopes: each device op's op_name in a profiler trace says which
    # block it belongs to (metadata only; docs/observability.md)
    with observe.Scope("norm"):
      x = self.ln.FProp(theta.ln, inputs)
    with observe.Scope("ffn"):
      h = self.ffn_in.FProp(theta.ffn_in, x)
      act = activations.GetFn(p.activation)
      if p.use_gated_activation:
        h = act(h) * self.ffn_gate.FProp(theta.ffn_gate, x)
      else:
        h = act(h)
      if p.relu_dropout_prob > 0:
        h = self.dropout.FProp(
            self.ChildTheta(theta, "dropout"), h,
            keep_prob=1.0 - p.relu_dropout_prob, name_suffix="relu")
      out = self.ffn_out.FProp(theta.ffn_out, h)
      if p.post_norm_tpl is not None:
        with observe.Scope("post_norm"):
          out = self.post_ln.FProp(theta.post_ln, out)
      if p.residual_dropout_prob > 0:
        out = self.dropout.FProp(
            self.ChildTheta(theta, "dropout"), out,
            keep_prob=1.0 - p.residual_dropout_prob, name_suffix="res")
      if paddings is not None:
        out = py_utils.ApplyPadding(paddings, out)
      if p.residual_scale != 1.0:
        out = p.residual_scale * out
      if p.add_skip_connection:
        out = inputs + out
    return out


def _MixThenRows(mixer, theta, plan, mix, out, residual, then, scale=1.0,
                 post=None):
  """`then(residual + scale * post(Mixer(.)))` (scale 1: no factor and no op;
  post: a norm on the branch's output, or None) in a
  serving step, the row-wise part over the
  rows the step holds. mix(theta) -> (ctx, *rest) is the mixer's
  `RaggedMix` (ctx: a tuple of `[1, T, ...]` arrays; its own projections
  branch inside it); out(theta, *ctx) -> [1, n, D] its `RaggedOut`; then: what
  the caller does next row by row (a dense feed-forward) or None. The output
  projection, the residual and `then` share ONE conditional
  (ragged.OverLiveRows). -> (x [1, T, D], *rest).

  With no `then` (a layer that is its mixer alone) nothing branches here: an
  output projection and a residual alone do not repay a conditional
  (nemotron3nano: `serve_tok_s` +10.1% with it, +9.4 to +10.2% without, and
  a Mamba-2 body's second conditional is 0.1 s of every process's set-up;
  PERF.md section 6, PR 51).

  A mixer whose `[D, N, H]` projections are re-laid for the MXU a layer at a
  time (`relaid_weights`: MultiHeadedAttention says why) keeps them outside
  the step's conditionals, its output projection too: its variables are
  taken from their stacks here (base_layer.StackSlice), as a scan's slice
  was."""
  relaid = getattr(mixer, "relaid_weights", False)
  if relaid:
    # (outside `atten`: a scan's slices count under `layer_scan`, as ever)
    theta = base_layer.TakeSlices(theta)
  with observe.Scope("atten"):
    ctx, *rest = mix(theta)
    if relaid:
      ctx, out = (out(theta, *ctx),), lambda theta, y: y

  def _Finish(residual, *ctx):
    with observe.Scope("atten"):
      branch = out(theta, *ctx)
      if post is not None:
        with observe.Scope("post_norm"):
          branch = post(branch)
      x = residual + (branch if scale == 1.0 else scale * branch)
    return x if then is None else then(x)

  if then is None:
    return (_Finish(residual, *ctx), *rest)
  return (ragged.OverLiveRows(_Finish, plan, residual, *ctx), *rest)


class TransformerAttentionLayer(base_layer.BaseLayer):
  """Pre-LN attention block with residual (ref `:5226`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim.")
    p.Define("num_heads", 8, "Heads.")
    p.Define("atten_tpl", attention_lib.MultiHeadedAttention.Params(),
             "Attention template.")
    p.Define("residual_dropout_prob", 0.0, "Residual dropout.")
    p.Define("norm_tpl", layers_lib.LayerNorm.Params(), "Norm template.")
    p.Define("is_masked", False, "Causal self-attention.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    self.CreateChild("ln", p.norm_tpl.Copy().Set(input_dim=p.input_dim))
    atten_p = p.atten_tpl.Copy().Set(
        input_dim=p.input_dim,
        hidden_dim=p.atten_tpl.hidden_dim or p.input_dim,
        num_heads=p.num_heads)
    self.CreateChild("atten", atten_p)
    self.CreateChild("dropout", layers_lib.DeterministicDropoutLayer.Params())

  def FProp(self, theta, query_vec, source_vecs=None, paddings=None,
            atten_mask=None, segment_ids=None):
    """Self-attention when source_vecs is None; else cross-attention."""
    p = self.p
    with observe.Scope("norm"):
      x = self.ln.FProp(theta.ln, query_vec)
    with observe.Scope("atten"):
      if source_vecs is None:
        # causality is passed as a flag (not a materialized mask) so the
        # fused flash kernel can take over when eligible.
        out, probs = self.atten.FProp(
            theta.atten, x, paddings=paddings, atten_mask=atten_mask,
            segment_ids=segment_ids, causal=p.is_masked)
      else:
        out, probs = self.atten.FProp(
            theta.atten, x, key_vec=source_vecs, value_vec=source_vecs,
            paddings=paddings, atten_mask=atten_mask)
      if p.residual_dropout_prob > 0:
        out = self.dropout.FProp(
            self.ChildTheta(theta, "dropout"), out,
            keep_prob=1.0 - p.residual_dropout_prob)
      return query_vec + out, probs

  def InitStates(self, theta, batch_size, max_len):
    return self.atten.InitStates(theta.atten, batch_size, max_len)

  def ExtendStep(self, theta, query_vec, cached_states, cache_paddings=None):
    return self._Step("ExtendStep", theta, query_vec, cached_states,
                      cache_paddings)

  def Prefill(self, theta, query_vec, cached_states, cache_paddings=None,
              live_len=None):
    """Whole-chunk cache priming: query_vec [B, C, D] -> ([B, C, D], states)."""
    return self._Step("Prefill", theta, query_vec, cached_states,
                      cache_paddings, live_len=live_len)

  def _Step(self, method, theta, query_vec, cached_states, cache_paddings,
            **kw):
    with observe.Scope("norm"):
      x = self.ln.FProp(theta.ln, query_vec)
    with observe.Scope("atten"):
      out, new_states = getattr(self.atten, method)(
          theta.atten, x, cached_states, paddings=cache_paddings, **kw)
      return query_vec + out, new_states

  def InitPagedStates(self, theta, num_pages, page_size, num_slots=0,
                      kv_cache_dtype=None):
    return self.atten.InitPagedStates(theta.atten, num_pages, page_size,
                                      num_slots=num_slots,
                                      kv_cache_dtype=kv_cache_dtype)

  def PagedStep(self, theta, query_vec, cached_states, block_tables, q_pos,
                in_len, ssm_col_states: bool = False):
    """Block-table continuous-batching step (see attention.PagedStep).

    ssm_col_states: speculative-verify mode — O(1)-state mixers also
    return their per-column state trajectory for rejection rollback
    (ssm.GatedSSMLayer.PagedStep); attention mixers ignore it (KV-page
    rollback is free — the write cursor is host-side and reads never
    pass q_pos + in_len)."""
    with observe.Scope("norm"):
      x = self.ln.FProp(theta.ln, query_vec)
    with observe.Scope("atten"):
      if ssm_col_states and hasattr(self.atten, "StateBytesPerSlot"):
        out, new_states = self.atten.PagedStep(
            theta.atten, x, cached_states, block_tables, q_pos, in_len,
            collect_col_states=True)
      else:
        out, new_states = self.atten.PagedStep(
            theta.atten, x, cached_states, block_tables, q_pos, in_len)
      return query_vec + out, new_states

  def RaggedPlanKeys(self, cached_states) -> list:
    """The mixer's attention call over these paged states, as the key its
    part of the step's plan is built for (MultiHeadedAttention.
    RaggedPlanKey); a mixer that is no attention declares none."""
    if not hasattr(self.atten, "RaggedPlanKey"):
      return []
    return [self.atten.RaggedPlanKey(cached_states)]

  def RaggedStep(self, theta, query_vec, cached_states, block_tables, rows,
                 ssm_col_states: bool = False, layer=None, plan=None,
                 then=None):
    """Packed-token continuous-batching step (core/ragged.py RaggedRows);
    query_vec [1, T, D]. Same pre-LN/residual wrapper and spec-verify
    dispatch as PagedStep — only the inner mixer contract changes.
    layer: the mixer's state is stacked over a repeat axis and this is
    its index there (MultiHeadedAttention.RaggedStep); None = its own.
    plan: the step's attention.RaggedPlan, for a mixer that is attention.
    then: what the caller does next with the block's output, row by row (a
    layer's feed-forward): it runs in the same branch as the mixer's output
    projection and the residual, over the rows the step holds
    (ragged.OverLiveRows: one conditional for the three)."""
    kw = {} if layer is None else {"layer": layer}
    if ssm_col_states and hasattr(self.atten, "StateBytesPerSlot"):
      kw["collect_col_states"] = True
    if plan is not None and hasattr(self.atten, "RaggedPlanKey"):
      kw["plan"] = plan
    with observe.Scope("norm"):
      x = self.ln.FProp(theta.ln, query_vec)
    ragged_out = hasattr(self.atten, "RaggedOut")

    def _Mix(theta):
      # a mixer that works by rows of slots, not of the pack
      # (ssm.GatedSSMLayer), has one call, and its output is whole already
      step = self.atten.RaggedMix if ragged_out else self.atten.RaggedStep
      ctx, new_states = step(theta, x, cached_states, block_tables, rows,
                             **kw)
      return (ctx,), new_states

    return _MixThenRows(
        self.atten, theta.atten, plan, _Mix,
        self.atten.RaggedOut if ragged_out else (lambda theta, y: y),
        query_vec, then)


class TransformerLayer(base_layer.BaseLayer):
  """Self-atten (+ optional cross-atten) + FFN (ref `TransformerLayer:6265`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim.")
    p.Define("num_heads", 8, "Heads.")
    p.Define("hidden_dim", 0, "FFN inner dim (0 = 4*input).")
    p.Define("mask_self_atten", False, "Causal self-attention (decoder).")
    p.Define("has_aux_atten", False, "Cross-attention to encoder outputs.")
    p.Define("tr_atten_tpl", TransformerAttentionLayer.Params(),
             "Self-attention template.")
    p.Define("tr_aux_atten_tpl", None, "Cross-attention template (None = "
             "same as tr_atten_tpl).")
    p.Define("tr_fflayer_tpl", TransformerFeedForwardLayer.Params(),
             "FFN template.")
    p.Define(
        "mixer_tpl", None,
        "Optional sequence-mixer template replacing the self-attention "
        "inner layer (e.g. ssm.GatedSSMLayer.Params()). The pre-LN/residual "
        "wrapper, decode contract, and paged-serving contract are shared — "
        "only the mixer inside tr_atten_tpl's TransformerAttentionLayer is "
        "swapped, which is how hybrid stacks mix attention and O(1)-state "
        "layers per depth. None = keep tr_atten_tpl.atten_tpl.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    atten_p = p.tr_atten_tpl.Copy().Set(
        input_dim=p.input_dim, num_heads=p.num_heads, is_masked=p.mask_self_atten)
    if p.mixer_tpl is not None:
      atten_p.atten_tpl = p.mixer_tpl.Copy()
    self.CreateChild("self_atten", atten_p)
    if p.has_aux_atten:
      aux_p = (p.tr_aux_atten_tpl or p.tr_atten_tpl).Copy().Set(
          input_dim=p.input_dim, num_heads=p.num_heads, is_masked=False)
      self.CreateChild("aux_atten", aux_p)
    ff_p = p.tr_fflayer_tpl.Copy().Set(input_dim=p.input_dim)
    if not ff_p.hidden_dim or p.hidden_dim:
      # an expert layer states its own width (core/moe.py) and the layer
      # has no dense feed-forward beside it
      ff_p.hidden_dim = p.hidden_dim or 4 * p.input_dim
    self.CreateChild("fflayer", ff_p)

  def _RouterLogits(self, theta, inputs):
    """Where the feed-forward is an expert layer (core/moe.py): its router
    reads the layer's INPUT, so its logits are taken here, before the
    attention block, and ride past it as a keyword of the feed-forward's
    call. Nothing otherwise."""
    if (hasattr(self.fflayer, "RouterLogits")
        and self.fflayer.p.router_reads == "layer_input"):
      with observe.Scope("ffn"), observe.Scope("moe_route"):
        return {"router_logits": self.fflayer.RouterLogits(theta.fflayer,
                                                           inputs)}
    return {}

  def StackAddressed(self) -> set:
    """Paths (tuples of keys into this layer's theta) of the variables a
    scan over layers hands the layer whole beside its index instead of
    slicing them: what the feed-forward says of itself
    (core/moe.DroplessMoELayer.StackAddressed)."""
    if not hasattr(self.fflayer, "StackAddressed"):
      return set()
    return {("fflayer", name) for name in self.fflayer.StackAddressed()}

  def FProp(self, theta, inputs, paddings=None, aux_vecs=None,
            aux_paddings=None, atten_mask=None, segment_ids=None,
            token_ids=None):
    del token_ids  # only MoE layers with hash gating consume ids
    routed = self._RouterLogits(theta, inputs)
    x, _ = self.self_atten.FProp(
        theta.self_atten, inputs, paddings=paddings, atten_mask=atten_mask,
        segment_ids=segment_ids)
    if self.p.has_aux_atten:
      assert aux_vecs is not None
      x, aux_probs = self.aux_atten.FProp(
          theta.aux_atten, x, source_vecs=aux_vecs, paddings=aux_paddings)
      # consumers that need alignment (e.g. XEnDec target lambdas) collect
      # per-layer cross-attention probs trace-side, no API change
      coll = py_utils.NamedCollectionTop("cross_atten_probs")
      if coll is not None and aux_probs is not None:
        coll[self.path] = aux_probs
    return self.fflayer.FProp(theta.fflayer, x, paddings, **routed)

  def InitStates(self, theta, batch_size, max_len):
    return NestedMap(
        self_atten=self.self_atten.InitStates(theta.self_atten, batch_size,
                                              max_len))

  def ExtendStep(self, theta, inputs, cached_states, aux_vecs=None,
                 aux_paddings=None, cache_paddings=None):
    return self._Step("ExtendStep", theta, inputs, cached_states, aux_vecs,
                      aux_paddings, cache_paddings)

  def Prefill(self, theta, inputs, cached_states, aux_vecs=None,
              aux_paddings=None, cache_paddings=None, live_len=None):
    return self._Step("Prefill", theta, inputs, cached_states, aux_vecs,
                      aux_paddings, cache_paddings, live_len=live_len)

  def _Step(self, method, theta, inputs, cached_states, aux_vecs,
            aux_paddings, cache_paddings, **kw):
    routed = self._RouterLogits(theta, inputs)
    x, new_sa = getattr(self.self_atten, method)(
        theta.self_atten, inputs, cached_states.self_atten,
        cache_paddings=cache_paddings, **kw)
    if self.p.has_aux_atten:
      x, _ = self.aux_atten.FProp(
          theta.aux_atten, x, source_vecs=aux_vecs, paddings=aux_paddings)
    out = self.fflayer.FProp(theta.fflayer, x, **routed)
    return out, NestedMap(self_atten=new_sa)

  def InitPagedStates(self, theta, num_pages, page_size, num_slots=0,
                      kv_cache_dtype=None):
    assert not self.p.has_aux_atten, (
        "continuous-batching serving is decoder-only (no cross-attention)")
    states = NestedMap(self_atten=self.self_atten.InitPagedStates(
        theta.self_atten, num_pages, page_size, num_slots=num_slots,
        kv_cache_dtype=kv_cache_dtype))
    if hasattr(self.fflayer, "InitPagedStates"):
      # an expert layer's tokens by expert of the newest step
      states.fflayer = self.fflayer.InitPagedStates(theta.fflayer)
    return states

  def PagedStep(self, theta, inputs, cached_states, block_tables, q_pos,
                in_len, ssm_col_states: bool = False):
    routed = self._RouterLogits(theta, inputs)
    x, new_sa = self.self_atten.PagedStep(
        theta.self_atten, inputs, cached_states.self_atten, block_tables,
        q_pos, in_len, ssm_col_states=ssm_col_states)
    out = self.fflayer.FProp(theta.fflayer, x, **routed)
    new_states = cached_states.Copy()
    new_states.self_atten = new_sa
    return out, new_states

  def RaggedPlanKeys(self, cached_states) -> list:
    return self.self_atten.RaggedPlanKeys(cached_states.self_atten)

  def RaggedStep(self, theta, inputs, cached_states, block_tables, rows,
                 ssm_col_states: bool = False, layer=None, plan=None):
    routed = self._RouterLogits(theta, inputs)
    # a dense feed-forward is row-wise: it runs in the branch of the
    # attention block's output (TransformerAttentionLayer.RaggedStep)
    dense = "fflayer" not in cached_states and not routed
    x, new_sa = self.self_atten.RaggedStep(
        theta.self_atten, inputs, cached_states.self_atten, block_tables,
        rows, ssm_col_states=ssm_col_states, layer=layer, plan=plan,
        then=(lambda x: self.fflayer.FProp(theta.fflayer, x))
        if dense else None)
    if dense:
      return x, NestedMap(self_atten=new_sa)
    if "fflayer" not in cached_states:
      out = self.fflayer.FProp(theta.fflayer, x, **routed)
      return out, NestedMap(self_atten=new_sa)
    out, new_ff = self.fflayer.RaggedStep(
        theta.fflayer, x, cached_states.fflayer, rows, layer=layer, **routed)
    return out, NestedMap(self_atten=new_sa, fflayer=new_ff)


class StackedTransformerLayers(base_layer.BaseLayer):
  """N distinct transformer layers (ref `StackedTransformerLayers:7116`)."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("num_layers", 0, "Depth.")
    p.Define("transformer_layer_params_tpl", TransformerLayer.Params(),
             "Per-layer template.")
    p.Define(
        "layer_tpls", None,
        "Optional explicit per-layer templates (list of TransformerLayer "
        "Params, length num_layers) overriding transformer_layer_params_tpl "
        "— the hook heterogeneous stacks (hybrid attention/SSM) hang off. "
        "Also the repeat-block body trick: a RepeatedTransformerLayer whose "
        "body is a StackedTransformerLayers with layer_tpls scans one "
        "heterogeneous block of depth k, giving num_layers/k repeats of "
        "e.g. [ssm, ssm, ..., attention].")
    p.Define("final_ln", True, "LayerNorm on the final output.")
    p.Define("input_dim", 0, "Model dim (propagated to layers).")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.num_layers > 0
    if p.layer_tpls:
      assert len(p.layer_tpls) == p.num_layers, (
          len(p.layer_tpls), p.num_layers)
      tpls = [t.Copy() for t in p.layer_tpls]
    else:
      tpls = [p.transformer_layer_params_tpl.Copy()
              for _ in range(p.num_layers)]
    if p.input_dim:
      for t in tpls:
        t.input_dim = p.input_dim
    self.CreateChildren("x_layers", tpls)
    if p.final_ln:
      self.CreateChild(
          "final_ln",
          layers_lib.LayerNorm.Params().Set(
              input_dim=p.input_dim or tpls[0].input_dim))

  def FProp(self, theta, inputs, paddings=None, aux_vecs=None,
            aux_paddings=None, segment_ids=None, token_ids=None):
    x = inputs
    for i, layer in enumerate(self.x_layers):
      x = layer.FProp(theta.x_layers[i], x, paddings, aux_vecs, aux_paddings,
                      segment_ids=segment_ids, token_ids=token_ids)
    if self.p.final_ln:
      x = self.final_ln.FProp(theta.final_ln, x)
    return x

  def StackAddressed(self) -> set:
    """As TransformerLayer.StackAddressed, over this block's layers."""
    return {("x_layers", str(i)) + path
            for i, l in enumerate(self.x_layers)
            if hasattr(l, "StackAddressed") for path in l.StackAddressed()}

  def InitStates(self, theta, batch_size, max_len):
    return NestedMap(x_layers=[
        l.InitStates(theta.x_layers[i], batch_size, max_len)
        for i, l in enumerate(self.x_layers)
    ])

  def ExtendStep(self, theta, inputs, cached_states, aux_vecs=None,
                 aux_paddings=None, cache_paddings=None):
    return self._Step("ExtendStep", theta, inputs, cached_states, aux_vecs,
                      aux_paddings, cache_paddings)

  def Prefill(self, theta, inputs, cached_states, aux_vecs=None,
              aux_paddings=None, cache_paddings=None, live_len=None):
    return self._Step("Prefill", theta, inputs, cached_states, aux_vecs,
                      aux_paddings, cache_paddings, live_len=live_len)

  def _Step(self, method, theta, inputs, cached_states, aux_vecs,
            aux_paddings, cache_paddings, **kw):
    x = inputs
    new_states = NestedMap(x_layers=[])
    for i, layer in enumerate(self.x_layers):
      x, ns = getattr(layer, method)(theta.x_layers[i], x,
                                     cached_states.x_layers[i], aux_vecs,
                                     aux_paddings,
                                     cache_paddings=cache_paddings, **kw)
      new_states.x_layers.append(ns)
    if self.p.final_ln:
      x = self.final_ln.FProp(theta.final_ln, x)
    return x, new_states

  def MixerLayers(self):
    """[(mixer, how many layers of the stack are it)], in stack order: what
    the serving census counts and prices (serving/kv_cache.StackCensus)."""
    return [(l.self_atten.atten, 1) for l in self.x_layers]

  # StepCounts(cached_states, geometry): what a step of the host's rows cost
  # this stack's mixers, in the serving engine's counters; asked once
  StepCounts = ragged.StackStepCounts

  def PageWindows(self):
    """None, or where this block's layers are attention layers of two
    kinds, full and sliding-window: each layer's window (0 = full). Such a
    block keeps ONE pool of uniform pages for all its layers (`kv_pool` in
    its paged states) and reads a block table a layer, `[layers, B,
    t_pages]`: a page holds page_size tokens of one layer, so neither kind
    has a share of the pool fixed for it (serving/kv_cache.KindPages)."""
    mixers = [getattr(getattr(l, "self_atten", None), "atten", None)
              for l in self.x_layers]
    windows = [int(getattr(getattr(m, "p", None), "window", 0) or 0)
               for m in mixers]
    if not any(windows) or all(windows):
      return None
    assert all(hasattr(m, "RaggedStep") and not hasattr(
        m, "StateBytesPerSlot") for m in mixers), (
            "layers of two kinds share a page pool among attention layers "
            "only")
    return windows

  def InitPagedStates(self, theta, num_pages, page_size, num_slots=0,
                      kv_cache_dtype=None):
    states = NestedMap(x_layers=[
        l.InitPagedStates(theta.x_layers[i], num_pages, page_size,
                          num_slots=num_slots, kv_cache_dtype=kv_cache_dtype)
        for i, l in enumerate(self.x_layers)
    ])
    if self.PageWindows() is not None:
      # one pool for every layer of the block: the first layer's, and no
      # layer keeps one of its own
      states.kv_pool = states.x_layers[0].self_atten
      for layer_states in states.x_layers:
        assert jax.tree_util.tree_map(jnp.shape, layer_states.self_atten) == (
            jax.tree_util.tree_map(jnp.shape, states.kv_pool))
        layer_states.self_atten = NestedMap()
    return states

  def PagedStep(self, theta, inputs, cached_states, block_tables, q_pos,
                in_len, ssm_col_states: bool = False):
    # forward the spec-verify flag only when set, so layer bodies that
    # predate it (no serving contract) are never handed a surprise kwarg
    kw = {"ssm_col_states": True} if ssm_col_states else {}
    x = inputs
    new_states = NestedMap(x_layers=[])
    for i, layer in enumerate(self.x_layers):
      x, ns = layer.PagedStep(theta.x_layers[i], x,
                              cached_states.x_layers[i], block_tables, q_pos,
                              in_len, **kw)
      new_states.x_layers.append(ns)
    if self.p.final_ln:
      x = self.final_ln.FProp(theta.final_ln, x)
    return x, new_states

  def _LayerStates(self, cached_states, i: int, pool):
    """Layer i's paged states: its own, or (a block of two kinds of layer,
    PageWindows) the block's one pool as its `self_atten`."""
    states_i = cached_states.x_layers[i]
    if pool is not None:
      states_i = states_i.Copy()
      states_i.self_atten = pool
    return states_i

  def RaggedPlanKeys(self, cached_states) -> list:
    """Every layer's (TransformerLayer.RaggedPlanKeys), in stack order."""
    pool = cached_states.get("kv_pool")
    return [key for i, layer in enumerate(self.x_layers)
            for key in layer.RaggedPlanKeys(
                self._LayerStates(cached_states, i, pool))]

  def RaggedStep(self, theta, inputs, cached_states, block_tables, rows,
                 ssm_col_states: bool = False, layer=None, plan=None):
    """layer: None here (distinct layers, each with a pool of its own); an
    index when this stack is the body of a RepeatedTransformerLayer, whose
    stacked states every x_layer then addresses by it. A block of two
    kinds of layer (PageWindows) hands its one pool from layer to layer
    and each layer its own table of `block_tables` [layers, B, t_pages].
    plan: the step's attention.RaggedPlan from the stack this one is the
    body of; None: built here, once for all the layers."""
    kw = {"ssm_col_states": True} if ssm_col_states else {}
    if layer is not None:
      kw["layer"] = layer
    if plan is None:
      plan = attention_lib.BuildRaggedPlan(
          self.RaggedPlanKeys(cached_states), rows, *block_tables.shape[-2:])
    x = inputs
    new_states = NestedMap(x_layers=[])
    pool = cached_states.get("kv_pool")
    for i, x_layer in enumerate(self.x_layers):
      states_i = self._LayerStates(cached_states, i, pool)
      tables_i = block_tables if pool is None else block_tables[i]
      x, ns = x_layer.RaggedStep(theta.x_layers[i], x, states_i, tables_i,
                                 rows, plan=plan, **kw)
      if pool is not None:
        pool, ns.self_atten = ns.self_atten, NestedMap()
      new_states.x_layers.append(ns)
    if pool is not None:
      new_states.kv_pool = pool
    if self.p.final_ln:
      x = self.final_ln.FProp(theta.final_ln, x)
    return x, new_states

  def PagedStepPrefix(self, theta, inputs, cached_states, block_tables,
                      q_pos, in_len, num_layers: int):
    """First num_layers layers only — the early-exit draft pass for
    self-speculative decoding. States of the untouched suffix layers pass
    through unchanged so the returned pytree matches PagedStep's (the
    draft loop threads it as a transient carry and discards it)."""
    assert 1 <= num_layers <= len(self.x_layers), (
        num_layers, len(self.x_layers))
    x = inputs
    new_states = NestedMap(x_layers=[])
    for i, layer in enumerate(self.x_layers):
      if i < num_layers:
        x, ns = layer.PagedStep(theta.x_layers[i], x,
                                cached_states.x_layers[i], block_tables,
                                q_pos, in_len)
      else:
        ns = cached_states.x_layers[i]
      new_states.x_layers.append(ns)
    if self.p.final_ln:
      x = self.final_ln.FProp(theta.final_ln, x)
    return x, new_states


class RepeatedTransformerLayer(base_layer.BaseLayer):
  """N IDENTICAL-architecture layers as one scan with stacked weights.

  Ref `RepeatedTransformerLayer:6976` + `repeat_layer.GenericRepeatLayer:80`.
  theta.body has every leaf stacked on axis 0 (length num_layers); FProp scans
  the body over that axis. Compile time is O(1) in depth; per-layer dropout
  folds the scan index into the step seed.

  Paged decode states are stacked the same way ([L, NP, P, N, H] pools).
  RaggedStep carries them through its scan and the body addresses layer
  i's pages at base i * NP of the stack seen as one pool: as scanned
  values XLA copied the stack and re-sliced a layer's pool every layer.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("num_layers", 0, "Repeat count.")
    p.Define("body", TransformerLayer.Params(), "The repeated layer.")
    p.Define("per_layer_checkpoint", True,
             "jax.checkpoint each body iteration (remat for long stacks).")
    p.Define(
        "remat_policy", "full",
        "What the per-layer checkpoint saves: 'full' = save only the layer "
        "boundary and recompute everything in bwd (min memory, ~4/3x "
        "flops); 'dots' = save matmul outputs and recompute only "
        "elementwise ops (near-zero extra flops, more memory); 'none' = "
        "same as per_layer_checkpoint=False.")
    return p

  def __init__(self, params):
    super().__init__(params)
    assert self.p.num_layers > 0
    self.CreateChild("body", self.p.body)

  def InstantiateVariables(self, key):
    if self._path is None:
      self.FinalizePaths()
    return NestedMap(body=base_layer.StackedInstantiateVariables(
        self.body, key, self.p.num_layers))

  def VariableSpecs(self):
    return NestedMap(body=base_layer.StackedVariableSpecs(
        self.body, self.p.num_layers))

  def FProp(self, theta, inputs, paddings=None, aux_vecs=None,
            aux_paddings=None, segment_ids=None, token_ids=None):
    p = self.p
    aux_flag = py_utils.NewAuxFlag()

    def _BodyInner(theta_i, idx, carry):
      # Fold the layer index into step seeds: each scan iteration gets its
      # own dropout masks even though FProp is traced once.
      with py_utils.StepSeedSalt(idx):
        return self.body.FProp(theta_i, carry, paddings, aux_vecs,
                               aux_paddings, segment_ids=segment_ids,
                               token_ids=token_ids)

    wrapped = py_utils.CollectAuxLosses(_BodyInner, aux_flag)

    def _Body(carry, per_layer):
      theta_i, idx = per_layer
      x, aux_sum = wrapped(theta_i, idx, carry)
      return x, aux_sum

    body_fn = _Body
    if p.per_layer_checkpoint and p.remat_policy != "none":
      if p.remat_policy == "dots":
        # also pin the MoE dispatch/combine all-to-all outputs (tagged via
        # checkpoint_name in gshard._DispatchShardMap): without this the
        # backward pass replays both forward all-to-alls per MoE layer —
        # pure ICI traffic for activations 'dots' would have saved anyway
        # had the dispatch been a matmul
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "moe_dispatched", "moe_combined"))
        body_fn = jax.checkpoint(_Body, policy=policy)
      else:
        body_fn = jax.checkpoint(_Body)
    with observe.Scope("layer_scan"):
      out, aux_per_layer = jax.lax.scan(
          body_fn, inputs, (theta.body, jnp.arange(p.num_layers)))
    if aux_flag.emitted:
      py_utils.AddAuxLoss(f"{self.path}/aux_loss", jnp.sum(aux_per_layer))
    return out

  def InitStates(self, theta, batch_size, max_len):
    def _One(theta_i):
      return self.body.InitStates(theta_i, batch_size, max_len)

    return NestedMap(body=jax.vmap(_One)(theta.body))

  def ExtendStep(self, theta, inputs, cached_states, aux_vecs=None,
                 aux_paddings=None, cache_paddings=None):
    return self._Step("ExtendStep", theta, inputs, cached_states, aux_vecs,
                      aux_paddings, cache_paddings)

  def Prefill(self, theta, inputs, cached_states, aux_vecs=None,
              aux_paddings=None, cache_paddings=None, live_len=None):
    return self._Step("Prefill", theta, inputs, cached_states, aux_vecs,
                      aux_paddings, cache_paddings, live_len=live_len)

  def _Step(self, method, theta, inputs, cached_states, aux_vecs,
            aux_paddings, cache_paddings, **kw):
    def _Body(carry, per_layer):
      theta_i, states_i = per_layer
      x, new_states = getattr(self.body, method)(
          theta_i, carry, states_i, aux_vecs, aux_paddings,
          cache_paddings=cache_paddings, **kw)
      return x, new_states

    out, new_states = jax.lax.scan(_Body, inputs,
                                   (theta.body, cached_states.body))
    return out, NestedMap(body=new_states)

  def MixerLayers(self):
    """The body's mixers, each once a repeat (StackedTransformerLayers.
    MixerLayers). A body that serves nothing (gshard.DenseMoEBlock, which
    trains only) has none."""
    if isinstance(self.body, StackedTransformerLayers):
      inner = self.body.MixerLayers()
    elif isinstance(self.body, TransformerLayer):
      inner = [(self.body.self_atten.atten, 1)]
    else:
      inner = []
    return [(m, reps * self.p.num_layers) for m, reps in inner]

  StepCounts = ragged.StackStepCounts   # as StackedTransformerLayers'

  def PageWindows(self):
    """The body's (StackedTransformerLayers.PageWindows), or None."""
    return getattr(self.body, "PageWindows", lambda: None)()

  def InitPagedStates(self, theta, num_pages, page_size, num_slots=0,
                      kv_cache_dtype=None):
    def _One(theta_i):
      return self.body.InitPagedStates(theta_i, num_pages, page_size,
                                       num_slots=num_slots,
                                       kv_cache_dtype=kv_cache_dtype)

    return NestedMap(body=jax.vmap(_One)(theta.body))

  def PagedStep(self, theta, inputs, cached_states, block_tables, q_pos,
                in_len, ssm_col_states: bool = False):
    kw = {"ssm_col_states": True} if ssm_col_states else {}

    def _Body(carry, per_layer):
      theta_i, states_i = per_layer
      x, new_states = self.body.PagedStep(theta_i, carry, states_i,
                                          block_tables, q_pos, in_len, **kw)
      return x, new_states

    out, new_states = jax.lax.scan(_Body, inputs,
                                   (theta.body, cached_states.body))
    return out, NestedMap(body=new_states)

  def RaggedPlanKeys(self, cached_states) -> list:
    """The body's, once a repeat: one key an attention call of the step."""
    return self.body.RaggedPlanKeys(cached_states.body) * self.p.num_layers

  def RaggedStep(self, theta, inputs, cached_states, block_tables, rows,
                 ssm_col_states: bool = False):
    """The stacked states are the scan's CARRY, one buffer from the caller's
    (donated) argument to the returned state; layer i's mixers read and
    write their part in place, addressed by `layer=i`. A leaf a mixer adds
    for this step only (`col_states`) is its layer's own: a scanned output,
    which comes back stacked."""
    kw = {"ssm_col_states": True} if ssm_col_states else {}
    # what the rows alone decide is built here, once, and reaches every
    # trip as an invariant of the loop
    plan = attention_lib.BuildRaggedPlan(
        self.body.RaggedPlanKeys(cached_states.body), rows,
        *block_tables.shape[-2:])

    def _ByPath(tree):
      return dict(jax.tree_util.tree_flatten_with_path(tree)[0])

    carried = _ByPath(cached_states.body)

    # variables the body addresses in the stack by `layer` stay whole
    # (StackAddressed)
    whole = (self.body.StackAddressed()
             if hasattr(self.body, "StackAddressed") else set())

    def _Body(carry, idx):
      x, states = carry
      # no variable is scanned: layer idx's stays in its stack until a layer
      # uses it (base_layer.StackSlice), so that a use inside a conditional's
      # branch (ragged.OverLiveRows) slices the stack there
      theta_i = base_layer.SlicedTheta(theta.body, idx, whole)
      x, new_states = self.body.RaggedStep(theta_i, x, states, block_tables,
                                           rows, layer=idx, plan=plan, **kw)
      new = _ByPath(new_states)
      states = jax.tree_util.tree_map_with_path(
          lambda path, _: new[path], states)
      added = jax.tree_util.tree_map_with_path(
          lambda path, leaf: None if path in carried else leaf, new_states)
      return (x, states), added

    with observe.Scope("layer_scan"):
      (out, states), added = jax.lax.scan(
          _Body, (inputs, cached_states.body),
          jnp.arange(self.p.num_layers))
    final = _ByPath(states)
    new_states = jax.tree_util.tree_map_with_path(
        lambda path, leaf: final[path] if leaf is None else leaf, added,
        is_leaf=lambda leaf: leaf is None)
    return out, NestedMap(body=new_states)

  def PagedStepPrefix(self, theta, inputs, cached_states, block_tables,
                      q_pos, in_len, num_layers: int):
    """First num_layers FLAT layers — the early-exit draft pass.

    num_layers counts flat transformer layers from the bottom, so it must
    be a multiple of the scanned body's depth (1 for a plain repeat, the
    block depth for hybrid repeat-of-stacked bodies); the scan runs over
    the sliced leading repeats and the suffix repeats' states pass
    through untouched (pytree matches PagedStep's)."""
    body_depth = (len(self.body.x_layers)
                  if hasattr(self.body, "x_layers") else 1)
    assert num_layers % body_depth == 0, (
        f"an early-exit draft of num_layers={num_layers} layers: not a "
        f"multiple of the scanned repeat body's depth ({body_depth})")
    reps = num_layers // body_depth
    assert 1 <= reps <= self.p.num_layers, (reps, self.p.num_layers)
    prefix_theta = jax.tree_util.tree_map(lambda t: t[:reps], theta.body)
    prefix_states = jax.tree_util.tree_map(lambda s: s[:reps],
                                           cached_states.body)

    def _Body(carry, per_layer):
      theta_i, states_i = per_layer
      x, new_states = self.body.PagedStep(theta_i, carry, states_i,
                                          block_tables, q_pos, in_len)
      return x, new_states

    out, new_prefix = jax.lax.scan(_Body, inputs,
                                   (prefix_theta, prefix_states))
    new_body = jax.tree_util.tree_map(
        lambda new, old: jnp.concatenate([new, old[reps:]], axis=0),
        new_prefix, cached_states.body)
    return out, NestedMap(body=new_body)


class SharedStateLayer(base_layer.BaseLayer):
  """h += Mixer(LN(h)), then the feed-forward block, for a mixer of
  `BlockSequence`: one that reads or writes what the stack's layers share
  (`shared`: the one pool of pages, a memory an earlier layer exported, in a
  whole-sequence forward an earlier layer's K and V). The mixer's contract
  (ssm.Mamba1Layer, ssm.Mamba2Layer, ssm.GatedMemoryUnit,
  attention.DifferentialAttention, attention.PooledAttention):
  `FProp(theta, x, shared, paddings, segment_ids, depth) -> (out, shared)`,
  `InitPagedStates(theta, num_slots)`, and the serving step in two halves:
  `RaggedMix(theta, x, states, shared, rows, table, depth, plan) -> (ctx,
  states, shared)`, what mixes tokens (its projections over the rows the step
  holds, its kernels over the step's pairs and runs), and `RaggedOut(theta,
  *ctx, depth) -> out`, what follows row by row ([1, n, ...] -> [1, n, D]),
  which this layer runs with the residual and the feed-forward in ONE branch
  over the rows the step holds (ragged.OverLiveRows). `plan`: the step's
  attention.RaggedPlan or the retention layers' own.

  Either branch may be absent: `mixer_tpl` None is a layer that is its
  feed-forward alone, `tr_fflayer_tpl` None one that is its mixer alone. The
  absent branch has no norm, no weights and no op. The feed-forward may be
  an expert layer (core/moe.DroplessMoELayer whose router reads its own
  normed input): its tokens by expert are the layer's `routed` state leaf,
  and a scanned block hands it the experts' matrices whole with the repeat's
  index (`StackAddressed`, `repeat`). Where that layer holds a share of the
  experts it routes over (`first_expert`, `num_experts_held`) the pairs whose
  expert lives on another chip are the `elsewhere` leaf beside `routed`,
  carried through `InitPagedStates` / `RaggedStep` / the scanned block as it
  is, so that the engine counts them (`moe_pairs_elsewhere`).

  `residual_multiplier`: a factor on each branch's output before it is added
  to the stream, `h += f * Mixer(LN(h))` and `h += f * FeedForward(LN(h))`
  (the expert layer's own residual included); 1 is no factor and no op.

  `post_norm_tpl`: a norm on the MIXER branch's output before the residual
  add, `h += PostLN(Mixer(LN(h)))`; the feed-forward's is its own template's
  (`tr_fflayer_tpl.post_norm_tpl`, dense or experts). None: none, no
  variable and no op."""

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim.")
    p.Define("mixer_tpl", None, "The mixer's template; None: no mixer.")
    p.Define("norm_tpl", layers_lib.LayerNorm.Params(), "The mixer's norm.")
    p.Define("tr_fflayer_tpl", TransformerFeedForwardLayer.Params(),
             "Feed-forward block (with its own norm and residual); None: "
             "no feed-forward.")
    p.Define("residual_multiplier", 1.0,
             "Factor on a branch's output before the residual add.")
    p.Define("post_norm_tpl", None,
             "A norm on the mixer's output before the residual add.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.mixer_tpl is not None or p.tr_fflayer_tpl is not None
    self.mixer = None
    if p.mixer_tpl is not None:
      self.CreateChild("ln", p.norm_tpl.Copy().Set(input_dim=p.input_dim))
      self.CreateChild("atten", p.mixer_tpl.Copy().Set(input_dim=p.input_dim))
      self.mixer = self.atten
      if p.post_norm_tpl is not None:
        self.CreateChild("post_ln",
                         p.post_norm_tpl.Copy().Set(input_dim=p.input_dim))
    self._experts = False
    if p.tr_fflayer_tpl is not None:
      fflayer = p.tr_fflayer_tpl.Copy().Set(input_dim=p.input_dim)
      if p.residual_multiplier != 1.0:
        fflayer.residual_scale = p.residual_multiplier
      self.CreateChild("fflayer", fflayer)
      self._experts = hasattr(self.fflayer, "FPropWithCounts")
      assert not self._experts or (
          self.fflayer.p.router_reads == "normed_input"), (
              "an expert layer of a BlockSequence routes from its own input")

  def StackAddressed(self) -> set:
    """As TransformerLayer.StackAddressed: the experts' matrices."""
    if not self._experts:
      return set()
    return {("fflayer", name) for name in self.fflayer.StackAddressed()}

  def StackStates(self) -> tuple:
    """Names of the slot-state leaves the mixer reads and writes IN the
    block's stack, by the repeat's index: a scan that sliced them a trip and
    stacked them again would copy them whole (a retention layer's are 0.6
    GB a layer)."""
    return tuple(getattr(self.mixer, "stack_states", ()))

  def _FeedForward(self, theta, x, paddings, repeat):
    """-> (x, the expert layer's count leaves (`routed`, and `elsewhere`
    where it holds a share) or None)."""
    if self.p.tr_fflayer_tpl is None:
      return x, None
    if not self._experts:
      return self.fflayer.FProp(theta.fflayer, x, paddings), None
    x, counts = self.fflayer.FPropWithCounts(
        theta.fflayer, x, None, paddings,
        layer=repeat if theta.fflayer.w_up.ndim == 4 else None)
    valid = (math.prod(x.shape[:-1]) if paddings is None
             else jnp.sum((paddings < 0.5).astype(jnp.int32)))
    return x, self.fflayer.CountLeaves(counts, valid)

  def FProp(self, theta, x, shared, paddings=None, segment_ids=None,
            depth=0, repeat=None):
    if self.mixer is not None:
      with observe.Scope("norm"):
        normed = self.ln.FProp(theta.ln, x)
      with observe.Scope("atten"):
        out, shared = self.atten.FProp(theta.atten, normed, shared,
                                       paddings=paddings,
                                       segment_ids=segment_ids, depth=depth)
        if self.p.post_norm_tpl is not None:
          with observe.Scope("post_norm"):
            out = self.post_ln.FProp(theta.post_ln, out)
        scale = self.p.residual_multiplier
        x = x + (out if scale == 1.0 else scale * out)
    return self._FeedForward(theta, x, paddings, repeat)[0], shared

  def InitPagedStates(self, theta, num_slots):
    states = (self.atten.InitPagedStates(theta.atten, num_slots)
              if self.mixer is not None else NestedMap())
    if self._experts:
      states.update(self.fflayer.InitPagedStates(theta.fflayer))
    return states

  def RaggedStep(self, theta, x, states, shared, rows, table, depth, plan,
                 repeat=None):
    dense = self.p.tr_fflayer_tpl is not None and not self._experts
    feed_forward = (lambda x: self._FeedForward(theta, x, None, repeat)[0]
                    ) if dense else None
    if self.mixer is not None:
      if self._experts:
        states = NestedMap({k: v for k, v in states.items()
                            if k not in ("routed", "elsewhere")})
      with observe.Scope("norm"):
        normed = self.ln.FProp(theta.ln, x)
      # a mixer whose state is too large to slice a trip is handed the
      # whole stack's and the repeat's index (StackStates)
      extra = {"layer": repeat} if self.StackStates() else {}
      x, states, shared = _MixThenRows(
          self.atten, theta.atten, plan,
          lambda theta: self.atten.RaggedMix(
              theta, normed, states, shared, rows, table=table, depth=depth,
              plan=plan, **extra),
          lambda theta, *ctx: self.atten.RaggedOut(theta, *ctx, depth=depth),
          x, feed_forward, scale=self.p.residual_multiplier,
          post=None if self.p.post_norm_tpl is None else (
              lambda y: self.post_ln.FProp(theta.post_ln, y)))
    elif dense:
      # a layer that is its dense feed-forward alone
      x = ragged.OverLiveRows(feed_forward, plan, x)
    if self._experts:
      # the step's padding tokens are routed nowhere
      paddings = 1.0 - rows.valid.astype(jnp.float32)[None]
      experts = lambda x, paddings: self._FeedForward(theta, x, paddings,
                                                      repeat)
      # in a step whose live tokens fit the pack's decode width the layer
      # runs over those rows alone: the grouped matmuls skip dead rows by
      # themselves, what is round them (the gather of [T x k, D] rows, the
      # weighing, the unsort, the sum over k) does not
      narrow = getattr(plan, "narrow", None)
      if narrow is None:
        x, counts = experts(x, paddings)
      else:
        t, w = x.shape[1], narrow.rows

        def _Narrow(x, paddings):
          # the valid tokens lead the pack: the first W hold every live one
          y, counts = experts(x[:, :w], paddings[:, :w])
          return jnp.pad(y, ((0, 0), (0, t - w), (0, 0))), counts

        x, counts = jax.lax.cond(narrow.fits, _Narrow, experts, x, paddings)
      states = states.Copy()
      states.update(counts)
    return x, states, shared


class BlockSequence(base_layer.BaseLayer):
  """A stack told as data: blocks in sequence, each a short list of layers
  repeated some number of times and run as one scan over its stacked
  weights (a block of one repeat is a scan of one trip). A depth with no
  period is a sequence of blocks that each have one; what is left over
  between two periodic stretches is a block of its own.

  The layers are `SharedStateLayer`s and share, from the first block to the
  last, a `shared` map that every mixer may read and replace:

  - serving (`RaggedStep`): `kv_pool`, ONE pool of uniform pages
    `[pages, P, KV heads, H]` for every attention layer that owns pages. An
    owning layer writes and reads through its own block table
    (`block_tables[k]` for the k-th owner of the stack, in order); a layer
    that owns none reads the pages and the table of the nearest owner
    before it, and allocates and writes nothing. `memory`, `[1, T, E]`,
    what a layer exported for the same tokens (zeros until one has).
  - whole sequences (`FProp`): `memory`, and `key` / `value` of the layer
    that exports them.

  Slot state (`InitPagedStates`): every layer's own leaves, stacked over its
  block's repeats, `blocks[b].x_layers[j].<leaf>` `[repeats, slots, ...]`;
  they are the scan's inputs and outputs, the pool its carry.

  Serves through `RaggedStep` only (the engine's one packed program); the
  dense decode contracts (`ExtendStep`, `Prefill`, `PagedStep`) and
  speculative column states are not built for it.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("input_dim", 0, "Model dim (propagated to the layers).")
    p.Define("blocks", None,
             "[(list of SharedStateLayer Params, repeats)], in stack order.")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    assert p.blocks
    self._repeats = [int(reps) for _, reps in p.blocks]
    for b, (tpls, _) in enumerate(p.blocks):
      self.CreateChild(f"block_{b}", StackedTransformerLayers.Params().Set(
          num_layers=len(tpls), input_dim=p.input_dim, final_ln=False,
          layer_tpls=[t.Copy() for t in tpls]))
    self._bodies = [getattr(self, f"block_{b}").x_layers
                    for b in range(len(p.blocks))]
    # a layer's place: its depth in the stack, and which block table it
    # uses: its own (the k-th owner's) or the nearest owner's before it
    self._first_depth, self._table_of, depth, owners = [], [], 0, 0
    for layers, reps in zip(self._bodies, self._repeats):
      self._first_depth.append(depth)
      own = [getattr(l.mixer, "kv_owner", None) for l in layers]
      per = sum(bool(o) for o in own)
      tables, k = [], 0
      for o in own:
        if o:
          tables.append(("own", owners, k))
          k += 1
        elif o is None:
          tables.append(None)
        else:
          assert owners + k > 0 and (reps == 1 or per == 0), (
              "a layer that owns no pages reads the nearest owner before "
              "it, which a repeated block cannot hold beside it")
          tables.append(("read", owners + k - 1, None))
      self._table_of.append(tables)
      owners += per * reps
      depth += len(layers) * reps
    self._num_owners = owners
    # width of the memory a layer exports (0: none does)
    self._memory_dim = max(
        [m._e for m, _ in self._Mixers()
         if getattr(m.p, "export_memory", False)], default=0)
    # a scanned block hands these to its layers whole (StackAddressed)
    self._whole = [
        {("x_layers", str(j)) + path for j, l in enumerate(layers)
         for path in l.StackAddressed()} for layers in self._bodies]

  def InstantiateVariables(self, key):
    if self._path is None:
      self.FinalizePaths()
    return NestedMap({
        f"block_{b}": base_layer.StackedInstantiateVariables(
            getattr(self, f"block_{b}"), key, reps)
        for b, reps in enumerate(self._repeats)})

  def VariableSpecs(self):
    return NestedMap({
        f"block_{b}": base_layer.StackedVariableSpecs(
            getattr(self, f"block_{b}"), reps)
        for b, reps in enumerate(self._repeats)})

  def _Mixers(self):
    """[(mixer, repeats of its block)] for every layer that has one, a
    repeated block's layers once."""
    return [(l.mixer, reps) for layers, reps in zip(self._bodies,
                                                    self._repeats)
            for l in layers if l.mixer is not None]

  def MixerLayers(self):
    """[(mixer, how many layers of the stack are it)] for the mixers that
    keep decode state, pages or a slot's (serving/kv_cache.StackCensus)."""
    return [(m, reps) for m, reps in self._Mixers()
            if hasattr(m, "StateBytesPerSlot")
            or hasattr(m, "KvBytesPerToken")]

  def LayerKinds(self) -> dict:
    """{what a layer is: how many layers of the stack are it}: a layer is
    its mixer's class, its feed-forward's, or both joined by '+'."""
    kinds: dict = {}
    for layers, reps in zip(self._bodies, self._repeats):
      for l in layers:
        name = "+".join(type(c).__name__ for c in (
            l.mixer, getattr(l, "fflayer", None)) if c is not None)
        kinds[name] = kinds.get(name, 0) + reps
    return kinds

  def PageWindows(self):
    """The window of every layer that OWNS pages (0 = full), in stack
    order: one block table each, all out of one pool of uniform pages
    (serving/kv_cache.KindPages)."""
    return [int(m.p.window) for m, reps in self._Mixers()
            for _ in range(reps) if getattr(m, "kv_owner", False)]

  def _AttentionMixers(self) -> list:
    """The mixer of every layer of the stack that attends over pages, in
    stack order (a repeated block's once a repeat)."""
    return [l.mixer for layers, reps in zip(self._bodies, self._repeats)
            for _ in range(reps) for l in layers
            if hasattr(l.mixer, "RaggedPlanKey")]

  def RaggedPlanKeys(self, cached_states) -> list:
    """One key an attention call of the step (DifferentialAttention.
    RaggedPlanKey): what `RaggedStep` builds the step's plan for."""
    return [a.RaggedPlanKey(cached_states.kv_pool)
            for a in self._AttentionMixers()]

  def WritesWholePages(self, cached_states) -> bool:
    """Some layer of the stack writes its pages through
    ops/diff_attend.WritePages' kernel, which takes the step's WritePlan."""
    return any(a.writes_by_plan and key.kernel for a, key in zip(
        self._AttentionMixers(), self.RaggedPlanKeys(cached_states)))

  def StepCounts(self, cached_states, geometry: ragged.StepGeometry) -> list:
    return ragged.StackStepCounts(self, cached_states, geometry,
                                  self.WritesWholePages(cached_states))

  def SharedKvReadLayers(self) -> int:
    """Layers that read pages they do not own."""
    return sum(reps for m, reps in self._Mixers()
               if getattr(m, "kv_owner", None) is False)

  def _Scan(self, b, theta, x, shared, per_repeat, call, sliced=True):
    """Block b as one scan over its repeats: `call(layer, theta_j, x,
    shared, j-th entry of every per-repeat tree, depth, repeat) -> (x,
    out_j, shared)`; returns (x, shared, [out_j stacked over repeats]).
    The variables a layer addresses in the stack by `repeat`
    (`StackAddressed`: an expert layer's matrices) are not scanned: a slice
    of them a trip would be a copy of them a trip. sliced False (the serving
    step): no variable is; a repeat's stays in its stack until a layer uses
    it (base_layer.StackSlice, RepeatedTransformerLayer.RaggedStep)."""
    layers = self._bodies[b]
    first = self._first_depth[b]
    whole = self._whole[b]
    block = theta[f"block_{b}"]

    def _Whole(path):
      return base_layer.PathKeys(path) in whole

    scanned = block
    if not sliced:
      scanned = None
    elif whole:
      scanned = jax.tree_util.tree_map_with_path(
          lambda path, leaf: jnp.zeros(leaf.shape[:1], leaf.dtype)
          if _Whole(path) else leaf, block)

    def _Body(carry, per):
      x, shared = carry
      theta_i, idx, extra = per
      if not sliced:
        theta_i = base_layer.SlicedTheta(block, idx, whole)
      elif whole:
        theta_i = jax.tree_util.tree_map_with_path(
            lambda path, mine, stack: stack if _Whole(path) else mine,
            theta_i, block)
      outs = []
      for j, layer in enumerate(layers):
        depth = first + idx * len(layers) + j
        x, out, shared = call(layer, theta_i.x_layers[j], x, shared, j,
                              extra, depth, idx)
        outs.append(out)
      return (x, shared), outs

    with observe.Scope("layer_scan"):
      (x, shared), outs = jax.lax.scan(
          _Body, (x, shared),
          (scanned, jnp.arange(self._repeats[b]), per_repeat))
    return x, shared, outs

  def FProp(self, theta, inputs, paddings=None, aux_vecs=None,
            aux_paddings=None, segment_ids=None, token_ids=None):
    del aux_vecs, aux_paddings, token_ids
    bsz, t = inputs.shape[:2]
    shared = NestedMap()
    if self._memory_dim:
      shared.memory = jnp.zeros((bsz, t, self._memory_dim), inputs.dtype)
    kv = [m for m, _ in self._Mixers() if getattr(m.p, "export_kv", False)]
    if kv:
      shape = (bsz, t, kv[0].p.num_kv_heads, kv[0]._h)
      shared.key = shared.value = jnp.zeros(shape, inputs.dtype)

    def _Call(layer, theta_j, x, shared, j, extra, depth, repeat):
      del j, extra
      x, shared = layer.FProp(theta_j, x, shared, paddings=paddings,
                              segment_ids=segment_ids, depth=depth,
                              repeat=repeat)
      return x, None, shared

    x = inputs
    for b in range(len(self._bodies)):
      x, shared, _ = self._Scan(b, theta, x, shared, None, _Call)
    return x

  def InitPagedStates(self, theta, num_pages, page_size, num_slots=0,
                      kv_cache_dtype=None):
    # the pool's leaves are the owning mixers' to declare (`PagePool`); the
    # stack keeps ONE pool, so they have to declare the same
    owners = [m for m, _ in self._Mixers() if getattr(m, "kv_owner", False)]
    assert owners, "BlockSequence serves a stack in which some layer owns pages"
    pool = owners[0].PagePool(num_pages, page_size, kv_cache_dtype)
    declared = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), pool)
    for m in owners[1:]:
      other = jax.eval_shape(
          lambda m=m: m.PagePool(num_pages, page_size, kv_cache_dtype))
      assert other == declared, f"one pool, one page: {other} / {declared}"
    states = NestedMap(kv_pool=pool)
    states.blocks = []
    for b, layers in enumerate(self._bodies):
      def _One(theta_i, layers=layers):
        return [l.InitPagedStates(theta_i.x_layers[j], num_slots)
                for j, l in enumerate(layers)]
      states.blocks.append(jax.vmap(_One)(theta[f"block_{b}"]))
    return states

  def RaggedStep(self, theta, inputs, cached_states, block_tables, rows,
                 ssm_col_states: bool = False):
    """block_tables: [owners, B, t_pages], one table an owning layer in
    stack order (KindPages.tables)."""
    if ssm_col_states:
      raise NotImplementedError(
          "BlockSequence keeps no per-column states: no draft source")
    assert block_tables.shape[0] == self._num_owners, (
        block_tables.shape, self._num_owners)
    shared = NestedMap(kv_pool=cached_states.kv_pool)
    if self._memory_dim:
      shared.memory = jnp.zeros(inputs.shape[:2] + (self._memory_dim,),
                                inputs.dtype)
    # built once, before the blocks' scans, and an invariant of each
    plan = attention_lib.BuildRaggedPlan(
        self.RaggedPlanKeys(cached_states), rows, *block_tables.shape[-2:],
        page_writes=self.WritesWholePages(cached_states))
    if plan is None:
      # a stack of retention layers: what their step derives from the rows
      planners = [m for m, _ in self._Mixers() if hasattr(m, "StepPlan")]
      if planners:
        plan = planners[0].StepPlan(
            rows, *block_tables.shape[-2:],
            page_size=cached_states.kv_pool.key.shape[1])
    x = inputs
    new_states = NestedMap(blocks=[])
    for b, layers in enumerate(self._bodies):
      tables_of = self._table_of[b]
      reps = self._repeats[b]
      own = [t for t in tables_of if t and t[0] == "own"]
      # the block's own tables by repeat, [repeats, owners a repeat, B, tp]
      mine = None
      if own:
        first = own[0][1]
        mine = block_tables[first:first + reps * len(own)].reshape(
            (reps, len(own)) + block_tables.shape[1:])

      # the leaves a layer addresses in the stack ride the scan's carry whole
      # (SharedStateLayer.StackStates); the others are its inputs and outputs
      whole = [l.StackStates() for l in layers]
      scanned = [NestedMap({k: v for k, v in st.items() if k not in names})
                 for st, names in zip(cached_states.blocks[b], whole)]
      if any(whole):
        shared = shared.Copy()
        shared.stack_states = [
            NestedMap({k: st[k] for k in names})
            for st, names in zip(cached_states.blocks[b], whole)]

      def _Call(layer, theta_j, x, shared, j, extra, depth, repeat,
                tables_of=tables_of, whole=whole):
        states_i, mine_i = extra
        place = tables_of[j]
        table = None
        if place is not None:
          table = (mine_i[place[2]] if place[0] == "own"
                   else block_tables[place[1]])
        states_j = states_i[j]
        if whole[j]:
          states_j = states_j.Copy()
          states_j.update(shared.stack_states[j])
        x, ns, shared = layer.RaggedStep(theta_j, x, states_j, shared,
                                         rows, table, depth, plan,
                                         repeat=repeat)
        if whole[j]:
          shared = shared.Copy()
          shared.stack_states = list(shared.stack_states)
          shared.stack_states[j] = NestedMap({k: ns[k] for k in whole[j]})
          ns = NestedMap({k: v for k, v in ns.items() if k not in whole[j]})
        return x, ns, shared

      x, shared, outs = self._Scan(
          b, theta, x, shared, (scanned, mine), _Call, sliced=False)
      if any(whole):
        for out, carried in zip(outs, shared.stack_states):
          out.update(carried)
        shared = NestedMap({k: v for k, v in shared.items()
                            if k != "stack_states"})
      new_states.blocks.append(outs)
    new_states.kv_pool = shared.kv_pool
    return x, new_states
