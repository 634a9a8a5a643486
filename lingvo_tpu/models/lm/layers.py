"""Language model task layers (ref: lingvo/tasks/lm/layers.py + gshard LMs).

TransformerLm: embedding + repeated/stacked transformer + tied softmax over
packed or plain batches. The flagship model family: DenseLm* configs
(ref `tasks/lm/params/synthetic_packed_input.py`) instantiate this with mesh
sharding annotations for tp/dp.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from lingvo_tpu import observe
from lingvo_tpu.core import base_model
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import py_utils
from lingvo_tpu.core import transformer as transformer_lib
from lingvo_tpu.core.nested_map import NestedMap


# hybrid_override_pattern's letters
PATTERN_KINDS = {"M": "mamba2", "E": "experts", "*": "gqa", "R": "retention"}


def KindBlocks(kinds) -> list[tuple[list, int]]:
  """A list of layer kinds as blocks in sequence, [(a block's kinds,
  repeats)]: from each position the shortest stretch that repeats at least
  twice, taken as often as it repeats (the longest run wins, the shorter
  period among equals); where nothing repeats, the one layer alone."""
  kinds, out, i = list(kinds), [], 0
  while i < len(kinds):
    best = (1, 1)
    for k in range(1, (len(kinds) - i) // 2 + 1):
      reps = 1
      while kinds[i + reps * k:i + (reps + 1) * k] == kinds[i:i + k]:
        reps += 1
      if reps > 1 and k * reps > best[0] * best[1]:
        best = (k, reps)
    out.append((kinds[i:i + best[0]], best[1]))
    i += best[0] * best[1]
  return out


class TransformerLm(base_model.BaseTask):
  """Decoder-only transformer LM.

  Input batch fields (packed format, ref pack_ops.cc producers):
    ids: [b, t] int32        labels: [b, t] int32
    paddings: [b, t] f32     (optional) segment_ids: [b, t] int32
    (optional) segment_pos: [b, t] int32
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.Define("vocab_size", 32000, "Vocabulary size.")
    p.Define("model_dim", 512, "Model dim.")
    p.Define("num_layers", 6, "Depth.")
    p.Define("num_heads", 8, "Heads.")
    p.Define("hidden_dim", 2048, "FFN inner dim.")
    p.Define("use_repeat_layer", True,
             "Scan-over-layers (True) vs distinct layers (False).")
    p.Define("remat_policy", "full",
             "Per-layer rematerialization under use_repeat_layer: 'full' | "
             "'dots' (save matmul outputs; ~4/3x fewer bwd flops than "
             "'full') | 'none'.")
    p.Define("atten_tpl", None, "Optional attention template override.")
    p.Define(
        "mixer_tpl", None,
        "Optional O(1)-state sequence-mixer template (e.g. "
        "ssm.GatedSSMLayer.Params()). When set, SSM layers replace "
        "attention according to mixer_atten_every_n; decode/serving "
        "contracts are unchanged (the mixer implements "
        "ExtendStep/Prefill/PagedStep with a fixed [B, N, H, S] state).")
    p.Define(
        "mixer_atten_every_n", 0,
        "Hybrid-stack layout with mixer_tpl: every n-th layer (layers n, "
        "2n, ... 1-indexed) keeps full attention, the rest run the mixer — "
        "e.g. 6 gives [ssm x5, attention] blocks. 0 = every layer runs "
        "the mixer (pure-SSM stack, pageless serving). Under "
        "use_repeat_layer, num_layers must divide by n (the block is the "
        "scanned repeat body).")
    p.Define(
        "layer_kinds", None,
        "The stack as data, one name a layer: 'mamba' / 'mamba_export' "
        "(mixer_tpl, an ssm.Mamba1Layer; the second also exports its scan "
        "output as the stack's memory), 'window' / 'full' / 'cross' "
        "(atten_tpl, an attention.DifferentialAttention: within "
        "sliding_window_size, over everything, or over everything through "
        "the pages of the 'full' layer before it, with no K and V of its "
        "own), 'gmu' (ssm.GatedMemoryUnit over that memory), 'retention' "
        "(mixer_tpl, a retention.PowerRetention: pages of its own for its "
        "open chunk and a slot state, rotated by rope_theta inside the "
        "mixer; atten_tpl may then be None): each of "
        "these a mixer and the dense feed-forward. A layer that is ONE "
        "branch alone: 'mamba2' (mixer_tpl, an ssm.Mamba2Layer, and no "
        "feed-forward), 'gqa' (atten_tpl, an attention.PooledAttention "
        "over everything that owns its pages, and no feed-forward), "
        "'experts' (expert_ffn_tpl, a core/moe.DroplessMoELayer whose "
        "router reads its own normed input, and no mixer), 'gqa_window' "
        "(as 'gqa', within sliding_window_size and rotated by rope_theta), "
        "'gqa_rope' (as 'gqa', over everything, rotated by rope_theta), "
        "'short_conv' (an ssm.ShortConvLayer: a gated short convolution "
        "whose state is its tail a slot; it reads no template). "
        "A name that says mixer and feed-forward apart, '<mixer>+dense' or "
        "'<mixer>+experts', is ONE layer of both: the mixer one of those "
        "that may stand alone ('mamba2', 'gqa', 'gqa_window', 'gqa_rope', "
        "'short_conv'), then the "
        "dense feed-forward (hidden_dim) or the expert layer, so leading "
        "dense layers before expert ones are ['gqa_window+dense', "
        "'gqa_window+experts', ...]. Stretches "
        "that repeat are scanned, what lies between them is a block of "
        "its own (transformer.BlockSequence); no layer but a 'retention', "
        "a 'gqa_window' and a 'gqa_rope' one carries a position. "
        "None = the layouts below.")
    p.Define(
        "hybrid_override_pattern", None,
        "layer_kinds as one letter a layer, for a stack of single-branch "
        "layers: 'M' = 'mamba2', 'E' = 'experts', '*' = 'gqa'; and 'R' = "
        "'retention', a mixer WITH the dense feed-forward. The stack "
        "is its first num_layers letters, so a cut of a published depth "
        "keeps the pattern's start. A published layer of TWO pre-norm "
        "branches (a mixer, then experts) is written as its two letters, "
        "'ME' or '*E': num_layers then counts branches, two a published "
        "layer (granite_hybrid.py). None = layer_kinds as given.")
    p.Define("use_rotary", True, "RoPE instead of absolute positions.")
    p.Define("rope_theta", 1e4,
             "RoPE base where a layer rotates. KV heads and a head size "
             "that is not model_dim / num_heads are atten_tpl's keys "
             "(atten_tpl.num_kv_heads, atten_tpl.dim_per_head).")
    p.Define("sliding_window_size", 0,
             "Keys a query of a window layer sees, its own included.")
    p.Define(
        "sliding_window_layout", None,
        "The layer pattern as data: a list of 0 / 1, 1 = the layer attends "
        "within sliding_window_size (attention.MultiHeadedAttention."
        "window), 0 = over everything before it. Its length divides "
        "num_layers and it repeats down the stack. None = no window layer.")
    p.Define(
        "rope_layout", None,
        "A list of 0 / 1 as sliding_window_layout: 1 = the layer rotates "
        "queries and keys (rope_theta), 0 = it carries no position at all. "
        "None = every layer as use_rotary says. Under use_repeat_layer the "
        "shortest period of both layouts is the scanned body (a "
        "StackedTransformerLayers of that depth).")
    p.Define("norm_tpl", None,
             "Norm template of both blocks of a layer and of the final norm "
             "(None = LayerNorm), e.g. layers.RmsNorm.Params().")
    p.Define(
        "expert_ffn_tpl", None,
        "If set (core/moe.DroplessMoELayer.Params()), every layer's "
        "feed-forward is this expert layer and nothing else; hidden_dim is "
        "then unused. Served by ServingLoop; the capacity-based "
        "num_experts interleave below is the layer that trains "
        "expert-parallel (docs/moe_collectives.md).")
    p.Define("tie_embeddings", True,
             "The softmax reads the embedding table; False = a head of its "
             "own, [vocab_size, model_dim].")
    p.Define("scale_emb_sqrt_depth", True,
             "Embeddings times sqrt(model_dim).")
    p.Define("embedding_multiplier", 1.0,
             "A constant on the looked-up embeddings (beside "
             "scale_emb_sqrt_depth).")
    p.Define("logits_scaling", 1.0,
             "A constant the logits are divided by (the head's, tied or "
             "not), before the cap.")
    p.Define("residual_multiplier", 1.0,
             "A constant on every branch's output before it is added to the "
             "stream, in a stack told by layer_kinds / "
             "hybrid_override_pattern (transformer.SharedStateLayer).")
    p.Define("post_norm", False,
             "Every branch's output goes through a norm of its own (norm_tpl) "
             "before the residual add, beside the norm on its input: "
             "h += PostLN(Branch(LN(h))), in a stack told by layer_kinds / "
             "hybrid_override_pattern.")
    p.Define(
        "kv_cache_dtype", None,
        "Decode KV-cache storage dtype for every attention layer in the "
        "stack (see attention.MultiHeadedAttention.kv_cache_dtype): "
        "None = fprop dtype (bit-exact legacy caches), 'bfloat16', or "
        "'int8' (quantize-on-write with per-token-per-head scales). "
        "Serving can also override per-engine via "
        "InitPagedDecodeState(..., kv_cache_dtype=...).")
    p.Define("bidirectional", False,
             "No causal mask (BERT-style encoder; pair with an MLM task).")
    p.Define("label_smoothing", 0.0, "Label smoothing.")
    p.Define("softmax_logits_soft_max", 30.0, "Logit tanh cap (gshard-style).")
    p.Define("xent_block_size", 0,
             "If >0, train/eval loss runs the fused blockwise LM-head "
             "xent (ops/fused_xent.py) this many vocab entries at a time: "
             "ComputePredictions returns the final hidden instead of "
             "logits and the [B, T, V] logits tensor is never "
             "materialized in either direction (the peak train-step "
             "activation at vocab >= 32k). 0 = exact legacy dense path. "
             "Decode (ExtendStep/Prefill) is unaffected.")
    p.Define("softmax_num_sampled", 0,
             "If >0, train with a sampled softmax over this many log-uniform "
             "negatives (untied output head; the word-level 793k-vocab "
             "1B-words recipe). Eval still uses the full softmax.")
    p.Define("residual_dropout_prob", 0.0, "Residual dropout.")
    p.Define("atten_dropout_prob", 0.0, "Attention dropout.")
    p.Define("num_experts", 0,
             "If >0, GShard MoE: alternate dense and MoE layers "
             "(num_layers must be even; scanned as dense+MoE blocks).")
    p.Define("moe_hidden_dim", 0, "Expert FFN dim (0 = hidden_dim).")
    p.Define("moe_num_groups", 1, "Gating groups.")
    p.Define("moe_capacity_factor", 2.0, "Expert capacity factor.")
    p.Define("moe_aux_loss_weight", 0.01, "Load-balance loss weight.")
    p.Define("moe_second_expert_policy", "all", "'all' or 'random'.")
    p.Define("moe_gating_policy", "top2",
             "'top2' (learned), 'sinkhorn' (balanced top-1), or 'hash' "
             "(route by token-id hash).")
    p.Define("moe_dispatch_method", "auto",
             "MoE dispatch formulation: 'auto' | 'indexed' | 'einsum' "
             "(see gshard.MoEFeedForwardLayer).")
    p.Define("moe_dispatch_via_shard_map", None,
             "None = auto (explicit shard_map all_to_all whenever an "
             "'expert' mesh axis exists); True/False forces the path "
             "(see gshard.MoEFeedForwardLayer.dispatch_via_shard_map).")
    return p

  def __init__(self, params):
    super().__init__(params)
    p = self.p
    self.CreateChild(
        "emb",
        layers_lib.SharedEmbeddingSoftmaxLayer.Params().Set(
            vocab_size=p.vocab_size, embedding_dim=p.model_dim,
            logits_soft_max=p.softmax_logits_soft_max,
            xent_block_size=p.xent_block_size,
            scale_sqrt_depth=p.scale_emb_sqrt_depth,
            embedding_multiplier=p.embedding_multiplier,
            logits_divisor=p.logits_scaling,
            weight_split_dims_mapping=("model", None)))
    # the fused blockwise xent and the sampled softmax read the table
    # themselves and know no divisor
    assert p.logits_scaling == 1.0 or (
        p.softmax_num_sampled == 0 and p.xent_block_size == 0)
    if not p.tie_embeddings:
      assert p.softmax_num_sampled == 0 and p.xent_block_size == 0
      self.CreateChild("head", self.emb.p.Copy())
    if not p.use_rotary:
      self.CreateChild(
          "pos_emb",
          layers_lib.PositionalEmbeddingLayer.Params().Set(
              embedding_dim=p.model_dim))

    if p.hybrid_override_pattern is not None:
      assert p.layer_kinds is None
      assert p.num_layers <= len(p.hybrid_override_pattern), p.num_layers
      self.CreateChild("stack", self._KindStack(
          [PATTERN_KINDS[c]
           for c in p.hybrid_override_pattern[:p.num_layers]]))
    elif p.layer_kinds is not None:
      self.CreateChild("stack", self._KindStack(p.layer_kinds))
    else:
      self._CreateLayoutStack()
    if p.softmax_num_sampled > 0:
      assert p.xent_block_size == 0, (
          "sampled softmax and the fused blockwise xent are both "
          "no-[B,T,V]-logits training paths; pick one")
      assert p.label_smoothing == 0.0, (
          "label_smoothing is not supported with the sampled softmax "
          "(the sampled xent has no smoothing term)")
      self.CreateChild(
          "sampled_softmax",
          layers_lib.SampledSoftmax.Params().Set(
              input_dim=p.model_dim, num_classes=p.vocab_size,
              num_sampled=p.softmax_num_sampled))
    self.CreateChild(
        "final_ln",
        (p.norm_tpl or layers_lib.LayerNorm.Params()).Copy().Set(
            input_dim=p.model_dim))

  def _KindStack(self, layer_kinds):
    """Params of the stack `layer_kinds` describes."""
    from lingvo_tpu.core import ssm as ssm_lib
    p = self.p
    assert len(layer_kinds) == p.num_layers, (layer_kinds, p.num_layers)
    # a name's two halves: its mixer and, behind a '+', its feed-forward
    halves = [kind.partition("+")[::2] for kind in layer_kinds]
    kinds = {mixer for mixer, _ in halves}
    has_experts = any("experts" in h for h in halves)
    # the templates a kind reads
    reads_atten = {"window", "full", "cross", "gqa", "gqa_window", "gqa_rope"}
    assert p.mixer_tpl is not None or kinds <= reads_atten | {
        "experts", "short_conv"}, kinds
    assert p.atten_tpl is not None or not kinds & reads_atten, kinds
    assert p.num_experts == 0
    assert (p.expert_ffn_tpl is not None) == has_experts
    assert not p.bidirectional
    atten = (p.atten_tpl.Copy().Set(num_heads=p.num_heads)
             if p.atten_tpl is not None else None)
    # a mixer with the dense feed-forward after it
    mixers = {
        "retention": lambda: p.mixer_tpl.Copy().Set(
            num_heads=p.num_heads, rope_theta=p.rope_theta),
        "mamba": lambda: p.mixer_tpl.Copy().Set(export_memory=False),
        "mamba_export": lambda: p.mixer_tpl.Copy().Set(export_memory=True),
        "window": lambda: atten.Copy().Set(window=p.sliding_window_size),
        "full": lambda: atten.Copy().Set(window=0, export_kv=True),
        "cross": lambda: atten.Copy().Set(window=0, kv_owner=False),
        "gmu": lambda: ssm_lib.GatedMemoryUnit.Params().Set(
            memory_dim=p.mixer_tpl.expand * p.model_dim),
    }
    # one branch alone, or with the feed-forward its name says behind a '+'
    alone = {
        "mamba2": lambda: p.mixer_tpl.Copy(),
        "gqa": lambda: atten.Copy().Set(window=0),
        "gqa_window": lambda: atten.Copy().Set(
            window=p.sliding_window_size, use_rotary_position_emb=True,
            rope_max_timescale=p.rope_theta),
        "gqa_rope": lambda: atten.Copy().Set(
            window=0, use_rotary_position_emb=True,
            rope_max_timescale=p.rope_theta),
        "short_conv": ssm_lib.ShortConvLayer.Params,
    }
    assert p.sliding_window_size > 0 or not kinds & {"window", "gqa_window"}
    layer = transformer_lib.SharedStateLayer.Params().Set(
        residual_multiplier=p.residual_multiplier)
    layer.tr_fflayer_tpl.Set(
        hidden_dim=p.hidden_dim, activation="SILU", use_gated_activation=True,
        has_bias=False, residual_dropout_prob=p.residual_dropout_prob)
    experts = p.expert_ffn_tpl.Copy() if has_experts else None
    if p.norm_tpl is not None:
      layer.norm_tpl = p.norm_tpl.Copy()
      for ff in (layer.tr_fflayer_tpl, experts):
        if ff is not None:
          ff.norm_tpl = p.norm_tpl.Copy()
    if p.post_norm:
      post = (p.norm_tpl or layers_lib.LayerNorm.Params()).Copy()
      layer.post_norm_tpl = post.Copy()
      for ff in (layer.tr_fflayer_tpl, experts):
        if ff is not None:
          ff.post_norm_tpl = post.Copy()

    def _Layer(mixer, ff):
      if ff:
        assert mixer in alone and ff in ("dense", "experts"), (mixer, ff)
        return layer.Copy().Set(
            mixer_tpl=alone[mixer](),
            tr_fflayer_tpl=(layer.tr_fflayer_tpl if ff == "dense"
                            else experts).Copy())
      if mixer in mixers:
        return layer.Copy().Set(mixer_tpl=mixers[mixer]())
      if mixer in alone:
        return layer.Copy().Set(mixer_tpl=alone[mixer](), tr_fflayer_tpl=None)
      assert mixer == "experts", mixer
      return layer.Copy().Set(mixer_tpl=None, tr_fflayer_tpl=experts.Copy())

    blocks = [([_Layer(*kind.partition("+")[::2]) for kind in kinds], reps)
              for kinds, reps in KindBlocks(layer_kinds)]
    return transformer_lib.BlockSequence.Params().Set(
        input_dim=p.model_dim, blocks=blocks)

  def _CreateLayoutStack(self):
    p = self.p
    assert p.residual_multiplier == 1.0 and not p.post_norm, (
        "residual_multiplier and post_norm are a SharedStateLayer's "
        "(layer_kinds / hybrid_override_pattern)")
    layer_body = transformer_lib.TransformerLayer.Params().Set(
        input_dim=p.model_dim, num_heads=p.num_heads,
        hidden_dim=p.hidden_dim, mask_self_atten=not p.bidirectional)
    atten_tpl = p.atten_tpl
    if atten_tpl is not None:
      layer_body.tr_atten_tpl.atten_tpl = atten_tpl.Copy()
    layer_body.tr_atten_tpl.atten_tpl.use_rotary_position_emb = p.use_rotary
    layer_body.tr_atten_tpl.atten_tpl.rope_max_timescale = p.rope_theta
    if layer_body.tr_atten_tpl.atten_tpl.dim_per_head:
      layer_body.tr_atten_tpl.atten_tpl.hidden_dim = (
          p.num_heads * layer_body.tr_atten_tpl.atten_tpl.dim_per_head)
    if p.norm_tpl is not None:
      layer_body.tr_atten_tpl.norm_tpl = p.norm_tpl.Copy()
      layer_body.tr_fflayer_tpl.norm_tpl = p.norm_tpl.Copy()
    layer_body.tr_atten_tpl.atten_tpl.kv_cache_dtype = p.kv_cache_dtype
    layer_body.tr_atten_tpl.atten_tpl.atten_dropout_prob = p.atten_dropout_prob
    layer_body.tr_atten_tpl.atten_tpl.weight_split_dims_mapping = (
        None, "model", None)
    layer_body.tr_atten_tpl.residual_dropout_prob = p.residual_dropout_prob
    layer_body.tr_fflayer_tpl.residual_dropout_prob = p.residual_dropout_prob
    layer_body.tr_fflayer_tpl.weight_split_dims_mapping = (None, "model")
    if p.expert_ffn_tpl is not None:
      assert p.num_experts == 0 and p.mixer_tpl is None
      layer_body.tr_fflayer_tpl = p.expert_ffn_tpl.Copy()
      if p.norm_tpl is not None:
        layer_body.tr_fflayer_tpl.norm_tpl = p.norm_tpl.Copy()
      layer_body.hidden_dim = 0

    period = self._PatternPeriod()
    if period is not None and len(period) == 1:
      # the layouts say the same of every layer: the plain body, so set
      (windowed, rotates), period = period[0], None
      layer_body.tr_atten_tpl.atten_tpl.Set(
          window=p.sliding_window_size if windowed else 0,
          use_rotary_position_emb=bool(rotates))

    ssm_body = None
    if p.mixer_tpl is not None:
      assert p.num_experts == 0, (
          "hybrid SSM stacks don't compose with the MoE interleave yet")
      assert not p.bidirectional, (
          "GatedSSMLayer is causal; bidirectional stacks keep attention")
      mixer_tpl = p.mixer_tpl.Copy()
      mixer_tpl.weight_split_dims_mapping = (None, "model", None)
      ssm_body = layer_body.Copy().Set(mixer_tpl=mixer_tpl)
      if p.mixer_atten_every_n == 1:
        # attention at EVERY layer: the hybrid degenerates to the plain
        # attention stack and the mixer template is never instantiated
        ssm_body = None

    if p.num_experts > 0:
      from lingvo_tpu.parallel import gshard
      assert p.num_layers % 2 == 0, "MoE interleave needs even num_layers"
      moe_tpl = gshard.MoETransformerLayer.Params()
      moe_tpl.tr_atten_tpl = layer_body.tr_atten_tpl.Copy()
      moe_tpl.moe_tpl = gshard.MoEFeedForwardLayer.Params().Set(
          hidden_dim=p.moe_hidden_dim or p.hidden_dim,
          num_experts=p.num_experts,
          num_groups=p.moe_num_groups,
          capacity_factor=p.moe_capacity_factor,
          aux_loss_weight=p.moe_aux_loss_weight,
          second_expert_policy=p.moe_second_expert_policy,
          gating_policy=p.moe_gating_policy,
          dispatch_method=p.moe_dispatch_method,
          dispatch_via_shard_map=p.moe_dispatch_via_shard_map,
          residual_dropout_prob=p.residual_dropout_prob)
      block = gshard.DenseMoEBlock.Params().Set(
          input_dim=p.model_dim, num_heads=p.num_heads,
          dense_tpl=layer_body, moe_tpl=moe_tpl)
      self.CreateChild(
          "stack",
          transformer_lib.RepeatedTransformerLayer.Params().Set(
              num_layers=p.num_layers // 2, body=block,
              remat_policy=p.remat_policy))
    elif ssm_body is not None and p.mixer_atten_every_n > 1:
      # Hybrid stack: attention at layers n, 2n, ... (1-indexed), SSM
      # elsewhere — [ssm x (n-1), attention] blocks.
      n = p.mixer_atten_every_n
      assert p.num_layers % n == 0, (p.num_layers, n)
      if p.use_repeat_layer:
        # Scan one heterogeneous block of depth n: a Stacked body with
        # explicit per-layer templates (same trick as the MoE
        # DenseMoEBlock, built from stock parts).
        block = transformer_lib.StackedTransformerLayers.Params().Set(
            num_layers=n, input_dim=p.model_dim,
            layer_tpls=[ssm_body.Copy() for _ in range(n - 1)]
            + [layer_body.Copy()],
            final_ln=False)
        self.CreateChild(
            "stack",
            transformer_lib.RepeatedTransformerLayer.Params().Set(
                num_layers=p.num_layers // n, body=block,
                remat_policy=p.remat_policy))
      else:
        tpls = [
            layer_body.Copy() if (i + 1) % n == 0 else ssm_body.Copy()
            for i in range(p.num_layers)
        ]
        self.CreateChild(
            "stack",
            transformer_lib.StackedTransformerLayers.Params().Set(
                num_layers=p.num_layers, input_dim=p.model_dim,
                layer_tpls=tpls, final_ln=False))
    elif period is not None:
      # window and full layers, rotating and position-free ones, by the
      # layouts: one period is the scanned body, as the SSM hybrid's block
      assert ssm_body is None, "a layer pattern composes with attention only"
      depth = len(period) if p.use_repeat_layer else p.num_layers
      tpls = []
      for i in range(depth):
        windowed, rotates = period[i % len(period)]
        tpl = layer_body.Copy()
        tpl.tr_atten_tpl.atten_tpl.Set(
            window=p.sliding_window_size if windowed else 0,
            use_rotary_position_emb=bool(rotates))
        tpls.append(tpl)
      block = transformer_lib.StackedTransformerLayers.Params().Set(
          num_layers=depth, input_dim=p.model_dim, layer_tpls=tpls,
          final_ln=False)
      if p.use_repeat_layer:
        block = transformer_lib.RepeatedTransformerLayer.Params().Set(
            num_layers=p.num_layers // depth, body=block,
            remat_policy=p.remat_policy)
      self.CreateChild("stack", block)
    elif p.use_repeat_layer:
      self.CreateChild(
          "stack",
          transformer_lib.RepeatedTransformerLayer.Params().Set(
              num_layers=p.num_layers, body=ssm_body or layer_body,
              remat_policy=p.remat_policy))
    else:
      self.CreateChild(
          "stack",
          transformer_lib.StackedTransformerLayers.Params().Set(
              num_layers=p.num_layers, input_dim=p.model_dim,
              transformer_layer_params_tpl=ssm_body or layer_body,
              final_ln=False))

  def _PatternPeriod(self):
    """[(windowed, rotates)] over the shortest period of the two layouts;
    None where there is no layout."""
    p = self.p
    if p.sliding_window_layout is None and p.rope_layout is None:
      return None
    n = p.num_layers

    def _Tiled(layout, default):
      if layout is None:
        return [default] * n
      assert n % len(layout) == 0, (n, layout)
      return [int(x) for x in layout] * (n // len(layout))

    windowed = _Tiled(p.sliding_window_layout, 0)
    assert not any(windowed) or p.sliding_window_size > 0
    layers = list(zip(windowed, _Tiled(p.rope_layout, int(p.use_rotary))))
    for k in range(1, n + 1):
      if n % k == 0 and layers == layers[:k] * (n // k):
        return layers[:k]

  def _Head(self, theta, x):
    """[..., D] -> [..., V] logits: the tied table, or the head's own."""
    if self.p.tie_embeddings:
      return self.emb.Logits(theta.emb, x)
    return self.head.Logits(theta.head, x)

  # -- forward ---------------------------------------------------------------

  def Inference(self):
    """'score' subgraph for serving export (ref base_model.Inference:943):
    (ids, paddings) -> per-position log-probs + per-token xent-style score.
    Shapes come from the task's input params when attached (re-export after
    editing them to serve other lengths)."""
    p = self.p
    t = getattr(getattr(p, "input", None), "seq_len", None) or 64
    example = NestedMap(
        ids=jnp.zeros((1, t), jnp.int32),
        paddings=jnp.zeros((1, t), jnp.float32))

    def score_fn(theta, inputs):
      with py_utils.EvalContext():
        preds = self.ComputePredictions(theta, inputs)
      logits = self._FullLogits(theta, preds)
      log_probs = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
      return NestedMap(log_probs=log_probs)

    return {"score": (score_fn, example)}

  def _FullLogits(self, theta, predictions):
    """Dense [..., V] logits from a predictions map — the fallback for
    consumers that genuinely need the full distribution (serving export)
    when the fused-xent gate deferred them."""
    if "logits" in predictions:
      return predictions.logits
    return self._Head(theta, predictions.hidden)

  def ComputePredictions(self, theta, input_batch):
    p = self.p
    ids = input_batch.ids
    # named scopes at the block boundaries: op_name in a profiler trace
    with observe.Scope("embed"):
      x = self.emb.EmbLookup(theta.emb, ids)
      if not p.use_rotary:
        pos = input_batch.Get("segment_pos")
        if pos is not None:
          pe = self.pos_emb.FProp(NestedMap(),
                                  position=pos.astype(jnp.float32))
        else:
          pe = self.pos_emb.FProp(NestedMap(), seq_length=ids.shape[1])[None]
        x = x + pe.astype(x.dtype)
    seg_ids = input_batch.Get("segment_ids")
    x = self.stack.FProp(theta.stack, x, paddings=input_batch.paddings,
                         segment_ids=seg_ids, token_ids=ids)
    with observe.Scope("norm"):
      x = self.final_ln.FProp(theta.final_ln, x)
    if p.softmax_num_sampled > 0 and not py_utils.DoEval() and \
        py_utils.HasStepSeed():
      # training with a sampled softmax: defer to ComputeLoss (no [B,T,V]
      # logits are ever materialized — the point for 793k vocabs)
      return NestedMap(hidden=x)
    if p.xent_block_size > 0:
      # fused blockwise xent: ComputeLoss / ScoreSequences stream the
      # vocab; only full-distribution consumers (_FullLogits) pay for
      # dense logits
      return NestedMap(hidden=x)
    with observe.Scope("head_loss"):
      logits = self._Head(theta, x) if p.softmax_num_sampled == 0 \
          else self.sampled_softmax.Logits(
              self.ChildTheta(theta, "sampled_softmax"), x)
    return NestedMap(logits=logits)

  def ComputeLoss(self, theta, predictions, input_batch):
    with observe.Scope("head_loss"):
      return self._ComputeLoss(theta, predictions, input_batch)

  def _ComputeLoss(self, theta, predictions, input_batch):
    p = self.p
    weights = py_utils.SequenceMask(input_batch.paddings)
    tot_weight = jnp.maximum(jnp.sum(weights), 1e-8)
    if "hidden" in predictions and p.softmax_num_sampled > 0:
      per_tok = self.sampled_softmax.XentLossFromInputs(
          self.ChildTheta(theta, "sampled_softmax"), predictions.hidden,
          input_batch.labels)
      avg_xent = jnp.sum(per_tok * weights) / tot_weight
      metrics = NestedMap(
          loss=(avg_xent, tot_weight),
          log_pplx=(avg_xent, tot_weight),
          num_predictions=(tot_weight, 1.0))
      return metrics, NestedMap(xent=per_tok)
    if "hidden" in predictions:
      # fused blockwise xent over the tied table: per-token loss AND the
      # argmax metric come out of the streaming pass — [B, T, V] logits
      # are never live in either direction
      out = self.emb.FProp(theta.emb, predictions.hidden,
                           class_ids=input_batch.labels,
                           label_smoothing=p.label_smoothing)
      correct = (out.argmax == input_batch.labels)
    else:
      out = self.emb.XentLossFromLogits(
          predictions.logits, class_ids=input_batch.labels,
          label_smoothing=p.label_smoothing)
      correct = (jnp.argmax(predictions.logits, -1) == input_batch.labels)
    avg_xent = jnp.sum(out.per_example_xent * weights) / tot_weight
    metrics = NestedMap(
        loss=(avg_xent, tot_weight),
        log_pplx=(avg_xent, tot_weight),
        fraction_of_correct_next_step_preds=(
            jnp.sum(correct * weights) / tot_weight, tot_weight),
        num_predictions=(tot_weight, 1.0))
    per_example = NestedMap(xent=out.per_example_xent)
    return metrics, per_example

  def ScoreSequences(self, theta, input_batch):
    """Per-position label log-probs for given target sequences.

    input_batch: NestedMap with ids/labels/paddings (the training batch
    format). Returns NestedMap(label_log_probs [b, t] f32, weights
    [b, t]) — log P(labels[t] | ids[<=t]) at non-padded positions.

    With the fused gate on (p.xent_block_size > 0) the score comes out of
    the blockwise streaming pass; the legacy path is the f32 log-softmax
    over dense logits. Both agree to float tolerance.
    """
    with py_utils.EvalContext():
      preds = self.ComputePredictions(theta, input_batch)
    if "hidden" in preds and self.p.softmax_num_sampled == 0:
      out = self.emb.FProp(theta.emb, preds.hidden,
                           class_ids=input_batch.labels)
      log_probs = out.label_log_probs
    else:
      logits = self._FullLogits(theta, preds)
      lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
      log_probs = jnp.take_along_axis(
          lp, input_batch.labels[..., None].astype(jnp.int32), -1)[..., 0]
    return NestedMap(label_log_probs=log_probs,
                     weights=py_utils.SequenceMask(input_batch.paddings))

  # -- decode (sampling; beam search comes from core/beam_search) ------------

  def InitDecodeState(self, theta, batch_size, max_len):
    return self.stack.InitStates(theta.stack, batch_size, max_len)

  def ExtendStep(self, theta, ids_t, states, cache_paddings=None):
    """ids_t: [b, 1] -> (logits [b, vocab], new states).

    cache_paddings: optional [b, max_len] — 1.0 marks KV-cache slots that
    must never be attended (left-padding of right-aligned variable-length
    prompts in gshard_decode).
    """
    x = self.emb.EmbLookup(theta.emb, ids_t)
    x, new_states = self.stack.ExtendStep(theta.stack, x, states,
                                          cache_paddings=cache_paddings)
    x = self.final_ln.FProp(theta.final_ln, x)
    if self.p.softmax_num_sampled > 0:
      # decode must score with the head that was TRAINED (the untied
      # sampled-softmax head), not the tied embedding
      logits = self.sampled_softmax.Logits(
          self.ChildTheta(theta, "sampled_softmax"), x)
    else:
      logits = self._Head(theta, x)
    return logits[:, 0, :], new_states

  def Prefill(self, theta, ids, states, cache_paddings=None, live_len=None):
    """Chunked prefill: ids [b, c] -> (logits [b, c, vocab], new states).

    live_len: optional static bound (>= time_step + c) on how many cache
    slots the attention read touches — see MultiHeadedAttention.Prefill.

    Primes cache slots [time_step, time_step + c) with ONE batched
    attention pass per layer instead of c sequential ExtendStep calls —
    the prompt phase goes from O(prompt_len) full-cache attention calls to
    O(prompt_len / chunk). Written K/V is bit-identical to the per-token
    path; logits match it to float tolerance. Mirrors ExtendStep's
    position handling (rotary positions are the global slot indices;
    like ExtendStep — and unlike training FProp — NO absolute pos_emb is
    added for use_rotary=False models, whose decode has always been
    position-blind: absolute positions are ill-defined under the
    right-aligned ragged-prompt serving layout. Serve rotary models.)
    """
    x = self.emb.EmbLookup(theta.emb, ids)
    x, new_states = self.stack.Prefill(theta.stack, x, states,
                                       cache_paddings=cache_paddings,
                                       live_len=live_len)
    x = self.final_ln.FProp(theta.final_ln, x)
    if self.p.softmax_num_sampled > 0:
      logits = self.sampled_softmax.Logits(
          self.ChildTheta(theta, "sampled_softmax"), x)
    else:
      logits = self._Head(theta, x)
    return logits, new_states

  def InitPagedDecodeState(self, theta, num_pages: int, page_size: int,
                           num_slots: int = 0,
                           kv_cache_dtype: str | None = None):
    """Global KV page pool for the continuous-batching serving engine.

    Unlike InitDecodeState there is no batch/max_len shape — capacity is
    num_pages * page_size slots shared by however many sequences the
    engine's block tables map into it (serving/engine.py owns the layout;
    it passes allocator pages + 1 so the last page is the trash page).
    num_slots: the engine's slot count, required by O(1)-state mixer
    layers (one fixed [N, H, S] state per slot); attention layers ignore
    it. kv_cache_dtype overrides p.kv_cache_dtype for this pool (a static
    string — engines pass it as a jit static arg); PagedStep needs no
    matching flag, it detects the quantized pool from the scale sidecars
    in the state."""
    return self.stack.InitPagedStates(theta.stack, num_pages, page_size,
                                      num_slots=num_slots,
                                      kv_cache_dtype=kv_cache_dtype)

  def PagedStep(self, theta, ids, states, block_tables, q_pos, in_len,
                ssm_col_states: bool = False):
    """Continuous-batching step: ids [b, c] -> (logits [b, c, vocab],
    states).

    Row b's tokens land at its sequence's global slots
    [q_pos[b], q_pos[b] + in_len[b]) through block_tables [b, t_pages];
    c == 1 is a pure decode step, c > 1 a mixed prefill/decode step
    (decode rows use in_len == 1, padding queries past in_len are
    discarded by the engine). Same position policy as Prefill: rotary
    positions are the global slot indices, no absolute pos_emb (serve
    rotary models).

    ssm_col_states: speculative-verify mode — every O(1)-state mixer in
    the stack also returns its per-column state trajectory (`col_states`)
    so the serving engine can roll rejected draft suffixes back
    (serving/spec_decode.py selects the accepted column and strips the
    extra leaf before the states re-enter the engine).
    """
    x = self.emb.EmbLookup(theta.emb, ids)
    x, new_states = self.stack.PagedStep(theta.stack, x, states,
                                         block_tables, q_pos, in_len,
                                         ssm_col_states=ssm_col_states)
    x = self.final_ln.FProp(theta.final_ln, x)
    if self.p.softmax_num_sampled > 0:
      logits = self.sampled_softmax.Logits(
          self.ChildTheta(theta, "sampled_softmax"), x)
    else:
      logits = self._Head(theta, x)
    return logits, new_states

  def RaggedStep(self, theta, ids, states, block_tables, rows,
                 ssm_col_states: bool = False, head_cols=None):
    """Packed-token continuous-batching step: ids [1, T] ->
    (logits [1, T, vocab], states).

    The ONE compiled serving program: token t belongs to engine slot
    rows.row_of[t] at global kv slot rows.pos[t] (core/ragged.py
    RaggedRows) — a decode row is 1 token, a prefill chunk several with
    ascending positions, a spec-verify window row_k + 1, and padding
    tokens (rows.valid == False) emit garbage logits the engine never
    samples from. Position policy matches PagedStep: rotary positions are
    the global slot indices, no absolute pos_emb (serve rotary models).
    ssm_col_states as in PagedStep (per-column state trajectories for
    spec-verify rollback, shaped [B, wmax, ...] here).
    head_cols: [n] int32 indices into the packed token axis, the columns
    whose logits something reads. The stack runs over all T tokens (every
    one writes its K/V or advances its row's state); the final norm and the
    head run over these n alone, and the logits are [1, n, vocab], column
    head_cols[i]'s at i. None: all T, in packed order.
    """
    with observe.Scope("embed"):
      x = self.emb.EmbLookup(theta.emb, ids)
    x, new_states = self.stack.RaggedStep(theta.stack, x, states,
                                          block_tables, rows,
                                          ssm_col_states=ssm_col_states)
    with observe.Scope("norm"):
      if head_cols is not None:
        x = jnp.take(x, head_cols, axis=1)
      x = self.final_ln.FProp(theta.final_ln, x)
    with observe.Scope("head_sample"):
      if self.p.softmax_num_sampled > 0:
        logits = self.sampled_softmax.Logits(
            self.ChildTheta(theta, "sampled_softmax"), x)
      else:
        logits = self._Head(theta, x)
    return logits, new_states

  def PagedStepPrefix(self, theta, ids, states, block_tables, q_pos, in_len,
                      num_layers: int):
    """Early-exit PagedStep: run only the first num_layers of the stack,
    then the full final_ln + logits head — the self-speculation draft
    pass (serving/spec_decode.py). The returned states carry the prefix
    layers' writes with the suffix passed through (same pytree as
    PagedStep); callers treat them as TRANSIENT — draft steps are never
    committed, the verify step re-writes every position it keeps."""
    x = self.emb.EmbLookup(theta.emb, ids)
    x, new_states = self.stack.PagedStepPrefix(theta.stack, x, states,
                                               block_tables, q_pos, in_len,
                                               num_layers)
    x = self.final_ln.FProp(theta.final_ln, x)
    if self.p.softmax_num_sampled > 0:
      logits = self.sampled_softmax.Logits(
          self.ChildTheta(theta, "sampled_softmax"), x)
    else:
      logits = self._Head(theta, x)
    return logits, new_states


class BertLm(TransformerLm):
  """Masked-LM pretraining task (ref `tasks/lm/params/wiki_bert.py` +
  `tasks/lm/layers.py` MLM usage): bidirectional encoder, loss only on
  masked positions.

  Batch fields: ids (with mask tokens applied), labels (original ids),
  masked_weights [b, t] (1.0 where a prediction is scored), paddings.
  """

  @classmethod
  def Params(cls):
    p = super().Params()
    p.bidirectional = True
    p.use_rotary = False  # BERT uses absolute positions
    return p

  def ComputeLoss(self, theta, predictions, input_batch):
    p = self.p
    assert p.softmax_num_sampled == 0, (
        "BertLm has no sampled-softmax loss; use xent_block_size for a "
        "no-[B,T,V] MLM head")
    if "hidden" in predictions:
      # fused blockwise xent (p.xent_block_size > 0): loss + accuracy
      # without [B, T, V] logits
      out = self.emb.FProp(theta.emb, predictions.hidden,
                           class_ids=input_batch.labels,
                           label_smoothing=p.label_smoothing)
      correct = (out.argmax == input_batch.labels)
    else:
      out = self.emb.XentLossFromLogits(
          predictions.logits, class_ids=input_batch.labels,
          label_smoothing=p.label_smoothing)
      correct = (jnp.argmax(predictions.logits, -1) == input_batch.labels)
    weights = input_batch.masked_weights * py_utils.SequenceMask(
        input_batch.paddings)
    tot_weight = jnp.maximum(jnp.sum(weights), 1e-8)
    avg_xent = jnp.sum(out.per_example_xent * weights) / tot_weight
    acc = jnp.sum(correct * weights) / tot_weight
    metrics = NestedMap(
        loss=(avg_xent, tot_weight),
        mlm_log_pplx=(avg_xent, tot_weight),
        mlm_accuracy=(acc, tot_weight),
        num_predictions=(tot_weight, 1.0))
    return metrics, NestedMap(xent=out.per_example_xent)
