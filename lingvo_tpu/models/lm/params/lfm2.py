"""LFM2-24B-A2B (LiquidAI, `model_type` lfm2_moe, 24B parameters of which
about 2B a token) as Params of `TransformerLm`.

https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json:
40 layers of model dim 2048, each a mixer and a feed-forward, both pre-normed
(RMSNorm, eps 1e-5):

    h <- h + Mixer(LN(h));  h <- h + FF(LN(h))

- mixer, 30 layers of 40 (`layer_types` "conv"): a gated short convolution,
  [B; C; X] = x W_in (2048 -> 6144), u = B * X, three causal depthwise taps
  over u with no bias and no activation, y = C * conv, out = y W_out; what a
  sequence carries is the last two rows of u;
- mixer, published layers 2, 6, ..., 38 ("full_attention"): 32 query heads
  over 8 KV heads of 64, no bias; q and k each through an RMSNorm over the
  head's 64 dims (a learned scale of 64, one for q, one for k) BEFORE the
  rotation; RoPE over the whole head at theta 1e6; causal over everything;
- feed-forward, layers 0-1: SwiGLU of width 11,776;
- feed-forward, layers 2-39: 64 SwiGLU experts of width 1,536, 4 a token,
  scores sigmoid(router logits) in f32 from the branch's own normed input,
  the 4 chosen by score + a stored per-expert bias (which chooses and does
  not weigh), weights the chosen scores over their sum; no shared expert.

The attention layers do not line up with the dense / expert boundary (both
dense layers are convolution layers), so the stack is `layer_kinds`, one name
a layer. Embedding and head are tied, the embedding is not scaled, vocabulary
65,536; no logit scaling and no cap.

Every key below is a key of `TransformerLm.Params()` or of the templates it
lays out; the serving engine takes the task as it takes any other.
"""

from __future__ import annotations

import jax.numpy as jnp

from lingvo_tpu import model_registry
from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import moe as moe_lib
from lingvo_tpu.models.lm.params import synthetic_packed_input


def LayerKinds(num_layers: int, num_dense: int = 2,
               atten_every: int = 4, first_atten: int = 2) -> list[str]:
  """The published rule (`layer_types`, `num_dense_layers`): layers 2, 6,
  10, ... attend, the others are convolution layers; the first `num_dense`
  feed-forwards are dense, the rest experts."""
  return [("gqa_rope" if l % atten_every == first_atten else "short_conv")
          + ("+dense" if l < num_dense else "+experts")
          for l in range(num_layers)]


def StageKinds(periods: int = 2, atten_every: int = 4) -> list[str]:
  """What the first pipeline stage of whole periods holds: ONE of the two
  leading dense layers (a convolution layer) and `periods` whole periods of
  the layers behind them (published layers 2-9 for two: attention, three
  convolution layers, twice over), which come out as one scanned stretch."""
  kinds = LayerKinds(2 + periods * atten_every)
  return kinds[:1] + kinds[2:]


@model_registry.RegisterSingleTaskModel
class Lfm2_24B_A2B(synthetic_packed_input.DenseLmTemplate):
  """The published widths, depth and pattern."""

  SEQUENCE_LENGTH = 1024
  VOCAB_SIZE = 65536
  MODEL_DIM = 2048
  NUM_LAYERS = 40
  NUM_HEADS = 32
  NUM_KV_HEADS = 8
  DIM_PER_HEAD = 64
  HIDDEN_DIM = 11776
  ROPE_THETA = 1e6
  NUM_EXPERTS = 64
  EXPERTS_PER_TOKEN = 4
  EXPERT_DIM = 1536
  # made in the dtype they are served in, as trinity.EXPERT_DTYPE: a scanned
  # block's [2, 64, 2048, 1536] x 3 x 4 layers is 19 GB in f32
  EXPERT_DTYPE = jnp.bfloat16

  def Kinds(self) -> list[str]:
    return LayerKinds(self.NUM_LAYERS)

  def Task(self):
    p = super().Task()
    p.name = "lfm2"
    p.layer_kinds = self.Kinds()
    p.rope_theta = self.ROPE_THETA
    p.norm_tpl = layers_lib.RmsNorm.Params().Set(epsilon=1e-5)
    p.atten_tpl = attention_lib.PooledAttention.Params().Set(
        use_bias=False, enable_per_dim_scale=False,
        num_kv_heads=self.NUM_KV_HEADS, dim_per_head=self.DIM_PER_HEAD,
        qk_norm_epsilon=1e-5)
    p.expert_ffn_tpl = moe_lib.DroplessMoELayer.Params().Set(
        hidden_dim=self.EXPERT_DIM, num_experts=self.NUM_EXPERTS,
        num_experts_per_token=self.EXPERTS_PER_TOKEN, scoring="sigmoid",
        routed_scale=1.0, activation="swiglu", shared_hidden_dim=0,
        router_reads="normed_input", dtype=self.EXPERT_DTYPE)
    p.use_rotary = True     # no absolute position table
    p.tie_embeddings = True
    p.scale_emb_sqrt_depth = False
    p.softmax_logits_soft_max = 0.0
    return p


@model_registry.RegisterSingleTaskModel
class Lfm2Tiny(Lfm2_24B_A2B):
  """The same layers at a size the CPU serves in seconds, the stack the
  first stage's (`StageKinds`: a lead block of the dense convolution layer,
  then two periods of attention and three convolution layers as one scanned
  block): heads of 64 over a small model dim, so that the pool's rows hold
  two KV heads side by side as they do at the published widths
  (ops/ragged_block_attend.TileHeads), 8 experts top-3."""

  SEQUENCE_LENGTH = 64
  BATCH_SIZE = 4
  VOCAB_SIZE = 128
  MODEL_DIM = 48
  NUM_LAYERS = 9
  NUM_HEADS = 8
  NUM_KV_HEADS = 4
  DIM_PER_HEAD = 64
  HIDDEN_DIM = 96
  NUM_EXPERTS = 8
  EXPERTS_PER_TOKEN = 3
  EXPERT_DIM = 20
  EXPERT_DTYPE = jnp.float32

  def Kinds(self) -> list[str]:
    return StageKinds()
