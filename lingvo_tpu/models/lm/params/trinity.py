"""Trinity-Mini (Arcee, `model_type` afmoe, 26B parameters of which about 3B
a token) as Params of `TransformerLm`.

https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json:
32 layers of model dim 2048, each an attention mixer and a feed-forward, and
each branch normed going in AND coming out (RMSNorm, eps 1e-5):

    h <- h + PostLN(Attn(LN(h)));  h <- h + PostLN(FF(LN(h)))

- attention: 32 query heads over 4 KV heads of 128, no bias; q and k each
  through an RMSNorm over the head's 128 dims (a learned scale of 128, one
  for q, one for k) BEFORE any rotation; three layers in four attend within
  a window of 2,048 tokens and are rotated (theta 10,000), every fourth
  attends over everything and carries no position at all; the attend's
  output times sigmoid(x W_gate), W_gate 2048 -> 4096 of its own, before
  the output projection;
- feed-forward, layers 0-1: SwiGLU of width 6,144;
- feed-forward, layers 2-31: 128 SwiGLU experts of width 1,024, 8 a token,
  scores sigmoid(router logits) in f32 from the branch's own normed input,
  the 8 chosen by score + a stored per-expert bias (which chooses and does
  not weigh), weights the chosen scores over their sum times 2.826, beside
  one shared expert of the same width.

The embedding is multiplied by sqrt(2048) (`mup_enabled`), the head is its
own, vocabulary 200,192; no logit scaling and no cap.

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
               full_every: int = 4) -> list[str]:
  """The published rule (`layer_types`, `num_dense_layers`): every
  `full_every`-th layer attends over everything, the others within the
  window; the first `num_dense` feed-forwards are dense, the rest experts."""
  return [("gqa" if (l + 1) % full_every == 0 else "gqa_window")
          + ("+dense" if l < num_dense else "+experts")
          for l in range(num_layers)]


def StageKinds(full_every: int = 4) -> list[str]:
  """What one pipeline stage of whole periods holds where it is the first:
  the leading dense layer (published layer 0) and one whole period of the
  layers behind the dense ones (published layers 4-7)."""
  kinds = LayerKinds(2 * full_every)
  return kinds[:1] + kinds[full_every:]


@model_registry.RegisterSingleTaskModel
class TrinityMini(synthetic_packed_input.DenseLmTemplate):
  """The published widths, depth and pattern."""

  SEQUENCE_LENGTH = 1024
  VOCAB_SIZE = 200192
  MODEL_DIM = 2048
  NUM_LAYERS = 32
  NUM_HEADS = 32
  NUM_KV_HEADS = 4
  DIM_PER_HEAD = 128
  HIDDEN_DIM = 6144
  WINDOW = 2048
  ROPE_THETA = 1e4
  NUM_EXPERTS = 128
  EXPERTS_PER_TOKEN = 8
  EXPERT_DIM = 1024
  SHARED_EXPERT_DIM = 1024
  ROUTE_SCALE = 2.826
  # made in the dtype they are served in, as granite_hybrid.EXPERT_DTYPE: a
  # scanned block's [3, 128, 2048, 1024] x 3 is 9.7 GB in f32
  EXPERT_DTYPE = jnp.bfloat16

  def Kinds(self) -> list[str]:
    return LayerKinds(self.NUM_LAYERS)

  def Task(self):
    p = super().Task()
    p.name = "trinity"
    p.layer_kinds = self.Kinds()
    p.sliding_window_size = self.WINDOW
    p.rope_theta = self.ROPE_THETA
    p.norm_tpl = layers_lib.RmsNorm.Params().Set(epsilon=1e-5)
    p.post_norm = True
    p.atten_tpl = attention_lib.PooledAttention.Params().Set(
        use_bias=False, enable_per_dim_scale=False,
        num_kv_heads=self.NUM_KV_HEADS, dim_per_head=self.DIM_PER_HEAD,
        qk_norm_epsilon=1e-5, output_gate=True)
    p.expert_ffn_tpl = moe_lib.DroplessMoELayer.Params().Set(
        hidden_dim=self.EXPERT_DIM, num_experts=self.NUM_EXPERTS,
        num_experts_per_token=self.EXPERTS_PER_TOKEN, scoring="sigmoid",
        routed_scale=self.ROUTE_SCALE, activation="swiglu",
        shared_hidden_dim=self.SHARED_EXPERT_DIM,
        router_reads="normed_input", dtype=self.EXPERT_DTYPE)
    p.use_rotary = True     # no absolute position table
    p.tie_embeddings = False
    p.scale_emb_sqrt_depth = True
    p.softmax_logits_soft_max = 0.0
    return p


@model_registry.RegisterSingleTaskModel
class TrinityTiny(TrinityMini):
  """The same layers at a size the CPU serves in seconds, the stack one
  stage's (`StageKinds`: a lead block of the dense layer, a scanned block of
  three window layers, the full layer): a head size that is not model_dim /
  heads, 2 KV heads, 8 experts top-3, a window shorter than a prompt."""

  SEQUENCE_LENGTH = 64
  BATCH_SIZE = 4
  VOCAB_SIZE = 128
  MODEL_DIM = 48
  NUM_LAYERS = 5
  NUM_HEADS = 6
  NUM_KV_HEADS = 2
  DIM_PER_HEAD = 16
  HIDDEN_DIM = 96
  WINDOW = 24
  NUM_EXPERTS = 8
  EXPERTS_PER_TOKEN = 3
  EXPERT_DIM = 20
  SHARED_EXPERT_DIM = 20
  EXPERT_DTYPE = jnp.float32

  def Kinds(self) -> list[str]:
    return StageKinds()
