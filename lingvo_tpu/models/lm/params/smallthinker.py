"""SmallThinker-21BA3B-Instruct (PowerInfer) as Params of `TransformerLm`.

https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json:
52 layers of model dim 2560; 28 query heads over 4 KV heads of 128; no dense
feed-forward, 64 ReGLU experts of width 768 routed top-6 by a router that
reads the layer's input before attention; RMSNorm; a period of four layers
(full attention without position, then three of a 4096-token sliding window
with RoPE at theta 1.5e6); untied head, no embedding scale, no logit cap.
Every key below is a key of `TransformerLm.Params()` or of the templates it
lays out; the serving engine takes the task as it takes any other.
"""

from __future__ import annotations

from lingvo_tpu import model_registry
from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import moe as moe_lib
from lingvo_tpu.models.lm.params import synthetic_packed_input


@model_registry.RegisterSingleTaskModel
class SmallThinker21BA3B(synthetic_packed_input.DenseLmTemplate):
  """The published widths and depth (21B parameters, 3B a token)."""

  SEQUENCE_LENGTH = 1024
  VOCAB_SIZE = 151936
  MODEL_DIM = 2560
  NUM_LAYERS = 52
  NUM_HEADS = 28
  NUM_KV_HEADS = 4
  DIM_PER_HEAD = 128
  NUM_EXPERTS = 64
  EXPERTS_PER_TOKEN = 6
  EXPERT_DIM = 768
  WINDOW = 4096
  ROPE_THETA = 1.5e6
  # one period, repeated down the stack: layer 0 of four is full attention
  # without rotary, the other three a window with it
  WINDOW_LAYOUT = (0, 1, 1, 1)
  ROPE_LAYOUT = (0, 1, 1, 1)

  def Task(self):
    p = super().Task()
    p.name = "smallthinker"
    p.rope_theta = self.ROPE_THETA
    p.sliding_window_size = self.WINDOW
    p.sliding_window_layout = list(self.WINDOW_LAYOUT)
    p.rope_layout = list(self.ROPE_LAYOUT)
    p.norm_tpl = layers_lib.RmsNorm.Params().Set(epsilon=1e-6)
    p.atten_tpl = attention_lib.MultiHeadedAttention.Params().Set(
        use_bias=False, enable_per_dim_scale=False,
        num_kv_heads=self.NUM_KV_HEADS, dim_per_head=self.DIM_PER_HEAD)
    p.expert_ffn_tpl = moe_lib.DroplessMoELayer.Params().Set(
        hidden_dim=self.EXPERT_DIM, num_experts=self.NUM_EXPERTS,
        num_experts_per_token=self.EXPERTS_PER_TOKEN)
    p.hidden_dim = 0
    p.tie_embeddings = False
    p.scale_emb_sqrt_depth = False
    p.softmax_logits_soft_max = 0.0
    return p


@model_registry.RegisterSingleTaskModel
class SmallThinkerTiny(SmallThinker21BA3B):
  """The same layers at a size the CPU serves in seconds: a head size that
  is not model_dim / heads, 2 KV heads, 8 experts top-2, a window shorter
  than a prompt, the period of four."""

  SEQUENCE_LENGTH = 64
  BATCH_SIZE = 4
  VOCAB_SIZE = 128
  MODEL_DIM = 48
  NUM_LAYERS = 4
  NUM_HEADS = 6
  NUM_KV_HEADS = 2
  DIM_PER_HEAD = 16
  NUM_EXPERTS = 8
  EXPERTS_PER_TOKEN = 2
  EXPERT_DIM = 32
  WINDOW = 24
