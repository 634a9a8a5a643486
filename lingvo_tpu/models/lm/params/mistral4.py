"""Mistral-Small-4-119B-2603's language model (mistralai) as Params of
`TransformerLm`.

https://huggingface.co/mistralai/Mistral-Small-4-119B-2603/blob/main/config.json:
36 layers of model dim 4096, every layer of one kind (`first_k_dense_replace`
0): multi-head latent attention (core/mla.py: 32 heads, `q_lora_rank` 1024,
`kv_lora_rank` 256, 64 + 64 query-key dims a head, values of 128, yarn
frequencies at factor 128 over 8192 positions, interleaved rotation) and an
expert layer (core/moe.py: 128 SwiGLU experts of width 2048 routed top-4 by
a softmax over all of them, renormalised over the four, one shared expert of
the same width, the router on the layer's own normed input); RMSNorm 1e-6,
untied head, no bias, no embedding scale, no logit cap. The vision encoder
is not built (no file of it is here). Every key below is a key of
`TransformerLm.Params()` or of the templates it lays out; the serving engine
takes the task as it takes any other, and serves it through the packed step
alone (the mixer has no dense decode contract).

As one of several chips that share each layer a deployment holds a run of
each layer's experts (`expert_ffn_tpl.first_expert`, `.num_experts_held`)
and a slice of the vocabulary (`vocab_size`): keys a configuration file
writes; the defaults here are the whole model.
"""

from __future__ import annotations

import jax.numpy as jnp

from lingvo_tpu import model_registry
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import mla as mla_lib
from lingvo_tpu.core import moe as moe_lib
from lingvo_tpu.models.lm.params import synthetic_packed_input


@model_registry.RegisterSingleTaskModel
class MistralSmall4(synthetic_packed_input.DenseLmTemplate):
  """The published widths and depth (119B parameters, 6.5B a token)."""

  SEQUENCE_LENGTH = 1024
  VOCAB_SIZE = 131072
  MODEL_DIM = 4096
  NUM_LAYERS = 36
  NUM_HEADS = 32
  Q_LORA_RANK = 1024
  KV_LORA_RANK = 256
  QK_NOPE_HEAD_DIM = 64
  QK_ROPE_HEAD_DIM = 64
  V_HEAD_DIM = 128
  ROPE_THETA = 1e4
  ROPE_FACTOR = 128.0
  ROPE_ORIGINAL_MAX_POSITION = 8192
  ROPE_BETA_FAST = 32.0
  ROPE_BETA_SLOW = 1.0
  ROPE_MSCALE_ALL_DIM = 1.0
  LLAMA_4_SCALING_BETA = 0.1
  NUM_EXPERTS = 128
  EXPERTS_PER_TOKEN = 4
  EXPERT_DIM = 2048
  SHARED_EXPERT_DIM = 2048
  # the experts' matrices are made in the dtype they are served in: 32 of a
  # layer's 128 over six layers are 9.7 GB in bf16
  EXPERT_DTYPE = jnp.bfloat16

  def Task(self):
    p = super().Task()
    p.name = "mistral4"
    p.rope_theta = self.ROPE_THETA
    p.norm_tpl = layers_lib.RmsNorm.Params().Set(epsilon=1e-6)
    p.atten_tpl = mla_lib.MultiHeadLatentAttention.Params().Set(
        q_lora_rank=self.Q_LORA_RANK, kv_lora_rank=self.KV_LORA_RANK,
        qk_nope_head_dim=self.QK_NOPE_HEAD_DIM,
        qk_rope_head_dim=self.QK_ROPE_HEAD_DIM, v_head_dim=self.V_HEAD_DIM,
        norm_epsilon=1e-6, rope_factor=self.ROPE_FACTOR,
        rope_original_max_position=self.ROPE_ORIGINAL_MAX_POSITION,
        rope_beta_fast=self.ROPE_BETA_FAST,
        rope_beta_slow=self.ROPE_BETA_SLOW,
        rope_mscale_all_dim=self.ROPE_MSCALE_ALL_DIM,
        llama_4_scaling_beta=self.LLAMA_4_SCALING_BETA)
    p.expert_ffn_tpl = moe_lib.DroplessMoELayer.Params().Set(
        hidden_dim=self.EXPERT_DIM, num_experts=self.NUM_EXPERTS,
        num_experts_per_token=self.EXPERTS_PER_TOKEN, scoring="softmax",
        activation="swiglu", shared_hidden_dim=self.SHARED_EXPERT_DIM,
        router_reads="normed_input", dtype=self.EXPERT_DTYPE)
    p.hidden_dim = 0
    p.tie_embeddings = False
    p.scale_emb_sqrt_depth = False
    p.softmax_logits_soft_max = 0.0
    return p


@model_registry.RegisterSingleTaskModel
class MistralSmall4Tiny(MistralSmall4):
  """The same layers at a size the CPU serves in seconds: a latent row of
  16 + 8 = 24, a rotary part that is not the other part's size, 8 experts
  top-2, and an original window of 32 positions so that yarn's ramp and the
  query's position scale both show inside a short prompt."""

  SEQUENCE_LENGTH = 64
  BATCH_SIZE = 4
  VOCAB_SIZE = 128
  MODEL_DIM = 48
  NUM_LAYERS = 2
  NUM_HEADS = 4
  Q_LORA_RANK = 24
  KV_LORA_RANK = 16
  QK_NOPE_HEAD_DIM = 12
  QK_ROPE_HEAD_DIM = 8
  V_HEAD_DIM = 10
  ROPE_FACTOR = 8.0
  ROPE_ORIGINAL_MAX_POSITION = 32
  ROPE_BETA_FAST = 4.0
  NUM_EXPERTS = 8
  EXPERTS_PER_TOKEN = 2
  EXPERT_DIM = 20
  SHARED_EXPERT_DIM = 20
  EXPERT_DTYPE = jnp.float32
