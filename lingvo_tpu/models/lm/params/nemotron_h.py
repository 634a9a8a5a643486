"""NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type` nemotron_h) as Params of
`TransformerLm`.

https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json
52 layers of model dim 2688, every one ONE residual branch under RMSNorm
(eps 1e-5), `h <- h + Branch(RMSNorm(h))`, by `hybrid_override_pattern`
(one letter a layer):

- `M`: a Mamba-2 mixer (64 heads of 64 channels, 8 groups of 128 state
  indices, a convolution of 4 taps over x, B and C together, an RMSNorm
  over groups of 512 after the gate);
- `E`: 128 experts of width 1856, relu^2 without a gate, six a token chosen
  by sigmoid score plus a selection bias and weighted by the scores over
  their sum times 2.5, beside a shared expert of width 3712; the router
  reads the branch's own normed input;
- `*`: grouped-query attention, 32 query heads over 2 KV heads of 128, no
  bias, no position encoding.

An untied head over 131,072, no embedding scale, no logit cap, no bias on
any projection. 31.58B parameters, 3.2B active a token.

Every key below is a key of `TransformerLm.Params()` or of the templates it
lays out; the serving engine takes the task as it takes any other.
"""

from __future__ import annotations

import jax.numpy as jnp

from lingvo_tpu import model_registry
from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import moe as moe_lib
from lingvo_tpu.core import ssm as ssm_lib
from lingvo_tpu.models.lm.params import synthetic_packed_input

@model_registry.RegisterSingleTaskModel
class Nemotron3Nano30BA3B(synthetic_packed_input.DenseLmTemplate):
  """The published widths, depth and pattern."""

  SEQUENCE_LENGTH = 1024
  VOCAB_SIZE = 131072
  MODEL_DIM = 2688
  NUM_LAYERS = 52
  PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
  NUM_HEADS = 32
  NUM_KV_HEADS = 2
  DIM_PER_HEAD = 128
  MAMBA_HEADS = 64
  MAMBA_HEAD_DIM = 64
  MAMBA_GROUPS = 8
  STATE_DIM = 128
  CONV_WIDTH = 4
  NUM_EXPERTS = 128
  EXPERTS_PER_TOKEN = 6
  EXPERT_DIM = 1856
  SHARED_EXPERT_DIM = 3712
  ROUTED_SCALE = 2.5
  # the experts' matrices are made in the dtype they are served in: a
  # scanned block's [repeats, 128, 2688, 1920] is 5.3 GB in f32, more than
  # a 16 GB chip has free beside the model while it is being made
  EXPERT_DTYPE = jnp.bfloat16

  def Task(self):
    p = super().Task()
    p.name = "nemotron_h"
    # the stack is the pattern's first num_layers letters: a file that cuts
    # the depth to one period writes num_layers alone. A letter is ONE
    # branch: a model whose published layer is two pre-norm branches writes
    # it as its two letters (granite_hybrid.py)
    p.hybrid_override_pattern = self.PATTERN
    p.norm_tpl = layers_lib.RmsNorm.Params().Set(epsilon=1e-5)
    p.mixer_tpl = ssm_lib.Mamba2Layer.Params().Set(
        num_heads=self.MAMBA_HEADS, head_dim=self.MAMBA_HEAD_DIM,
        num_groups=self.MAMBA_GROUPS, state_dim=self.STATE_DIM,
        conv_width=self.CONV_WIDTH, norm_epsilon=1e-5)
    p.atten_tpl = attention_lib.PooledAttention.Params().Set(
        use_bias=False, enable_per_dim_scale=False,
        num_kv_heads=self.NUM_KV_HEADS, dim_per_head=self.DIM_PER_HEAD)
    p.expert_ffn_tpl = moe_lib.DroplessMoELayer.Params().Set(
        hidden_dim=self.EXPERT_DIM, num_experts=self.NUM_EXPERTS,
        num_experts_per_token=self.EXPERTS_PER_TOKEN, scoring="sigmoid",
        routed_scale=self.ROUTED_SCALE, activation="relu2",
        shared_hidden_dim=self.SHARED_EXPERT_DIM,
        router_reads="normed_input",
        dtype=self.EXPERT_DTYPE)
    p.hidden_dim = 0
    p.use_rotary = True     # no absolute position table; no layer rotates
    p.tie_embeddings = False
    p.scale_emb_sqrt_depth = False
    p.softmax_logits_soft_max = 0.0
    return p


@model_registry.RegisterSingleTaskModel
class Nemotron3NanoTiny(Nemotron3Nano30BA3B):
  """The same layers at a size the CPU serves in seconds: the pattern's
  first nine letters hold every kind, a repeated block and single ones, a
  head size that is not model_dim / heads, an expert width that is no
  multiple of anything."""

  SEQUENCE_LENGTH = 64
  BATCH_SIZE = 4
  VOCAB_SIZE = 128
  MODEL_DIM = 48
  NUM_LAYERS = 9
  NUM_HEADS = 4
  NUM_KV_HEADS = 2
  DIM_PER_HEAD = 8
  MAMBA_HEADS = 8
  MAMBA_HEAD_DIM = 8
  MAMBA_GROUPS = 2
  STATE_DIM = 16
  NUM_EXPERTS = 8
  EXPERTS_PER_TOKEN = 3
  EXPERT_DIM = 20
  SHARED_EXPERT_DIM = 40
  EXPERT_DTYPE = jnp.float32
