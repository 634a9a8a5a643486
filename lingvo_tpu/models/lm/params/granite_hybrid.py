"""granite-4.0-h-small (`model_type` granitemoehybrid, "Granite 4.0-H Small
32B-A9B") as Params of `TransformerLm`.

https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
40 layers of model dim 4096, each TWO pre-norm residual branches under
RMSNorm (eps 1e-5), every branch's output times `residual_multiplier` 0.22
before it is added to the stream:

    h <- h + 0.22 * Mixer(RMSNorm(h));  h <- h + 0.22 * Experts(RMSNorm(h))

`hybrid_override_pattern` writes a branch a letter, so a published layer is
its two letters and `num_layers` counts branches (80 for the 40 published
layers); `layer_types` is nine `mamba` in ten with `attention` at indices 5,
15, 25, 35, so one period of ten layers is `MEMEMEMEME*EMEMEMEME`:

- `M`: a Mamba-2 mixer, 128 heads of 64 channels, ONE group of 128 state
  indices for all of them, a convolution of 4 taps over x, B and C together,
  the gate before an RMSNorm over all 8,192 channels;
- `*`: grouped-query attention, 32 query heads over 8 KV heads of 128,
  scores times `attention_multiplier` 1/128 (not 128 ** -0.5), no bias, no
  position encoding (`position_embedding_type` "nope");
- `E`: 72 experts of width 768, gated (SwiGLU, three matrices), ten a token,
  weighed by the softmax over the ten chosen logits, beside a shared expert
  of width 1536 of the same form; the router reads the branch's own normed
  input.

The embedding is multiplied by `embedding_multiplier` 12, the logits divided
by `logits_scaling` 16, embedding and head are tied, vocabulary 100,352.
About 32B parameters, 9B active a token.

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
class Granite40HSmall(synthetic_packed_input.DenseLmTemplate):
  """The published widths, depth and pattern."""

  SEQUENCE_LENGTH = 1024
  VOCAB_SIZE = 100352
  MODEL_DIM = 4096
  PERIOD = "MEMEMEMEME*EMEMEMEME"      # ten published layers, two letters each
  PATTERN = PERIOD * 4
  NUM_LAYERS = 80                      # branches: 40 published layers of two
  NUM_HEADS = 32
  NUM_KV_HEADS = 8
  DIM_PER_HEAD = 128
  MAMBA_HEADS = 128
  MAMBA_HEAD_DIM = 64
  MAMBA_GROUPS = 1
  STATE_DIM = 128
  CONV_WIDTH = 4
  NUM_EXPERTS = 72
  EXPERTS_PER_TOKEN = 10
  EXPERT_DIM = 768
  SHARED_EXPERT_DIM = 1536
  EMBEDDING_MULTIPLIER = 12.0
  RESIDUAL_MULTIPLIER = 0.22
  ATTENTION_MULTIPLIER = 0.0078125
  LOGITS_SCALING = 16.0
  # made in the dtype they are served in, as nemotron_h.EXPERT_DTYPE: a
  # scanned block's [5, 36, 4096, 768] x 3 is 6.8 GB in f32
  EXPERT_DTYPE = jnp.bfloat16

  def Task(self):
    p = super().Task()
    p.name = "granite_hybrid"
    # the stack is the pattern's first num_layers letters: a file that cuts
    # the depth to one period writes num_layers 20 alone
    p.hybrid_override_pattern = self.PATTERN
    p.norm_tpl = layers_lib.RmsNorm.Params().Set(epsilon=1e-5)
    p.mixer_tpl = ssm_lib.Mamba2Layer.Params().Set(
        num_heads=self.MAMBA_HEADS, head_dim=self.MAMBA_HEAD_DIM,
        num_groups=self.MAMBA_GROUPS, state_dim=self.STATE_DIM,
        conv_width=self.CONV_WIDTH, norm_epsilon=1e-5)
    p.atten_tpl = attention_lib.PooledAttention.Params().Set(
        use_bias=False, enable_per_dim_scale=False,
        num_kv_heads=self.NUM_KV_HEADS, dim_per_head=self.DIM_PER_HEAD,
        score_scale=self.ATTENTION_MULTIPLIER)
    p.expert_ffn_tpl = moe_lib.DroplessMoELayer.Params().Set(
        hidden_dim=self.EXPERT_DIM, num_experts=self.NUM_EXPERTS,
        num_experts_per_token=self.EXPERTS_PER_TOKEN, scoring="softmax",
        activation="swiglu", shared_hidden_dim=self.SHARED_EXPERT_DIM,
        router_reads="normed_input", dtype=self.EXPERT_DTYPE)
    p.hidden_dim = 0
    p.use_rotary = True     # no absolute position table; no layer rotates
    p.tie_embeddings = True
    p.scale_emb_sqrt_depth = False
    p.embedding_multiplier = self.EMBEDDING_MULTIPLIER
    p.residual_multiplier = self.RESIDUAL_MULTIPLIER
    p.logits_scaling = self.LOGITS_SCALING
    p.softmax_logits_soft_max = 0.0
    return p


@model_registry.RegisterSingleTaskModel
class Granite40HSmallTiny(Granite40HSmall):
  """The same layers at a size the CPU serves in seconds: one period of 20
  branches (a scanned block of five `ME`, the attention layer, a scanned
  block of four `EM`, a single `E`), ONE group for all four heads, a head
  size that is not model_dim / heads, a score scale that is not head size
  ** -0.5."""

  SEQUENCE_LENGTH = 64
  BATCH_SIZE = 4
  VOCAB_SIZE = 128
  MODEL_DIM = 48
  NUM_LAYERS = 20
  NUM_HEADS = 4
  NUM_KV_HEADS = 2
  DIM_PER_HEAD = 8
  MAMBA_HEADS = 4
  MAMBA_HEAD_DIM = 16
  STATE_DIM = 16
  NUM_EXPERTS = 8
  EXPERTS_PER_TOKEN = 3
  EXPERT_DIM = 20
  SHARED_EXPERT_DIM = 40
  ATTENTION_MULTIPLIER = 0.25
  EXPERT_DTYPE = jnp.float32
