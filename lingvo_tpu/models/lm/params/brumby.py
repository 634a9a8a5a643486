"""Brumby-14B-Base (`model_type` brumby) as Params of `TransformerLm`.

https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json
40 layers of model dim 5120, pre-norm under RMSNorm (eps 1e-6) as the
Qwen3-14B checkpoint the model was retrained from: `h <- h + Mixer(RMSNorm(
h))`, `h <- h + W_down(silu(W_gate x) * (W_up x))`, `x = RMSNorm(h)`, the
feed-forward 17,408 wide. Every layer's mixer is a power-retention layer
(core/retention.PowerRetention, Manifest AI, arXiv:2507.04239, degree 2): 40
query heads over 8 KV heads of 128, q and k normed a head and rotated at
1e6, one sigmoid gate a KV head, no bias. An untied head over 151,936, no
embedding scale, no logit cap. 14.77B parameters.

Every key below is a key of `TransformerLm.Params()` or of the templates it
lays out; the serving engine takes the task as it takes any other.
"""

from __future__ import annotations

from lingvo_tpu import model_registry
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import retention as retention_lib
from lingvo_tpu.models.lm.params import synthetic_packed_input


@model_registry.RegisterSingleTaskModel
class Brumby14BBase(synthetic_packed_input.DenseLmTemplate):
  """The published widths and depth."""

  SEQUENCE_LENGTH = 1024
  VOCAB_SIZE = 151936
  MODEL_DIM = 5120
  NUM_LAYERS = 40
  NUM_HEADS = 40
  NUM_KV_HEADS = 8
  DIM_PER_HEAD = 128
  HIDDEN_DIM = 17408
  ROPE_THETA = 1e6

  def Task(self):
    p = super().Task()
    p.name = "brumby"
    p.norm_tpl = layers_lib.RmsNorm.Params().Set(epsilon=1e-6)
    p.mixer_tpl = retention_lib.PowerRetention.Params().Set(
        num_kv_heads=self.NUM_KV_HEADS, dim_per_head=self.DIM_PER_HEAD,
        norm_epsilon=1e-6)
    p.atten_tpl = None
    # every layer is the one kind; the stack is the pattern's first
    # num_layers letters, so a file that cuts the depth writes num_layers
    p.hybrid_override_pattern = "R" * 40
    p.use_rotary = True     # no absolute position table; the mixer rotates
    p.rope_theta = self.ROPE_THETA
    p.tie_embeddings = False
    p.scale_emb_sqrt_depth = False
    p.softmax_logits_soft_max = 0.0
    return p


@model_registry.RegisterSingleTaskModel
class BrumbyTiny(Brumby14BBase):
  """The same layers at a size the CPU serves in seconds: grouped heads, a
  head size that is not model_dim / heads."""

  SEQUENCE_LENGTH = 64
  BATCH_SIZE = 4
  VOCAB_SIZE = 128
  MODEL_DIM = 48
  NUM_LAYERS = 3
  NUM_HEADS = 4
  NUM_KV_HEADS = 2
  DIM_PER_HEAD = 16
  HIDDEN_DIM = 96
