"""Phi-4-mini-flash-reasoning (Microsoft) as Params of `TransformerLm`.

https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
and its `modeling_phi4flash.py` (the decoder-hybrid-decoder "SambaY" of
arXiv:2507.06607): 32 layers of model dim 2560, every one a mixer and a gated
SiLU feed-forward of 10,240 under LayerNorm (eps 1e-5, scale and bias), no
other bias and no position encoding; a tied table of 200,064, no embedding
scale, no logit cap. The mixers, by depth `l` of `n` (a multiple of 4):

- `l < n/2`, even: Mamba-1 (E = 2 x 2560, 16 state indices, step size of
  rank 160, a convolution of 4 taps); odd: differential attention (40 query
  heads over 20 K heads of 64, V as 10 heads of 128) within 512 tokens;
- `l = n/2`: the Mamba-1 layer that also exports its scan output;
- `l = n/2 + 1`: differential attention over everything, the model's one
  full-length cache;
- after it, even: a gated memory unit over that export; odd: differential
  attention with no K and V of its own, reading the full layer's.

Every key below is a key of `TransformerLm.Params()` or of the templates it
lays out; the serving engine takes the task as it takes any other.
"""

from __future__ import annotations

from lingvo_tpu import model_registry
from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core import layers as layers_lib
from lingvo_tpu.core import ssm as ssm_lib
from lingvo_tpu.models.lm.params import synthetic_packed_input


def LayerKinds(num_layers: int) -> list[str]:
  """The published rule for a depth divisible by 4 (module docstring)."""
  assert num_layers % 4 == 0 and num_layers >= 8, num_layers
  half = num_layers // 2
  kinds = []
  for l in range(num_layers):
    if l < half:
      kinds.append("window" if l % 2 else "mamba")
    elif l == half:
      kinds.append("mamba_export")
    elif l == half + 1:
      kinds.append("full")
    else:
      kinds.append("cross" if l % 2 else "gmu")
  return kinds


@model_registry.RegisterSingleTaskModel
class Phi4MiniFlash(synthetic_packed_input.DenseLmTemplate):
  """The published widths and depth (3.85B parameters)."""

  SEQUENCE_LENGTH = 1024
  VOCAB_SIZE = 200064
  MODEL_DIM = 2560
  NUM_LAYERS = 32
  NUM_HEADS = 40
  NUM_KV_HEADS = 20
  DIM_PER_HEAD = 64
  HIDDEN_DIM = 10240
  WINDOW = 512
  STATE_DIM = 16
  CONV_WIDTH = 4
  EXPAND = 2
  DT_RANK = 160

  def Task(self):
    p = super().Task()
    p.name = "phi4flash"
    p.layer_kinds = LayerKinds(self.NUM_LAYERS)
    p.sliding_window_size = self.WINDOW
    p.mixer_tpl = ssm_lib.Mamba1Layer.Params().Set(
        expand=self.EXPAND, state_dim=self.STATE_DIM,
        conv_width=self.CONV_WIDTH, dt_rank=self.DT_RANK)
    p.atten_tpl = attention_lib.DifferentialAttention.Params().Set(
        num_kv_heads=self.NUM_KV_HEADS, dim_per_head=self.DIM_PER_HEAD)
    p.norm_tpl = layers_lib.LayerNorm.Params().Set(epsilon=1e-5)
    p.tie_embeddings = True
    p.scale_emb_sqrt_depth = False
    p.softmax_logits_soft_max = 0.0
    return p


@model_registry.RegisterSingleTaskModel
class Phi4MiniFlashTiny(Phi4MiniFlash):
  """The same layers at a size the CPU serves in seconds: 8 layers hold
  every kind, a head size that is not model_dim / heads, a window shorter
  than a prompt."""

  SEQUENCE_LENGTH = 64
  BATCH_SIZE = 4
  VOCAB_SIZE = 128
  MODEL_DIM = 48
  NUM_LAYERS = 8
  NUM_HEADS = 8
  NUM_KV_HEADS = 4
  DIM_PER_HEAD = 8
  HIDDEN_DIM = 96
  WINDOW = 24
  STATE_DIM = 8
  DT_RANK = 4
