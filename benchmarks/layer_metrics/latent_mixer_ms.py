"""Milliseconds a step under a latent-attention layer's scopes other than its
attend kernel and its page write (`qkv_proj`, `rope`, `mla_absorb`,
`out_proj`): what the layer does round its kernel."""
from benchmarks.harness import mla_cost

Read = mla_cost.MixerMs
