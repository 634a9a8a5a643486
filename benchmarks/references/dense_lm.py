"""Plain reference of the DenseLm family (TransformerLm with rotary attention,
a ReLU feed-forward, pre-LayerNorm residual blocks and a tied, tanh-capped
softmax), written from the layer equations in plain jax.numpy and float32:
no kernel, no remat, no cache, no batching tricks, and none of the program's
layer code. It reads only the names and shapes of the program's weights.

Used by the benchmark's `correct`, outside the timed window:
  train cells   the logits of the measured weights at a seeded sample of
                positions, and the loss over the sampled rows, against the
                program's own forward (the flash kernel, bf16);
  serve cells   the logits over the whole vocabulary at the newest position
                of a seeded sample of live sequences against the logits the
                engine's own step computed there from its paged cache.
On a TPU an f32 matmul runs in lower precision unless told otherwise, so the
caller wraps this in jax.default_matmul_precision("highest").
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_LN_EPS = 1e-6
_R_SOFTPLUS_0 = 1.442695041


def _LayerNorm(x, scale, bias):
  mean = jnp.mean(x, -1, keepdims=True)
  var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
  return (x - mean) * jax.lax.rsqrt(var + _LN_EPS) * (1.0 + scale) + bias


def _Rotary(x):
  """x [B, T, N, H]: rotate the halves of H by position / 10000^(i/half)."""
  t, h = x.shape[1], x.shape[-1]
  half = h // 2
  timescale = 10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half)
  ang = jnp.arange(t, dtype=jnp.float32)[None, :, None, None] / timescale
  sin, cos = jnp.sin(ang), jnp.cos(ang)
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def SeededWeights(theta, attention_out_scale: float):
  """The weights a cell makes from its seed (the program's own init), with
  every layer's attention output projection scaled. As the init leaves them,
  attention is worth next to nothing beside the residual stream: dropping it
  in all 24 layers of dense1b moves the reference's logits by 0.08, a wrong
  page of the cache by 0.002 to 0.008, less than bf16 rounding (0.06), so no
  comparison of outputs could see the paged cache, the flash kernel or the
  segment mask (CPU runs of this reference at full width, PR 23). The scale
  lifts what a page of context is worth over that rounding. Shapes and the
  work of every step stay as they were."""

  def _Leaf(path, x):
    keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
    if keys[-1] == "w_post" and "self_atten" in keys:
      return (x.astype(jnp.float32) * attention_out_scale).astype(x.dtype)
    return x

  return jax.tree_util.tree_map_with_path(_Leaf, theta)


def _F32(tree):
  return jax.tree_util.tree_map(lambda v: jnp.asarray(v, jnp.float32), tree)


def _Hidden(theta, ids, segment_ids):
  """ids [B, T] -> (final-LayerNorm output [B, T, D], embedding [V, D]), f32.
  Causal; tokens of different segments do not see each other."""
  emb = jnp.asarray(theta["emb"]["emb"], jnp.float32)
  body = theta["stack"]["body"]
  d = emb.shape[1]
  b, t = ids.shape
  x = emb[ids] * math.sqrt(d)
  visible = jnp.tril(jnp.ones((t, t), bool))[None]
  if segment_ids is not None:
    visible = visible & (segment_ids[:, :, None] == segment_ids[:, None, :])

  def _Layer(x, layer):
    layer = _F32(layer)
    at = layer["self_atten"]["atten"]
    y = _LayerNorm(x, layer["self_atten"]["ln"]["scale"],
                   layer["self_atten"]["ln"]["bias"])
    q = jnp.einsum("btd,dnh->btnh", y, at["w_query"]) + at["b_query"]
    k = jnp.einsum("btd,dnh->btnh", y, at["w_key"]) + at["b_key"]
    v = jnp.einsum("btd,dnh->btnh", y, at["w_value"]) + at["b_value"]
    q, k = _Rotary(q), _Rotary(k)
    h = q.shape[-1]
    q = q * jax.nn.softplus(at["per_dim_scale"]["per_dim_scale"]) * (
        _R_SOFTPLUS_0 / math.sqrt(h))
    s = jnp.einsum("btnh,bsnh->bnts", q, k)
    s = jnp.where(visible[:, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    ctx = jnp.einsum("bnts,bsnh->btnh", p, v)
    x = x + jnp.einsum("btnh,dnh->btd", ctx, at["w_post"]) + at["b_post"]
    ff = layer["fflayer"]
    y = _LayerNorm(x, ff["ln"]["scale"], ff["ln"]["bias"])
    y = jax.nn.relu(y @ ff["ffn_in"]["w"] + ff["ffn_in"]["b"])
    return x + y @ ff["ffn_out"]["w"] + ff["ffn_out"]["b"], None

  # the weights are stacked [layers, ...]: one layer after the other
  x, _ = jax.lax.scan(_Layer, x, body)
  x = _LayerNorm(x, jnp.asarray(theta["final_ln"]["scale"], jnp.float32),
                 jnp.asarray(theta["final_ln"]["bias"], jnp.float32))
  return x, emb


def _Head(x, emb, logit_cap):
  logits = jnp.einsum("...d,vd->...v", x, emb)
  if logit_cap > 0:
    logits = logit_cap * jnp.tanh(logits / logit_cap)
  return logits


def Logits(theta, ids, segment_ids=None, logit_cap: float = 30.0):
  """theta: the program's weights (any float dtype); ids [B, T] -> f32 logits
  [B, T, V]."""
  return _Head(*_Hidden(theta, ids, segment_ids), logit_cap)


def LogitsAt(theta, ids, at, logit_cap: float = 30.0):
  """ids [B, W] right-padded sequences, at [B] one position in each -> f32
  logits [B, V] there. Padding sits after `at`, and causality keeps it out
  of sight; only the chosen positions go through the [V, D] head."""
  x, emb = _Hidden(theta, ids, None)
  return _Head(x[jnp.arange(ids.shape[0]), at], emb, logit_cap)
