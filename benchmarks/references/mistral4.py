"""Plain reference of Mistral-Small-4-119B-2603's language model, one chip's
share of it, written from the published `config.json` (model_type mistral4;
DeepSeek-V3's layer, whose keys the config uses) in plain jax.numpy and
float32: no kernel, no cache, no sort, no batching, and none of the
program's layer code. It reads only the names and shapes of the program's
weights.

Layer l, input h [T, D], RMSNorm with eps 1e-6 and a learned scale, heads
i = 1..N, in the EXPANDED form (keys and values by heads rebuilt from every
token's latent row; the program serves the absorbed form):

    x    = RMSNorm_1(h)
    c_q  = RMSNorm(x W_qa)                           [q_lora_rank]
    [q_nope_i | q_rope_i] = c_q W_qb,i               [nope | rope]
    [c_kv | k_r] = x W_kva;  c_kv <- RMSNorm(c_kv)   [kv_lora_rank | rope]
    q_rope_i, k_r rotated at the token's position: interleaved pairs
        (2j, 2j + 1), yarn frequencies (theta, factor, original window,
        beta_fast, beta_slow); one k_r for all heads
    [k_nope_i | v_i] = c_kv W_kvb,i                  [nope | v]
    s_i(t, s) = a(t) scale (q_nope_i(t) . k_nope_i(s) + q_rope_i(t) . k_r(s)),
        s <= t;  scale = (nope + rope)^-0.5 m^2,  m = 0.1 mscale_all_dim
        ln(factor) + 1;  a(t) = 1 + beta ln(1 + floor(t / original window))
    h   <- h + concat_i(softmax_s(s_i) v_i) W_o
    g    = RMSNorm_2(h)
    p    = softmax(g W_r) over ALL experts;  S = the k largest;
    w_e  = p_e / sum_{e' in S} p_e'                  (routed_scaling_factor 1)
    h   <- h + sum_{e in S, e held here} w_e (silu(g W_gate,e) * (g W_up,e))
           W_down,e + (silu(g W_sg) * (g W_su)) W_sd

Final RMSNorm; an untied head; no embedding scale; no bias; no logit cap
unless one is passed.

THE SHARE. The weights are one chip's of several that share each layer: the
experts' matrices hold `Eh` experts, `[first_expert, first_expert + Eh)` of
the `E` the router scores (`w_router` keeps its width, so E is read off it),
and the embedding and the head hold a slice of the vocabulary. The reference
computes the same share: a chosen expert that lives elsewhere adds nothing
here, its weight stays in the sum the four are normalised over, the shared
expert is added once. Nothing stands in for the other chips.

Where the program departs from these lines, and the reference follows the
WEIGHTS it is handed: a norm's scale is stored as an offset from one (the
program's RmsNorm multiplies by 1 + scale; the init is 0); the head is
stored [V, D] like the embedding table; the rotation turns the pairs where
they lie (the published code first moves the even members to the front and
the odd ones behind them, for both q and k: every score is the same sum).
Nothing else. Which of `m^2` and `a(t)` model_type mistral4 applies is the
configuration file's to state under `assumed`.

Widths are read off the weights' shapes. What no shape tells (eps, experts a
token, first_expert, the rotary parameters, the sizes of a head's parts) is
read from `benchmarks/configs/mistralsmall4.json`: the published keys where
the weights have the published model dim, the `rehearsal` group's
`task_params` where they have its (`_Arch`). A test at yet another size
states its own through `SeededWeights`' keyword arguments.

How it is computed (`LogitsAt`), to stay inside what a chip has free beside
the served model and pool (2 GB): one row at a time (`lax.map`), only the
blocks of `_BLOCK` tokens up to the row's own `at` (a dynamic trip count),
every token's latent row first (320 values, 31 MB at 24,576 tokens), then
attention by blocks of queries against blocks of keys with a running softmax,
a key block's K and V by heads rebuilt from its latent rows where it is used
(all 24,576 tokens' would be 0.6 GB), weights upcast where they are used, one
held expert at a time, its tokens gathered in pieces of `_PIECE`.

On a TPU an f32 matmul runs in lower precision unless told otherwise, so the
caller wraps this in jax.default_matmul_precision("highest").
"""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK = 1024   # tokens a block of queries, and of keys
_PIECE = 128    # tokens of one expert computed together

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "mistralsmall4.json")
# what a caller stated through SeededWeights; it outranks the file
_STATED: dict = {}
_ARCH: dict = {}

# `_ARCH` key <- (published key, the rehearsal group's task_params key)
_ROPE_KEYS = {
    "rope_theta": ("rope_theta", "rope_theta"),
    "rope_factor": ("factor", "atten_tpl.rope_factor"),
    "rope_original": ("original_max_position_embeddings",
                      "atten_tpl.rope_original_max_position"),
    "beta_fast": ("beta_fast", "atten_tpl.rope_beta_fast"),
    "beta_slow": ("beta_slow", "atten_tpl.rope_beta_slow"),
    "mscale_all_dim": ("mscale_all_dim", "atten_tpl.rope_mscale_all_dim"),
    "scaling_beta": ("llama_4_scaling_beta",
                     "atten_tpl.llama_4_scaling_beta"),
}


def _Arch(model_dim: int) -> dict:
  """What the weights' shapes do not say, for weights of `model_dim`."""
  with open(_CONFIG) as f:
    cfg = json.load(f)
  rope = cfg["rope_parameters"]
  arch = {name: float(rope[key]) for name, (key, _) in _ROPE_KEYS.items()}
  arch.update(eps=float(cfg["rms_norm_eps"]),
              experts_per_token=int(cfg["num_experts_per_tok"]),
              first_expert=int(cfg["task_params"].get(
                  "expert_ffn_tpl.first_expert", 0)),
              nope=int(cfg["qk_nope_head_dim"]),
              rope=int(cfg["qk_rope_head_dim"]))
  small = cfg["rehearsal"]
  if model_dim == small["model_dim"] != cfg["model_dim"]:
    tp = small["task_params"]
    arch.update({name: float(tp[key])
                 for name, (_, key) in _ROPE_KEYS.items() if key in tp})
    arch.update(
        experts_per_token=int(tp["expert_ffn_tpl.num_experts_per_token"]),
        first_expert=int(tp.get("expert_ffn_tpl.first_expert", 0)),
        nope=int(tp["atten_tpl.qk_nope_head_dim"]),
        rope=int(tp["atten_tpl.qk_rope_head_dim"]))
  arch.update(_STATED)
  return arch


def SeededWeights(theta, attention_out_scale: float = 1.0,
                  router_scale: float = 1.0, router_reads_share: float = 0.0,
                  query_scale: float = 1.0, router_layer_gain=(),
                  **stated):
  """The weights a cell makes from its seed (the program's own init), with
  every layer's attention output projection, every router and every query
  up-projection scaled, and, where `router_reads_share` > 0, the routing
  made a function of the token alone: the first `share` of the model's
  dimensions are written by no layer (those columns of every attention
  output projection and of every expert's and the shared expert's down
  projection are zero), so the stream holds the embedding there, exactly, at
  every precision, and the routers read those dimensions only (their other
  rows are zero). `router_layer_gain`: a factor a layer on its router, in the
  stack's order (a router reads its layer's NORMED input, and under random
  weights the stream grows from layer to layer, so the share of it that the
  embedding holds shrinks). The configuration file's `weights_reason` has
  the readings that set the scales. `stated`: keys of `_ARCH` that a size
  other than the file's two changes (a test's)."""
  _STATED.clear()
  _STATED.update(stated)
  scales = {"w_post": attention_out_scale, "w_router": router_scale,
            "w_qb": query_scale}
  d = theta["head"]["emb"].shape[1]
  reads = max(1, int(d * router_reads_share)) if router_reads_share else 0
  # where the model dimension lies in each: w_post [.., D, N, V], w_down
  # [.., E, F, D] and w_shared_down [.., F, D] (written: the first `reads`
  # are zeroed), w_router [.., D, E] (read: all but the first are zeroed)
  model_axis = {"w_post": -3, "w_down": -1, "w_shared_down": -1,
                "w_router": -2}

  def _Leaf(path, x):
    name = str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1])))
    scale = scales.get(name, 1.0)
    if scale != 1.0:
      x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    if name == "w_router" and len(router_layer_gain):
      # a stack cut to fewer layers (the rehearsal's) keeps the first gains
      gain = jnp.asarray(router_layer_gain, jnp.float32)[:x.shape[0]]
      assert gain.shape[0] == x.shape[0], (gain.shape, x.shape)
      x = (x.astype(jnp.float32) * gain[:, None, None]).astype(x.dtype)
    if reads and name in model_axis:
      at = jnp.arange(d).reshape((d,) + (1,) * (-model_axis[name] - 1))
      keep = at < reads if name == "w_router" else at >= reads
      x = jnp.where(keep, x, jnp.zeros_like(x))
    return x

  return jax.tree_util.tree_map_with_path(_Leaf, theta)


def _F32(x):
  return jnp.asarray(x, jnp.float32)


def _RmsNorm(x, scale):
  ms = jnp.mean(jnp.square(x), -1, keepdims=True)
  return x * jax.lax.rsqrt(ms + _ARCH["eps"]) * (1.0 + _F32(scale))


def _InvFreq(dim: int) -> np.ndarray:
  """Yarn's frequencies for a rotary part of `dim`: pair j keeps 1 /
  theta^(2j / dim) where it turns more than beta_fast times inside the
  original window, has it divided by the factor where it turns fewer than
  beta_slow times, and a linear ramp over j between the two pairs."""
  a = _ARCH
  theta, factor = a["rope_theta"], a["rope_factor"]
  freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
  if factor == 1.0:
    return freq.astype(np.float32)
  pair = lambda turns: dim * math.log(
      a["rope_original"] / (turns * 2 * math.pi)) / (2 * math.log(theta))
  low = max(math.floor(pair(a["beta_fast"])), 0)
  high = min(math.ceil(pair(a["beta_slow"])), dim - 1)
  ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
  return (freq / factor * ramp + freq * (1 - ramp)).astype(np.float32)


def _Rotate(x, pos):
  """x [T, ..., R] at positions pos [T]: pair (2j, 2j + 1) turned by
  pos * inv_freq[j]."""
  ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * (
      _InvFreq(x.shape[-1]))
  cos, sin = jnp.cos(ang), jnp.sin(ang)
  even, odd = x[..., 0::2], x[..., 1::2]
  return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                   -1).reshape(x.shape)


def _QueryScale(pos):
  a = _ARCH
  m = 0.1 * a["mscale_all_dim"] * math.log(a["rope_factor"]) + 1.0
  scale = (a["nope"] + a["rope"]) ** -0.5 * m * m
  return scale * (1.0 + a["scaling_beta"] * jnp.log1p(
      jnp.floor(pos.astype(jnp.float32) / a["rope_original"])))


def _Experts(ff, rep, g):
  """g [B, D] normed tokens -> [B, D]: each token's chosen experts that are
  held here, weighted, and the shared expert. ff: the expert layer's
  weights stacked over the layers, rep: which layer."""
  b, d = g.shape
  p = jax.nn.softmax(g @ _F32(ff["w_router"][rep]), -1)       # all experts
  top, idx = jax.lax.top_k(p, _ARCH["experts_per_token"])
  w = top / jnp.sum(top, -1, keepdims=True)
  weight = jnp.zeros(p.shape, jnp.float32).at[
      jnp.arange(b)[:, None], idx].set(w)
  chosen = jnp.zeros(p.shape, bool).at[jnp.arange(b)[:, None], idx].set(True)
  held, first = ff["w_up"].shape[1], _ARCH["first_expert"]
  g_pad = jnp.concatenate([g, jnp.zeros((1, d), g.dtype)])

  def _Expert(k, y):
    mine = chosen[:, first + k]
    routed = jnp.nonzero(mine, size=b, fill_value=b)[0]
    w_gate, w_up, w_down = (_F32(ff[name][rep, k]) for name in (
        "w_gate", "w_up", "w_down"))

    def _Piece(c, y):
      tok = jax.lax.dynamic_slice(
          jnp.concatenate([routed, jnp.full((_PIECE,), b, routed.dtype)]),
          (c * _PIECE,), (_PIECE,))
      x = g_pad[tok]
      out = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
      share = jnp.concatenate([weight[:, first + k], jnp.zeros((1,))])[tok]
      return y.at[tok].add(out * share[:, None], mode="drop")

    return jax.lax.fori_loop(0, (jnp.sum(mine) + _PIECE - 1) // _PIECE,
                             _Piece, y)

  y = jax.lax.fori_loop(0, held, _Expert, jnp.zeros((b, d), jnp.float32))
  w_sg, w_su, w_sd = (_F32(ff[name][rep]) for name in (
      "w_shared_gate", "w_shared_up", "w_shared_down"))
  return y + (jax.nn.silu(g @ w_sg) * (g @ w_su)) @ w_sd


def _Layer(layer, rep, x, n_blocks):
  """One layer over the first n_blocks blocks of x [S, D]."""
  at, ff = layer["self_atten"]["atten"], layer["fflayer"]
  s_len, _ = x.shape
  blk = min(_BLOCK, s_len)
  w_qa, w_qb, w_kva, w_kvb, w_o = (_F32(at[k][rep]) for k in (
      "w_qa", "w_qb", "w_kva", "w_kvb", "w_post"))
  q_ln, kv_ln = at["q_ln"]["scale"][rep], at["kv_ln"]["scale"][rep]
  ln_1 = layer["self_atten"]["ln"]["scale"][rep]
  ln_2 = ff["ln"]["scale"][rep]
  rank, n = w_kvb.shape[:2]
  nope = _ARCH["nope"]

  def _Slice(a, i):
    return jax.lax.dynamic_slice_in_dim(a, i * blk, blk, 0)

  # every token's latent row first: a query block reads back to 0
  def _Latent(i, rows):
    c_all, r_all = rows
    pos = i * blk + jnp.arange(blk)
    kv = _RmsNorm(_Slice(x, i), ln_1) @ w_kva
    return (jax.lax.dynamic_update_slice_in_dim(
        c_all, _RmsNorm(kv[:, :rank], kv_ln), i * blk, 0),
            jax.lax.dynamic_update_slice_in_dim(
                r_all, _Rotate(kv[:, rank:], pos), i * blk, 0))

  c_all, r_all = jax.lax.fori_loop(0, n_blocks, _Latent, (
      jnp.zeros((s_len, rank), jnp.float32),
      jnp.zeros((s_len, w_kva.shape[1] - rank), jnp.float32)))

  def _Block(i, y):
    xb = _Slice(x, i)
    pos = i * blk + jnp.arange(blk)
    c_q = _RmsNorm(_RmsNorm(xb, ln_1) @ w_qa, q_ln)
    q = jnp.einsum("tr,rnh->tnh", c_q, w_qb) * _QueryScale(pos)[:, None, None]
    q_nope, q_rope = q[..., :nope], _Rotate(q[..., nope:], pos)

    def _Keys(j, carry):
      m, l, acc = carry
      key_pos = j * blk + jnp.arange(blk)
      # the key block's K and V by heads, from its latent rows
      kv = jnp.einsum("sr,rnh->snh", _Slice(c_all, j), w_kvb)
      s = (jnp.einsum("tnh,snh->nts", q_nope, kv[..., :nope])
           + jnp.einsum("tnh,sh->nts", q_rope, _Slice(r_all, j)))
      seen = key_pos[None, :] <= pos[:, None]
      s = jnp.where(seen, s, -1e30)
      m_new = jnp.maximum(m, jnp.max(s, -1))
      p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
      alpha = jnp.exp(m - m_new)
      acc = acc * alpha[..., None] + jnp.einsum(
          "nts,snh->nth", p, kv[..., nope:])
      return m_new, alpha * l + jnp.sum(p, -1), acc

    v = w_kvb.shape[-1] - nope
    _, l, acc = jax.lax.fori_loop(0, i + 1, _Keys, (
        jnp.full((n, blk), -1e30, jnp.float32),
        jnp.zeros((n, blk), jnp.float32),
        jnp.zeros((n, blk, v), jnp.float32)))
    hb = xb + jnp.einsum("nth,dnh->td", acc / l[..., None], w_o)
    return jax.lax.dynamic_update_slice_in_dim(
        y, hb + _Experts(ff, rep, _RmsNorm(hb, ln_2)), i * blk, 0)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _RowHidden(theta, ids, at):
  """ids [S] one right-padded sequence, at a scalar -> the final norm's
  output [D] there."""
  s_len = ids.shape[0]
  blk = min(_BLOCK, s_len)
  x = _F32(theta["emb"]["emb"][jnp.pad(ids, (0, -s_len % blk))])
  n_blocks = at // blk + 1
  body = theta["stack"]["body"]
  layers = body["fflayer"]["w_router"].shape[0]
  x = jax.lax.fori_loop(
      0, layers, lambda rep, x: _Layer(body, rep, x, n_blocks), x)
  return _RmsNorm(x[at], theta["final_ln"]["scale"])


def LogitsAt(theta, ids, at, logit_cap: float = 0.0):
  """ids [B, W] right-padded sequences, at [B] one position in each -> f32
  logits [B, V] there over the vocabulary the weights hold, one row after
  the other; the head by slices of the vocabulary."""
  head = theta["head"]["emb"]
  v, d = head.shape
  _ARCH.clear()
  _ARCH.update(_Arch(d))
  x = jax.lax.map(lambda row: _RowHidden(theta, row[0], row[1]), (ids, at))
  pieces = next(c for c in (64, 32, 16, 8, 4, 2, 1) if v % c == 0)
  logits = jax.lax.map(lambda w: x @ _F32(w).T,
                       head.reshape(pieces, v // pieces, d))
  logits = logits.transpose(1, 0, 2).reshape(x.shape[0], v)
  if logit_cap > 0:
    logits = logit_cap * jnp.tanh(logits / logit_cap)
  return logits
