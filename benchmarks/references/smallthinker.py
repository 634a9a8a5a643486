"""Plain reference of SmallThinker-21BA3B-Instruct's layer equations, written
from the published `config.json` and model card in plain jax.numpy and
float32: no kernel, no cache, no sort, no batching, and none of the program's
layer code. It reads only the names and shapes of the program's weights.

Layer l, input x [T, D], RMSNorm with eps 1e-6 and a learned scale:

    r   = x W_r                                  router logits, from the layer's
                                                 un-normed INPUT, before attention
    u   = RMSNorm_1(x)
    q, k, v = u W_q [N, H], u W_k [Nkv, H], u W_v [Nkv, H]          no bias
    if the layer rotates:  q, k = RoPE(q, k; theta, whole head, halves rotated)
    s   = q_n . k_(n // G) / sqrt(H), causal; a window layer sees only keys j
          with i - window < j <= i
    h   = x + concat_n(softmax(s) v_(n // G)) W_o
    g   = RMSNorm_2(h)
    S   = top-k of r;  w = softmax(r restricted to S)
    y   = h + sum_{e in S} w_e (relu(g W_gate_e) * (g W_up_e)) W_down_e

A period of four layers (full attention without rotary, then three of the
window with it) repeats down the stack; final RMSNorm; an untied head; no
embedding scale; no logit cap unless one is passed.

Where the program departs from these lines, and the reference follows the
WEIGHTS it is handed: a norm's scale is stored as an offset from one (the
program's RmsNorm multiplies by 1 + scale; the init is 0); the head is stored
[V, D] like the embedding table. Nothing else.

Heads, KV heads, head size, experts and their width are read off the
weights' shapes. What no shape tells (the window, the experts a token, the
RoPE base, the norm's eps) is read from this configuration's own file,
`benchmarks/configs/smallthinker21b.json`: the published keys where the
weights have the published model dim, the `rehearsal` group's where they
have its (`_Arch`). A test at yet another size states its own through
`SeededWeights`' keyword arguments.

How it is computed (`LogitsAt`), to stay inside what a chip has free beside
the served model (1.6 GB) and inside ten seconds: one row at a time
(`lax.map`), only the blocks of `_BLOCK` tokens up to the row's own `at`
(causality keeps what follows out of sight; a dynamic trip count), attention
by blocks of queries against blocks of keys with a running softmax (a
[28, 14k, 14k] score tensor is 22 GB), weights upcast where they are used,
one expert at a time (a layer is 1.6 GB in f32). A token goes through its k
experts only: for each expert the tokens routed to it are gathered, in pieces
of `_PIECE`, and their weighted outputs added back. A dense loop over all the
experts would cost ten times that.

On a TPU an f32 matmul runs in lower precision unless told otherwise, so the
caller wraps this in jax.default_matmul_precision("highest").
"""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp

_BLOCK = 1024   # tokens a block of queries, and of keys
_PIECE = 128    # tokens of one expert computed together

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "smallthinker21b.json")
# what a caller stated through SeededWeights; it outranks the file
_STATED: dict = {}
_ARCH: dict = {}


def _Arch(model_dim: int) -> dict:
  """What the weights' shapes do not say, for weights of `model_dim`: from
  the configuration file's published keys (sliding_window_size,
  moe_num_active_primary_experts, rope_theta, rms_norm_eps, and the period
  of the two layouts, [windowed, rotates] a layer), or from its `rehearsal`
  group where the weights are that size."""
  with open(_CONFIG) as f:
    cfg = json.load(f)
  layouts = list(zip(cfg["sliding_window_layout"], cfg["rope_layout"]))
  period = next(k for k in range(1, len(layouts) + 1)
                if layouts == (layouts[:k] * len(layouts))[:len(layouts)])
  arch = {"window": cfg["sliding_window_size"],
          "experts_per_token": cfg["moe_num_active_primary_experts"],
          "rope_theta": float(cfg["rope_theta"]),
          "eps": float(cfg["rms_norm_eps"]),
          "period": tuple(layouts[:period])}
  small = cfg["rehearsal"]
  if model_dim == small["model_dim"] != cfg["model_dim"]:
    arch["window"] = small["task_params"]["sliding_window_size"]
    arch["experts_per_token"] = small["task_params"][
        "expert_ffn_tpl.num_experts_per_token"]
  arch.update(_STATED)
  return arch


def SeededWeights(theta, attention_out_scale: float = 1.0,
                  router_scale: float = 1.0, router_reads_share: float = 0.0,
                  window: int | None = None,
                  experts_per_token: int | None = None):
  """The weights a cell makes from its seed (the program's own init), with
  every layer's attention output projection and every router scaled, and,
  where `router_reads_share` > 0, the routing made a function of the token
  alone: the first `share` of the model's dimensions are written by no layer
  (those columns of every attention output projection and every expert's
  down projection are zero), so the stream holds the embedding there,
  exactly, in every layer and at every precision, and the routers read those
  dimensions only (their other rows are zero). Router logits are then the
  same numbers in the program and here, and a near-tie between a token's
  k-th and (k+1)-th expert is decided alike; everywhere else the weights
  are the seed's. The configuration file's `weights_reason` has the readings
  that made this necessary and set the scales. `window` and
  `experts_per_token` state the two sizes of `_ARCH` that a smaller
  configuration changes."""
  _STATED.clear()
  if window is not None:
    _STATED["window"] = int(window)
  if experts_per_token is not None:
    _STATED["experts_per_token"] = int(experts_per_token)
  scales = {"w_post": attention_out_scale, "w_router": router_scale}
  d = theta["head"]["emb"].shape[1]
  reads = max(1, int(d * router_reads_share)) if router_reads_share else 0
  # where the model dimension lies in each: w_post [.., D, N, H],
  # w_down [.., E, F, D] (written: the first `reads` are zeroed),
  # w_router [.., D, E] (read: all but the first `reads` are zeroed)
  model_axis = {"w_post": -3, "w_down": -1, "w_router": -2}

  def _Leaf(path, x):
    name = str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1])))
    scale = scales.get(name, 1.0)
    if scale != 1.0:
      x = (x.astype(jnp.float32) * scale).astype(x.dtype)
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


def _Rope(x, pos):
  """x [B, N, H] at positions pos [B]: the halves of H rotated by
  pos / theta^(i / half)."""
  half = x.shape[-1] // 2
  timescale = _ARCH["rope_theta"] ** (
      jnp.arange(half, dtype=jnp.float32) / half)
  ang = pos.astype(jnp.float32)[:, None, None] / timescale
  sin, cos = jnp.sin(ang), jnp.cos(ang)
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _Experts(ff, rep, g, r):
  """g [B, D] normed tokens, r [B, E] router logits -> [B, D]: each token's
  k experts, weighted. ff: the expert block's weights stacked over the
  stack's repeats, rep: which repeat."""
  b, d = g.shape
  e = r.shape[-1]
  top, idx = jax.lax.top_k(r, _ARCH["experts_per_token"])
  w = jax.nn.softmax(top, -1)
  # weight of (token, expert), 0 where the token did not choose the expert
  weight = jnp.zeros((b, e), jnp.float32).at[
      jnp.arange(b)[:, None], idx].set(w)
  g_pad = jnp.concatenate([g, jnp.zeros((1, d), g.dtype)])

  def _Expert(k, y):
    routed = jnp.nonzero(weight[:, k] > 0, size=b, fill_value=b)[0]
    n = jnp.sum(weight[:, k] > 0)
    w_gate, w_up, w_down = (_F32(ff[name][rep, k]) for name in (
        "w_gate", "w_up", "w_down"))

    def _Piece(c, y):
      tok = jax.lax.dynamic_slice(
          jnp.concatenate([routed, jnp.full((_PIECE,), b, routed.dtype)]),
          (c * _PIECE,), (_PIECE,))
      x = g_pad[tok]
      out = (jax.nn.relu(x @ w_gate) * (x @ w_up)) @ w_down
      share = jnp.concatenate([weight[:, k], jnp.zeros((1,))])[tok]
      return y.at[tok].add(out * share[:, None], mode="drop")

    return jax.lax.fori_loop(0, (n + _PIECE - 1) // _PIECE, _Piece, y)

  return jax.lax.fori_loop(0, e, _Expert, jnp.zeros((b, d), jnp.float32))


def _Layer(layer, rep, x, n_blocks, windowed, rotates):
  """One layer over the first n_blocks blocks of x [S, D]. layer: its
  weights stacked over the stack's repeats, rep: which repeat (an index
  into the stack, so that no repeat's experts are copied out of it)."""
  at, ff = layer["self_atten"]["atten"], layer["fflayer"]
  s_len, d = x.shape
  blk = min(_BLOCK, s_len)
  w_q, w_k, w_v, w_o = (_F32(at[k][rep]) for k in (
      "w_query", "w_key", "w_value", "w_post"))
  ln_1 = layer["self_atten"]["ln"]["scale"][rep]
  ln_2, w_router = ff["ln"]["scale"][rep], _F32(ff["w_router"][rep])
  n, h = w_q.shape[1:]
  n_kv = w_k.shape[1]
  group = n // n_kv
  window = _ARCH["window"]

  def _Slice(a, i):
    return jax.lax.dynamic_slice_in_dim(a, i * blk, blk, 0)

  # keys and values of every block first: a query block reads back to 0
  def _KeysValues(i, kv):
    k_all, v_all = kv
    pos = i * blk + jnp.arange(blk)
    u = _RmsNorm(_Slice(x, i), ln_1)
    k = jnp.einsum("td,dnh->tnh", u, w_k)
    if rotates:
      k = _Rope(k, pos)
    v = jnp.einsum("td,dnh->tnh", u, w_v)
    return (jax.lax.dynamic_update_slice_in_dim(k_all, k, i * blk, 0),
            jax.lax.dynamic_update_slice_in_dim(v_all, v, i * blk, 0))

  zeros = jnp.zeros((s_len, n_kv, h), jnp.float32)
  k_all, v_all = jax.lax.fori_loop(0, n_blocks, _KeysValues, (zeros, zeros))

  def _Block(i, y):
    xb = _Slice(x, i)
    pos = i * blk + jnp.arange(blk)
    r = xb @ w_router
    u = _RmsNorm(xb, ln_1)
    q = jnp.einsum("td,dnh->tnh", u, w_q)
    if rotates:
      q = _Rope(q, pos)
    q = (q / math.sqrt(h)).reshape(blk, n_kv, group, h)

    def _Keys(j, carry):
      m, l, acc = carry
      key_pos = j * blk + jnp.arange(blk)
      s = jnp.einsum("qgjh,kgh->gjqk", q, _Slice(k_all, j))
      seen = key_pos[None, :] <= pos[:, None]
      if windowed:
        seen &= key_pos[None, :] > pos[:, None] - window
      s = jnp.where(seen, s, -1e30)
      m_new = jnp.maximum(m, jnp.max(s, -1))
      p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
      alpha = jnp.exp(m - m_new)
      acc = acc * alpha[..., None] + jnp.einsum(
          "gjqk,kgh->gjqh", p, _Slice(v_all, j))
      return m_new, alpha * l + jnp.sum(p, -1), acc

    first = jnp.maximum(i * blk - window + 1, 0) // blk if windowed else 0
    _, l, acc = jax.lax.fori_loop(first, i + 1, _Keys, (
        jnp.full((n_kv, group, blk), -1e30, jnp.float32),
        jnp.zeros((n_kv, group, blk), jnp.float32),
        jnp.zeros((n_kv, group, blk, h), jnp.float32)))
    ctx = (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(blk, n, h)
    hb = xb + jnp.einsum("tnh,dnh->td", ctx, w_o)
    g = _RmsNorm(hb, ln_2)
    return jax.lax.dynamic_update_slice_in_dim(
        y, hb + _Experts(ff, rep, g, r), i * blk, 0)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _RowHidden(theta, ids, at):
  """ids [S] one right-padded sequence, at a scalar -> (the final norm's
  output [D] there, every layer's router logits [layers, E] there)."""
  s_len = ids.shape[0]
  blk = min(_BLOCK, s_len)
  x = _F32(theta["emb"]["emb"][jnp.pad(ids, (0, -s_len % blk))])
  n_blocks = at // blk + 1
  period = _ARCH["period"]
  body = theta["stack"]["body"]["x_layers"]
  assert len(body) == len(period), (len(body), period)
  repeats = body[0]["fflayer"]["w_router"].shape[0]

  def _Period(rep, carry):
    # the weights are stacked [repeats, ...]: one period after the other
    x, routes = carry
    for k, (layer, (windowed, rotates)) in enumerate(zip(body, period)):
      routes = routes.at[rep * len(period) + k].set(
          x[at] @ _F32(layer["fflayer"]["w_router"][rep]))
      x = _Layer(layer, rep, x, n_blocks, windowed, rotates)
    return x, routes

  experts = body[0]["fflayer"]["w_router"].shape[-1]
  x, routes = jax.lax.fori_loop(0, repeats, _Period, (x, jnp.zeros(
      (repeats * len(period), experts), jnp.float32)))
  return _RmsNorm(x[at], theta["final_ln"]["scale"]), routes


def RouterLogitsAt(theta, ids, at):
  """ids [B, W], at [B] as LogitsAt -> f32 [B, layers, E]: the router
  logits of every layer at each row's position, in the stack's order
  (benchmarks/tools/moe_controls.py lays the program's beside them)."""
  _ARCH.clear()
  _ARCH.update(_Arch(theta["head"]["emb"].shape[1]))
  return jax.lax.map(lambda row: _RowHidden(theta, row[0], row[1])[1],
                     (ids, at))


def LogitsAt(theta, ids, at, logit_cap: float = 0.0):
  """ids [B, W] right-padded sequences, at [B] one position in each -> f32
  logits [B, V] there, one row after the other; the head by slices of the
  vocabulary (upcast whole it is 1.6 GB)."""
  head = theta["head"]["emb"]
  v, d = head.shape
  _ARCH.clear()
  _ARCH.update(_Arch(d))
  x = jax.lax.map(lambda row: _RowHidden(theta, row[0], row[1])[0], (ids, at))
  pieces = next(c for c in (64, 32, 16, 8, 4, 2, 1) if v % c == 0)
  logits = jax.lax.map(lambda w: x @ _F32(w).T,
                       head.reshape(pieces, v // pieces, d))
  logits = logits.transpose(1, 0, 2).reshape(x.shape[0], v)
  if logit_cap > 0:
    logits = logit_cap * jnp.tanh(logits / logit_cap)
  return logits
