"""Plain reference of Trinity-Mini's layer equations (`model_type` afmoe),
written from the published `config.json` and the public modelling code of
that model type in plain jax.numpy and float32: no kernel, no cache, no pages,
no sort, no batching, and none of the program's layer code. It reads only the
names and shapes of the program's weights.

D the model dim, eps 1e-5 in every norm, no bias anywhere. Every layer has
FOUR RMSNorms, each branch normed going in and coming out:

    h_0 = Emb[ids] * sqrt(D)                                    (mup_enabled)
    h <- h + PostLN_a(Attn(LN_a(h)));   h <- h + PostLN_f(FF(LN_f(h)))
    logits = RMSNorm(h) Head^T                     (untied head, no scaling)

`Attn(u)`: q = u W_q [N heads of H], k = u W_k, v = u W_v [Nkv heads of H],
g = u W_g [N, H]. q and k each through an RMSNorm over the head's H dims with
a learned scale of H (one for q, one for k, shared by the heads), BEFORE any
rotation. A window layer rotates q and k (theta 10,000, the halves of H
rotated, no scaling) and query i sees keys j with i - window < j <= i; a full
layer rotates nothing, carries no position at all, and sees every key j <= i.
softmax(q . k / sqrt(H)), query head n reads KV head n // (N / Nkv);
`out = (concat_n(ctx_n) * sigmoid(g)) W_o`: the gate is elementwise over the
N H head dims, BEFORE the output projection.

`FF(u)`, a dense layer: `(silu(u W_gate) * (u W_up)) W_down`.

`FF(u)`, an expert layer: `s = sigmoid(u W_r)` over all E experts; the k
experts of largest `s + b` (`b` a stored per-expert bias that chooses and
does not weigh; one group, so no group limit); `w = s[chosen] / (sum
s[chosen] + 1e-20) * route_scale`; `routed = sum_i w_i Expert_i(u)`, every
expert and the one shared expert `(silu(u W_g) * (u W_u)) W_d`;
`FF(u) = Shared(u) + routed`.

Where the program departs from these lines, and the reference follows the
WEIGHTS it is handed: a norm's scale is stored as an offset from one
(multiply by 1 + scale; the init is 0), the head norms' too; the head is
stored [V, D] like the embedding table; the dense feed-forward's W_gate (the
one under the silu) is stored as `ffn_in`, its W_up as `ffn_gate`; the
stack is stored as blocks in sequence, each a short list of layers stacked
over its repeats (`stack.block_<b>.x_layers[j]`), and a layer's feed-forward
is told by the names it holds (`fflayer.w_router`: experts; `fflayer.ffn_in`:
dense). Nothing else.

Heads, KV heads, head size, the experts and every width are read off the
weights' shapes. What no shape tells (each stored layer's window, 0 for a
full layer, and with it whether it rotates; the experts a token; the route
scale; the RoPE base; the eps; how many leading layers are dense, which the
weights' names are held to) is read from this configuration's own file,
`benchmarks/configs/trinitymini.json`: the published keys and the file's
`attention_windows` where the weights have the published model dim, the
`rehearsal` group's where they have its (`_Arch`). A test at yet another size
states its own through `SeededWeights`' keyword arguments.

How it is computed (`LogitsAt`), to stay inside what a chip has free beside
an 8.5 GB model and 3 GB of pages: one row at a time (`lax.map`), only the
blocks of `_BLOCK` tokens up to the row's own `at` (causality keeps what
follows out of sight; a dynamic trip count), attention by blocks of queries
against blocks of keys with a running softmax (a window layer starts at the
first block its window reaches), weights upcast where they are used, one
expert at a time over the tokens that chose it, gathered in pieces of
`_PIECE` (a layer's experts are 3.2 GB in f32 and never exist).

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
    os.path.abspath(__file__))), "configs", "trinitymini.json")
# what a caller stated through SeededWeights; it outranks the file
_STATED: dict = {}
_ARCH: dict = {}


def _Arch(model_dim: int) -> dict:
  """What the weights' shapes do not say, for weights of `model_dim`: from
  the configuration file's published keys (and its `attention_windows`, one
  entry a stored layer), or from its `rehearsal` group where the weights are
  that size."""
  with open(_CONFIG) as f:
    cfg = json.load(f)
  arch = {"windows": tuple(cfg["attention_windows"]),
          "dense_layers": cfg["num_dense_layers_held"],
          "experts_per_token": cfg["num_experts_per_tok"],
          "route_scale": float(cfg["route_scale"]),
          "route_norm": bool(cfg["route_norm"]),
          "rope_theta": float(cfg["rope_theta"]),
          "eps": float(cfg["rms_norm_eps"]),
          "embedding_scale": math.sqrt(model_dim) if cfg["mup_enabled"]
          else 1.0}
  small = cfg["rehearsal"]
  if model_dim == small["model_dim"] != cfg["model_dim"]:
    arch["windows"] = tuple(small["attention_windows"])
    arch["experts_per_token"] = small["task_params"][
        "expert_ffn_tpl.num_experts_per_token"]
  arch.update(_STATED)
  return arch


def _Pattern(n: int):
  """A fixed pattern over n entries, evenly spread in [-0.5, 0.5)."""
  return ((jnp.arange(n) * 37) % n).astype(jnp.float32) / n - 0.5


def SeededWeights(theta, router_scale: float = 1.0,
                  router_reads_share: float = 0.0,
                  router_bias_spread: float = 0.0,
                  head_norm_spread: float = 0.0, **stated):
  """The weights a cell makes from its seed (the program's own init), with:

  head_norm_spread   the learned scales of the two head norms (q's and k's),
                     all one at the seed, set to a fixed pattern of that
                     spread about one: a norm whose scale is the same in
                     every dim commutes with the rotation, and a head norm
                     applied AFTER the rotation could not be told from one
                     applied before it;
  router_scale       every router scaled;
  router_reads_share where > 0, the routing made a function of the token
                     alone: the first `share` of the model's dimensions are
                     written by no layer (every branch's OUTPUT norm has the
                     scale 0 there: its stored offset is -1), so the stream
                     holds the scaled embedding there in every layer, and
                     the routers read those dimensions only (their other
                     rows are zero). The embedding table's entries in those
                     dimensions are rounded to ONE significant bit (a power
                     of two, the sign kept): sqrt(D) is no bf16 number, so a
                     bf16 program multiplies by bf16(sqrt(D)) and rounds the
                     product, and only for a power of two is that product
                     exact; every such entry of the program's stream is then
                     the reference's times ONE common factor
                     (bf16(sqrt(D)) / sqrt(D)), the router's logits differ by
                     one common factor (that one times the norms'), and a
                     near-tie between a token's k-th and (k+1)-th expert is
                     decided alike;
  router_bias_spread the selection bias `b`, zero at the seed, set to a
                     fixed pattern of that spread over the experts (so that
                     a bias that weighed as well as chose would show).

  The configuration file's `weights_reason` has the readings that set them.
  `**stated`: the sizes of `_ARCH` that a test at another size changes
  (windows, dense_layers, experts_per_token, route_scale, route_norm,
  rope_theta, eps, embedding_scale)."""
  _STATED.clear()
  _STATED.update(stated)
  d = theta["head"]["emb"].shape[1]
  reads = max(1, int(d * router_reads_share)) if router_reads_share else 0

  def _Leaf(path, x):
    keys = [str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path]
    name = keys[-1]
    if name == "w_router":
      scaled = x.astype(jnp.float32) * router_scale
      if reads:                                       # [.., D, E]
        scaled = jnp.where(jnp.arange(d)[:, None] < reads, scaled, 0.0)
      x = scaled.astype(x.dtype)
    if reads and keys[-2:] == ["post_ln", "scale"]:   # [.., D]
      x = jnp.where(jnp.arange(d) < reads, -1.0, x).astype(x.dtype)
    if reads and keys[:2] == ["emb", "emb"]:          # [V, D]
      mantissa, exponent = jnp.frexp(x.astype(jnp.float32))
      power = jnp.ldexp(0.5 * jnp.sign(mantissa), exponent)
      x = jnp.where(jnp.arange(d) < reads, power, x.astype(jnp.float32)
                    ).astype(x.dtype)
    if name == "router_bias" and router_bias_spread:
      x = jnp.broadcast_to(router_bias_spread * _Pattern(x.shape[-1]),
                           x.shape).astype(x.dtype)
    if keys[-2] in ("q_norm", "k_norm") and head_norm_spread:
      # another order for k than for q (the stored value is the offset)
      pattern = _Pattern(x.shape[-1])
      pattern = pattern if keys[-2] == "q_norm" else -pattern[::-1]
      x = jnp.broadcast_to(head_norm_spread * pattern, x.shape
                           ).astype(x.dtype)
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


def _Slice(a, i, blk):
  return jax.lax.dynamic_slice_in_dim(a, i * blk, blk, 0)


def _Gated(u, w_gate, w_up, w_down):
  return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _Attention(layer, rep, x, n_blocks, window):
  """x [S, D] -> x + PostLN(Attn(LN(x))) over the first n_blocks blocks;
  window 0: a full layer, which rotates nothing."""
  at = layer["atten"]
  s_len, _ = x.shape
  blk = min(_BLOCK, s_len)
  w_q, w_k, w_v, w_g, w_o = (_F32(at[k][rep]) for k in (
      "w_query", "w_key", "w_value", "w_gate", "w_post"))
  q_scale, k_scale = at["q_norm"]["scale"][rep], at["k_norm"]["scale"][rep]
  ln, post_ln = layer["ln"]["scale"][rep], layer["post_ln"]["scale"][rep]
  n, h = w_q.shape[1:]
  n_kv = w_k.shape[1]
  group = n // n_kv

  # keys and values of every block first: a query block reads back to 0
  def _KeysValues(i, kv):
    k_all, v_all = kv
    u = _RmsNorm(_Slice(x, i, blk), ln)
    k = _RmsNorm(jnp.einsum("td,dnh->tnh", u, w_k), k_scale)
    if window:
      k = _Rope(k, i * blk + jnp.arange(blk))
    v = jnp.einsum("td,dnh->tnh", u, w_v)
    return (jax.lax.dynamic_update_slice_in_dim(k_all, k, i * blk, 0),
            jax.lax.dynamic_update_slice_in_dim(v_all, v, i * blk, 0))

  zeros = jnp.zeros((s_len, n_kv, h), jnp.float32)
  k_all, v_all = jax.lax.fori_loop(0, n_blocks, _KeysValues, (zeros, zeros))

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    pos = i * blk + jnp.arange(blk)
    u = _RmsNorm(xb, ln)
    q = _RmsNorm(jnp.einsum("td,dnh->tnh", u, w_q), q_scale)
    if window:
      q = _Rope(q, pos)
    q = (q / math.sqrt(h)).reshape(blk, n_kv, group, h)

    def _Keys(j, carry):
      m, l, acc = carry
      key_pos = j * blk + jnp.arange(blk)
      s = jnp.einsum("qgjh,kgh->gjqk", q, _Slice(k_all, j, blk))
      seen = key_pos[None, :] <= pos[:, None]
      if window:
        seen &= key_pos[None, :] > pos[:, None] - window
      s = jnp.where(seen, s, -1e30)
      m_new = jnp.maximum(m, jnp.max(s, -1))
      pr = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
      alpha = jnp.exp(m - m_new)
      acc = acc * alpha[..., None] + jnp.einsum(
          "gjqk,kgh->gjqh", pr, _Slice(v_all, j, blk))
      return m_new, alpha * l + jnp.sum(pr, -1), acc

    first = jnp.maximum(i * blk - window + 1, 0) // blk if window else 0
    _, l, acc = jax.lax.fori_loop(first, i + 1, _Keys, (
        jnp.full((n_kv, group, blk), -1e30, jnp.float32),
        jnp.zeros((n_kv, group, blk), jnp.float32),
        jnp.zeros((n_kv, group, blk, h), jnp.float32)))
    ctx = (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(blk, n, h)
    gate = jax.nn.sigmoid(jnp.einsum("td,dnh->tnh", u, w_g))
    out = jnp.einsum("tnh,dnh->td", ctx * gate, w_o)
    return jax.lax.dynamic_update_slice_in_dim(
        y, xb + _RmsNorm(out, post_ln), i * blk, 0)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def Route(ff, rep, u):
  """u [B, D] normed tokens -> (the chosen experts [B, k], their weights
  [B, k]) of the expert layer `ff` at repeat `rep`."""
  s = jax.nn.sigmoid(u @ _F32(ff["w_router"][rep]))             # [B, E]
  _, idx = jax.lax.top_k(s + _F32(ff["router_bias"][rep]),
                         _ARCH["experts_per_token"])
  w = jnp.take_along_axis(s, idx, axis=-1)
  if _ARCH["route_norm"]:
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
  return idx, w * _ARCH["route_scale"]


def _Routed(ff, rep, u):
  """u [B, D] normed tokens -> [B, D]: each token's k experts, weighted."""
  b, d = u.shape
  e = ff["w_router"].shape[-1]
  idx, w = Route(ff, rep, u)
  # weight of (token, expert), and whether the token chose the expert
  weight = jnp.zeros((b, e), jnp.float32).at[
      jnp.arange(b)[:, None], idx].set(w)
  mask = jnp.zeros((b, e), bool).at[jnp.arange(b)[:, None], idx].set(True)
  u_pad = jnp.concatenate([u, jnp.zeros((1, d), u.dtype)])

  def _Expert(k, y):
    routed = jnp.nonzero(mask[:, k], size=b, fill_value=b)[0]
    count = jnp.sum(mask[:, k])
    w_gate, w_up, w_down = (_F32(ff[name][rep, k]) for name in (
        "w_gate", "w_up", "w_down"))

    def _Piece(c, y):
      tok = jax.lax.dynamic_slice(
          jnp.concatenate([routed, jnp.full((_PIECE,), b, routed.dtype)]),
          (c * _PIECE,), (_PIECE,))
      out = _Gated(u_pad[tok], w_gate, w_up, w_down)
      share = jnp.concatenate([weight[:, k], jnp.zeros((1,))])[tok]
      return y.at[tok].add(out * share[:, None], mode="drop")

    return jax.lax.fori_loop(0, (count + _PIECE - 1) // _PIECE, _Piece, y)

  return jax.lax.fori_loop(0, e, _Expert, jnp.zeros((b, d), jnp.float32))


def _FeedForward(layer, rep, x, n_blocks):
  """x [S, D] -> x + PostLN(FF(LN(x))): dense or experts, by the names the
  layer's feed-forward holds."""
  ff = layer["fflayer"]
  blk = min(_BLOCK, x.shape[0])
  ln, post_ln = ff["ln"]["scale"][rep], ff["post_ln"]["scale"][rep]
  experts = "w_router" in ff
  if experts:
    shared = [_F32(ff[name][rep]) for name in (
        "w_shared_gate", "w_shared_up", "w_shared_down")]
  else:
    dense = [_F32(ff[name]["w"][rep]) for name in (
        "ffn_in", "ffn_gate", "ffn_out")]

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    u = _RmsNorm(xb, ln)
    out = (_Gated(u, *shared) + _Routed(ff, rep, u) if experts
           else _Gated(u, *dense))
    return jax.lax.dynamic_update_slice_in_dim(
        y, xb + _RmsNorm(out, post_ln), i * blk, 0)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _Blocks(theta) -> list:
  """[(a block's layers, its repeats)] in stack order."""
  stack, out = theta["stack"], []
  while f"block_{len(out)}" in stack:
    layers = stack[f"block_{len(out)}"]["x_layers"]
    reps = jax.tree_util.tree_leaves(layers[0])[0].shape[0]
    out.append((layers, reps))
  return out


def _RowHidden(theta, ids, at):
  """ids [S] one right-padded sequence, at a scalar -> the final norm's
  output [D] there."""
  s_len = ids.shape[0]
  blk = min(_BLOCK, s_len)
  x = _ARCH["embedding_scale"] * _F32(
      theta["emb"]["emb"][jnp.pad(ids, (0, -s_len % blk))])
  n_blocks = at // blk + 1
  windows, depth = _ARCH["windows"], 0
  for layers, reps in _Blocks(theta):
    mine = windows[depth:depth + reps * len(layers)]
    depth += len(mine)
    # a scanned block's repeats are alike: a layer's window is its place's
    per_layer = mine[:len(layers)]
    assert mine == per_layer * reps, (mine, reps)

    # the file states how many leading layers are dense: the weights' names
    # are held to it (a stack of other kinds is another model)
    for j, layer in enumerate(layers):
      for rep in range(reps):
        place = depth - len(mine) + rep * len(layers) + j
        assert ("ffn_in" in layer["fflayer"]) == (
            place < _ARCH["dense_layers"]), (place, _ARCH["dense_layers"])

    def _Repeat(rep, x, layers=layers, per_layer=per_layer):
      for layer, window in zip(layers, per_layer):
        x = _Attention(layer, rep, x, n_blocks, window)
        x = _FeedForward(layer, rep, x, n_blocks)
      return x

    x = jax.lax.fori_loop(0, reps, _Repeat, x)
  assert depth == len(windows), (depth, windows)
  return _RmsNorm(x[at], theta["final_ln"]["scale"])


def LogitsAt(theta, ids, at, logit_cap: float = 0.0):
  """ids [B, W] right-padded sequences, at [B] one position in each -> f32
  logits [B, V] there, one row after the other; the head by slices of the
  vocabulary (upcast whole it is 1.6 GB)."""
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
