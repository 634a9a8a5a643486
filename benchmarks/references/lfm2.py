"""Plain reference of LFM2-24B-A2B's layer equations (`model_type` lfm2_moe),
written from the published `config.json` and the public modelling code of
that model type in plain jax.numpy and float32: no kernel, no cache, no pages,
no slot state, no sort, no batching, and none of the program's layer code. It
reads only the names and shapes of the program's weights.

D the model dim, eps 1e-5 in every norm, no bias anywhere:

    h_0 = Emb[ids]                                          (no multiplier)
    h <- h + Mixer(RMSNorm_op(h));   h <- h + FF(RMSNorm_ffn(h))
    logits = RMSNorm(h) Emb^T                               (tied, no cap)

`Mixer(x)`, a `conv` layer: [B; C; X] = x W_in (D -> 3 D, in that order);
u = B * X; c_t = sum_{k<K} w[k] * u_{t-K+1+k}, depthwise over the D channels,
causal, K = 3 taps, no bias, NO activation; y = C * c; out = y W_out.

`Mixer(x)`, a `full_attention` layer: q = x W_q [N heads of H], k = x W_k,
v = x W_v [Nkv heads of H]; q and k each through an RMSNorm over the head's H
dims with a learned scale of H (one for q, one for k, shared by the heads),
BEFORE the rotation; RoPE over the whole head (the halves of H rotated, theta
1e6, no scaling); query i sees every key j <= i; softmax(q . k / sqrt(H)),
query head n reads KV head n // (N / Nkv); out = concat_n(ctx_n) W_o.

`FF(u)`, a dense layer: `(silu(u W_1) * (u W_3)) W_2`.

`FF(u)`, an expert layer: `s = sigmoid(u W_r)` over all E experts; the k
experts of largest `s + b` (`b` a stored per-expert bias that chooses and
does not weigh); `w = s[chosen] / (sum s[chosen] + 1e-6)` (times
`routed_scaling_factor`, 1); `FF(u) = sum_i w_i Expert_i(u)`, every expert the
same SwiGLU; no shared expert.

Where the program departs from these lines, and the reference follows the
WEIGHTS it is handed: a norm's scale is stored as an offset from one
(multiply by 1 + scale; the init is 0), the head norms' too; the dense
feed-forward's W_1 (the one under the silu) is stored as `ffn_in`, its W_3 as
`ffn_gate`; the stack is stored as blocks in sequence, each a short list of
layers stacked over its repeats (`stack.block_<b>.x_layers[j]`); what a layer
is, is told by the names it holds (`atten.conv_w`: a convolution layer,
`atten.w_query`: an attention layer; `fflayer.w_router`: experts,
`fflayer.ffn_in`: dense). The program leaves the 1e-6 out of the weights' sum
(under f32's resolution of a sum of four sigmoids times four; the reference
keeps it). Nothing else.

Heads, KV heads, head size, the taps, the experts and every width are read
off the weights' shapes. What no shape tells (the experts a token, the RoPE
base, the eps, how many leading layers are dense, which the weights' names
are held to) is read from this configuration's own file,
`benchmarks/configs/lfm2_24b.json`: the published keys where the weights have
the published model dim, the `rehearsal` group's where they have its
(`_Arch`). A test at yet another size states its own through `SeededWeights`'
keyword arguments.

How it is computed (`LogitsAt`), to stay inside what a chip has free beside a
10.4 GB model and 2 GB of pages: one row at a time (`lax.map`), only the
blocks of `_BLOCK` tokens up to the row's own `at` (causality keeps what
follows out of sight; a dynamic trip count), a convolution layer block after
block with the last K - 1 rows of u handed on, attention by blocks of queries
against blocks of keys with a running softmax, weights upcast where they are
used, one expert at a time over the tokens that chose it, gathered in pieces
of `_PIECE` (a layer's experts are 2.4 GB in f32 and never exist).

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
    os.path.abspath(__file__))), "configs", "lfm2_24b.json")
# what a caller stated through SeededWeights; it outranks the file
_STATED: dict = {}
_ARCH: dict = {}


def _Arch(model_dim: int) -> dict:
  """What the weights' shapes do not say, for weights of `model_dim`: from
  the configuration file's published keys, or from its `rehearsal` group
  where the weights are that size."""
  with open(_CONFIG) as f:
    cfg = json.load(f)
  arch = {"dense_layers": cfg["num_dense_layers_held"],
          "experts_per_token": cfg["num_experts_per_tok"],
          "route_scale": float(cfg["routed_scaling_factor"]),
          "route_norm": bool(cfg["norm_topk_prob"]),
          "rope_theta": float(cfg["rope_parameters"]["rope_theta"]),
          "eps": float(cfg["norm_eps"])}
  small = cfg["rehearsal"]
  if model_dim == small["model_dim"] != cfg["model_dim"]:
    arch["experts_per_token"] = small["task_params"][
        "expert_ffn_tpl.num_experts_per_token"]
  arch.update(_STATED)
  return arch


def _Pattern(n: int):
  """A fixed pattern over n entries, evenly spread in [-0.5, 0.5)."""
  return ((jnp.arange(n) * 37) % n).astype(jnp.float32) / n - 0.5


def _Blocks(theta) -> list:
  """[(a block's layers, its repeats)] in stack order."""
  stack, out = theta["stack"], []
  while f"block_{len(out)}" in stack:
    layers = stack[f"block_{len(out)}"]["x_layers"]
    reps = jax.tree_util.tree_leaves(layers[0])[0].shape[0]
    out.append((layers, reps))
  return out


def SeededWeights(theta, attention_out_scale: float = 1.0,
                  router_scale: float = 1.0,
                  router_reads_share: float = 0.0, router_layer_gain=(),
                  router_bias_spread: float = 0.0,
                  head_norm_spread: float = 0.0, **stated):
  """The weights a cell makes from its seed (the program's own init), with:

  attention_out_scale  every attention layer's output projection scaled;
  head_norm_spread   the learned scales of the two head norms (q's and k's),
                     all one at the seed, set to a fixed pattern of that
                     spread about one: a norm whose scale is the same in
                     every dim commutes with the rotation, and a head norm
                     applied AFTER the rotation could not be told from one
                     applied before it;
  router_scale       every router scaled;
  router_layer_gain  a factor more for each expert layer's router, in stack
                     order (the routers read their layer's NORMED input, and
                     the dimensions they read shrink under the norm as the
                     stream round them grows);
  router_reads_share where > 0, the routing made a function of the token
                     alone: the first `share` of the model's dimensions are
                     written by no layer (those columns of every branch's
                     output projection are zero), so the stream holds the
                     embedding there, exactly, in every layer and at every
                     precision (the embedding has no multiplier), and the
                     routers read those dimensions only (their other rows
                     are zero);
  router_bias_spread the selection bias `b`, zero at the seed, set to a
                     fixed pattern of that spread over the experts (so that
                     a bias that weighed as well as chose would show).

  The configuration file's `weights_reason` has the readings that set them.
  `**stated`: the sizes of `_ARCH` that a test at another size changes
  (dense_layers, experts_per_token, route_scale, route_norm, rope_theta,
  eps)."""
  _STATED.clear()
  _STATED.update(stated)
  d = theta["emb"]["emb"].shape[1]
  reads = max(1, int(d * router_reads_share)) if router_reads_share else 0
  # where the model dimension lies in each: w_post [.., D, N, H], w_out
  # [.., D, D], ffn_out.w [.., F, D], w_down [.., experts, F, D] (written:
  # the first `reads` are zeroed); w_router [.., D, experts] (read: all but
  # the first `reads` are zeroed)
  model_axis = {"w_post": -3, "w_out": -1, "w_down": -1, "w_router": -2}
  scales = {"w_post": attention_out_scale, "w_router": router_scale}
  # an expert layer's place among the expert layers, by (block, layer,
  # repeat): the stack runs a block's repeats one after the other
  gain, seen = {}, 0
  for b, (layers, reps) in enumerate(_Blocks(theta)):
    mine = [j for j, l in enumerate(layers) if "w_router" in l["fflayer"]]
    for j in mine:
      gain[(f"block_{b}", str(j))] = [
          router_layer_gain[seen + r * len(mine) + mine.index(j)]
          if router_layer_gain else 1.0 for r in range(reps)]
    seen += reps * len(mine)
  assert not router_layer_gain or seen == len(router_layer_gain), (
      seen, router_layer_gain)

  def _Leaf(path, x):
    keys = [str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path]
    name = keys[-1]
    if keys[-2:] == ["ffn_out", "w"]:
      name = "w_down"              # the dense feed-forward's, [.., F, D]
    scale = scales.get(name, 1.0)
    if scale != 1.0:
      x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    if name == "w_router" and router_layer_gain:
      by_rep = jnp.asarray(gain[(keys[1], keys[3])], jnp.float32)
      x = (x.astype(jnp.float32) * by_rep[:, None, None]).astype(x.dtype)
    if reads and name in model_axis:
      at = jnp.arange(d).reshape((d,) + (1,) * (-model_axis[name] - 1))
      keep = at < reads if name == "w_router" else at >= reads
      x = jnp.where(keep, x, jnp.zeros_like(x))
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


def _ShortConv(layer, rep, x, n_blocks):
  """x [S, D] -> x + Conv(LN(x)) over the first n_blocks blocks, a block
  after the other with the last K - 1 rows of u handed on."""
  m = layer["atten"]
  s_len, d = x.shape
  blk = min(_BLOCK, s_len)
  w_in, w_out = _F32(m["w_in"][rep]), _F32(m["w_out"][rep])
  taps = _F32(m["conv_w"][rep])                                 # [K, D]
  k = taps.shape[0]
  ln = layer["ln"]["scale"][rep]

  def _Block(i, carry):
    y, tail = carry
    xb = _Slice(x, i, blk)
    b, c, xx = jnp.split(_RmsNorm(xb, ln) @ w_in, 3, axis=-1)
    u = jnp.concatenate([tail, b * xx])                         # [K-1+blk, D]
    conv = sum(taps[j] * u[j:j + blk] for j in range(k))
    out = (c * conv) @ w_out
    return (jax.lax.dynamic_update_slice_in_dim(y, xb + out, i * blk, 0),
            u[blk:])

  y, _ = jax.lax.fori_loop(0, n_blocks, _Block,
                           (x, jnp.zeros((k - 1, d), jnp.float32)))
  return y


def _Attention(layer, rep, x, n_blocks):
  """x [S, D] -> x + Attn(LN(x)) over the first n_blocks blocks."""
  at = layer["atten"]
  s_len, _ = x.shape
  blk = min(_BLOCK, s_len)
  w_q, w_k, w_v, w_o = (_F32(at[k][rep]) for k in (
      "w_query", "w_key", "w_value", "w_post"))
  q_scale, k_scale = at["q_norm"]["scale"][rep], at["k_norm"]["scale"][rep]
  ln = layer["ln"]["scale"][rep]
  n, h = w_q.shape[1:]
  n_kv = w_k.shape[1]
  group = n // n_kv

  # keys and values of every block first: a query block reads back to 0
  def _KeysValues(i, kv):
    k_all, v_all = kv
    u = _RmsNorm(_Slice(x, i, blk), ln)
    k = _Rope(_RmsNorm(jnp.einsum("td,dnh->tnh", u, w_k), k_scale),
              i * blk + jnp.arange(blk))
    v = jnp.einsum("td,dnh->tnh", u, w_v)
    return (jax.lax.dynamic_update_slice_in_dim(k_all, k, i * blk, 0),
            jax.lax.dynamic_update_slice_in_dim(v_all, v, i * blk, 0))

  zeros = jnp.zeros((s_len, n_kv, h), jnp.float32)
  k_all, v_all = jax.lax.fori_loop(0, n_blocks, _KeysValues, (zeros, zeros))

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    pos = i * blk + jnp.arange(blk)
    u = _RmsNorm(xb, ln)
    q = _Rope(_RmsNorm(jnp.einsum("td,dnh->tnh", u, w_q), q_scale), pos)
    q = (q / math.sqrt(h)).reshape(blk, n_kv, group, h)

    def _Keys(j, carry):
      m, l, acc = carry
      key_pos = j * blk + jnp.arange(blk)
      s = jnp.einsum("qgjh,kgh->gjqk", q, _Slice(k_all, j, blk))
      seen = key_pos[None, :] <= pos[:, None]
      s = jnp.where(seen, s, -1e30)
      m_new = jnp.maximum(m, jnp.max(s, -1))
      pr = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
      alpha = jnp.exp(m - m_new)
      acc = acc * alpha[..., None] + jnp.einsum(
          "gjqk,kgh->gjqh", pr, _Slice(v_all, j, blk))
      return m_new, alpha * l + jnp.sum(pr, -1), acc

    _, l, acc = jax.lax.fori_loop(0, i + 1, _Keys, (
        jnp.full((n_kv, group, blk), -1e30, jnp.float32),
        jnp.zeros((n_kv, group, blk), jnp.float32),
        jnp.zeros((n_kv, group, blk, h), jnp.float32)))
    ctx = (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(blk, n, h)
    out = jnp.einsum("tnh,dnh->td", ctx, w_o)
    return jax.lax.dynamic_update_slice_in_dim(y, xb + out, i * blk, 0)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def Route(ff, rep, u):
  """u [B, D] normed tokens -> (the chosen experts [B, k], their weights
  [B, k]) of the expert layer `ff` at repeat `rep`."""
  s = jax.nn.sigmoid(u @ _F32(ff["w_router"][rep]))             # [B, E]
  _, idx = jax.lax.top_k(s + _F32(ff["router_bias"][rep]),
                         _ARCH["experts_per_token"])
  w = jnp.take_along_axis(s, idx, axis=-1)
  if _ARCH["route_norm"]:
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
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
  """x [S, D] -> x + FF(LN(x)): dense or experts, by the names the layer's
  feed-forward holds."""
  ff = layer["fflayer"]
  blk = min(_BLOCK, x.shape[0])
  ln = ff["ln"]["scale"][rep]
  experts = "w_router" in ff
  if not experts:
    dense = [_F32(ff[name]["w"][rep]) for name in (
        "ffn_in", "ffn_gate", "ffn_out")]

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    u = _RmsNorm(xb, ln)
    out = _Routed(ff, rep, u) if experts else _Gated(u, *dense)
    return jax.lax.dynamic_update_slice_in_dim(y, xb + out, i * blk, 0)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _RowHidden(theta, ids, at):
  """ids [S] one right-padded sequence, at a scalar -> the final norm's
  output [D] there."""
  s_len = ids.shape[0]
  blk = min(_BLOCK, s_len)
  x = _F32(theta["emb"]["emb"][jnp.pad(ids, (0, -s_len % blk))])
  n_blocks = at // blk + 1
  depth = 0
  for layers, reps in _Blocks(theta):
    # the file states how many leading layers are dense: the weights' names
    # are held to it (a stack of other kinds is another model)
    for j, layer in enumerate(layers):
      for rep in range(reps):
        place = depth + rep * len(layers) + j
        assert ("ffn_in" in layer["fflayer"]) == (
            place < _ARCH["dense_layers"]), (place, _ARCH["dense_layers"])
    depth += reps * len(layers)

    def _Repeat(rep, x, layers=layers):
      for layer in layers:
        mixer = _ShortConv if "conv_w" in layer["atten"] else _Attention
        x = mixer(layer, rep, x, n_blocks)
        x = _FeedForward(layer, rep, x, n_blocks)
      return x

    x = jax.lax.fori_loop(0, reps, _Repeat, x)
  return _RmsNorm(x[at], theta["final_ln"]["scale"])


def LogitsAt(theta, ids, at, logit_cap: float = 0.0):
  """ids [B, W] right-padded sequences, at [B] one position in each -> f32
  logits [B, V] there, one row after the other; the head is the embedding
  table (tied), by slices of the vocabulary."""
  head = theta["emb"]["emb"]
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
