"""Plain reference of granite-4.0-h-small's layer equations (`model_type`
granitemoehybrid), written from the published `config.json` and the catalog
row's description in plain jax.numpy and float32: no kernel, no cache, no
slot state, no sort, no chunked scan, and none of the program's layer code.
It reads only the names and shapes of the program's weights.

D the model dim, eps 1e-5 everywhere, no bias but the convolution's, no
position encoding anywhere. `f` = residual_multiplier, `u = RMSNorm(h)`:

    h_0 = embedding_multiplier * Emb[ids]
    a published layer:  h <- h + f * Mixer(u);  h <- h + f * Experts(u)
    logits = RMSNorm(h) Emb^T / logits_scaling          (tied head)

The program writes a branch a letter of `hybrid_override_pattern` (`M`, `*`,
`E`), so a published layer is two stored layers, each ONE branch; the
reference walks the stored layers and multiplies every branch by `f`.

`M`, Mamba-2 (Hm heads of P channels, E = Hm P; G groups of N state indices,
G = 1 as published; K taps; C = E + 2 G N):

    [z; xBC; dt] = u W_in                                D -> E + C + Hm
    xBC_t = silu(b_conv + sum_{k<K} w_conv[k] * xBC_{t-K+1+k})   depthwise, causal
    [x; B; C] = xBC       x [Hm, P], B and C [G, N]
    delta_t = softplus(dt_t + dt_bias)                   [Hm]
    S_t[h] = exp(delta_t[h] A[h]) S_{t-1}[h] + delta_t[h] x_t[h] (x) B_t[g(h)]
             A = -exp(A_log) [Hm]; S [Hm, P, N], S_0 = 0
    y_t[h] = S_t[h] C_t[g(h)] + D_skip[h] x_t[h]
    y = RMSNorm_groups(y * silu(z)) * w_norm   the gate BEFORE the norm; the
             mean square over each of G groups of E / G channels (all 8,192)
    out = y W_out                                        E -> D

`*`, attention: q N heads of H, k and v Nkv heads of H, no bias, causal
softmax(attention_multiplier * q k), query head n reads KV head
n // (N / Nkv), `out = concat_n(ctx_n) W_o`.

`E`, experts: `l = u W_r` over ALL the router's experts; the k largest;
`w = softmax` over those k; `Expert_e(u) = (silu(u W_g,e) * (u W_u,e)) W_d,e`;
`routed = sum_i w_i Expert_i(u)`; `shared` the same form; `out = routed +
shared`. THE SHARE: the weights hold experts [first_expert, first_expert +
held) of the router's width; a chosen expert outside that run adds nothing
(its weight stays in the softmax over the k), in program and reference
alike, and nothing stands in for the chips that hold the others. The logits
are over the rows of the table the weights hold (a slice of the vocabulary).

Where the program departs from these lines, and the reference follows the
WEIGHTS it is handed: a norm's scale is stored as an offset from one
(multiply by 1 + scale; the init is 0), the gated norm's too; W_in's columns
are in the order [z; xBC; dt] and xBC's in the order [x; B; C]; the stack is
stored as blocks in sequence, each a short list of layers stacked over its
repeats (`stack.block_<b>.x_layers[j]`), and a layer is told by the names it
holds (`fflayer`: E; `atten.w_in`: M; `atten.w_query`: *). Nothing else.

Hm, E, C, K, the heads, the experts held and the router's width are read off
the weights' shapes. What no shape tells (G, N, the experts a token, the four
multipliers, the first held expert, the eps) is read from this
configuration's own file, `benchmarks/configs/granite4hsmall.json`: the
published keys where the weights have the published model dim, the
`rehearsal` group's where they have its (`_Arch`). A test at yet another
size states its own through `SeededWeights`' keyword arguments.

How it is computed (`LogitsAt`), to stay inside what a chip has free beside
a 9.5 GB model and 4.5 GB of decode state: as references/nemotron_h.py: one
row at a time, only the blocks of `_BLOCK` tokens up to the row's own `at`,
a Mamba-2 layer block by block with the state and the convolution's last
K - 1 inputs carried and the scan inside a block a loop over single tokens,
attention by blocks with a running softmax, weights upcast where they are
used, one expert at a time over the tokens that chose it.

On a TPU an f32 matmul runs in lower precision unless told otherwise, so the
caller wraps this in jax.default_matmul_precision("highest").
"""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp

_BLOCK = 1024   # tokens a block: of a Mamba-2 layer, of queries, of keys
_PIECE = 128    # tokens of one expert computed together

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "granite4hsmall.json")
# what a caller stated through SeededWeights; it outranks the file
_STATED: dict = {}
_ARCH: dict = {}


def _Arch(model_dim: int) -> dict:
  """What the weights' shapes do not say, for weights of `model_dim`: from
  the configuration file's published keys, or from its `rehearsal` group
  where the weights are that size."""
  with open(_CONFIG) as f:
    cfg = json.load(f)
  tp = cfg["task_params"]
  small = cfg["rehearsal"]
  if model_dim == small["model_dim"] != cfg["model_dim"]:
    tp = {**tp, **small["task_params"]}
  arch = {"groups": tp["mixer_tpl.num_groups"],
          "state_dim": tp["mixer_tpl.state_dim"],
          "experts_per_token": tp["expert_ffn_tpl.num_experts_per_token"],
          "first_expert": tp["expert_ffn_tpl.first_expert"],
          "eps": float(tp["norm_tpl.epsilon"]),
          "embedding_multiplier": float(tp["embedding_multiplier"]),
          "residual_multiplier": float(tp["residual_multiplier"]),
          "attention_multiplier": float(tp["atten_tpl.score_scale"]),
          "logits_scaling": float(tp["logits_scaling"])}
  arch.update(_STATED)
  return arch


def SeededWeights(theta, attention_out_scale: float = 1.0,
                  query_scale: float = 1.0, router_scale: float = 1.0,
                  router_reads_share: float = 0.0,
                  ssm_decay_scale: float = 1.0, **stated):
  """The weights a cell makes from its seed (the program's own init), with:

  attention_out_scale  every attention layer's output projection scaled;
  query_scale        every attention layer's query projection scaled: the
                     published factor on q . k is 1 / H, not H ** -0.5, and
                     the seed's projections give scores of spread sqrt(H)
                     before it, so at the seed every softmax is near uniform
                     whatever the factor; sqrt(H) here gives the scores the
                     spread one, as H ** -0.5 would give unscaled weights;
  router_scale       every router scaled;
  router_reads_share where > 0, the routing made a function of the token
                     alone: the first `share` of the model's dimensions are
                     written by no layer (those columns of every branch's
                     output projection are zero), so the stream holds the
                     scaled embedding there, exactly, in every layer and at
                     every precision, and the routers read those dimensions
                     only (their other rows are zero). The table's
                     entries in those dimensions are rounded to 6
                     significant bits, so that 12 x an entry (two bits more)
                     is a bf16 number: the product the program rounds to its
                     stream's precision is then the reference's, to the bit;
  ssm_decay_scale    every Mamba-2 head's A scaled (`a_log` shifted by its
                     logarithm): the init's A of 1..16 forgets within tens
                     of tokens, so nothing read hundreds of tokens into a
                     request depends on what its slot's state held before.

  The configuration file's `weights_reason` has the readings that set them.
  `**stated`: the sizes of `_ARCH` that a test at another size changes
  (groups, state_dim, experts_per_token, first_expert, eps and the four
  multipliers)."""
  _STATED.clear()
  _STATED.update(stated)
  scales = {"w_post": attention_out_scale, "w_query": query_scale,
            "w_router": router_scale}
  d = theta["emb"]["emb"].shape[1]
  reads = max(1, int(d * router_reads_share)) if router_reads_share else 0
  # where the model dimension lies in each: w_post [.., D, N, H], w_out
  # [.., E, D], w_down [.., experts, F, D], w_shared_down [.., F, D]
  # (written: the first `reads` are zeroed); w_router [.., D, experts]
  # (read: all but the first `reads` are zeroed)
  model_axis = {"w_post": -3, "w_out": -1, "w_down": -1, "w_shared_down": -1,
                "w_router": -2}

  def _Leaf(path, x):
    name = str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1])))
    scale = scales.get(name, 1.0)
    if scale != 1.0:
      x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    if reads and name in model_axis:
      at = jnp.arange(d).reshape((d,) + (1,) * (-model_axis[name] - 1))
      keep = at < reads if name == "w_router" else at >= reads
      x = jnp.where(keep, x, jnp.zeros_like(x))
    if reads and name == "emb":
      mantissa, exponent = jnp.frexp(x.astype(jnp.float32))
      coarse = jnp.ldexp(jnp.round(mantissa * 64.0) / 64.0, exponent)
      x = jnp.where(jnp.arange(d) < reads, coarse, x.astype(jnp.float32)
                    ).astype(x.dtype)
    if name == "a_log" and ssm_decay_scale != 1.0:
      x = (x.astype(jnp.float32) + math.log(ssm_decay_scale)).astype(x.dtype)
    return x

  return jax.tree_util.tree_map_with_path(_Leaf, theta)


def _F32(x):
  return jnp.asarray(x, jnp.float32)


def _RmsNorm(x, scale):
  ms = jnp.mean(jnp.square(x), -1, keepdims=True)
  return x * jax.lax.rsqrt(ms + _ARCH["eps"]) * (1.0 + _F32(scale))


def _Slice(a, i, blk):
  return jax.lax.dynamic_slice_in_dim(a, i * blk, blk, 0)


def _Mamba2(layer, rep, x, n_blocks):
  """x [S, D] -> x + the Mamba-2 branch, over the first n_blocks blocks."""
  m = layer["atten"]
  s_len, _ = x.shape
  blk = min(_BLOCK, s_len)
  w_in, w_out = _F32(m["w_in"][rep]), _F32(m["w_out"][rep])
  conv_w, conv_b = _F32(m["conv_w"][rep]), _F32(m["conv_b"][rep])
  dt_bias, d_skip = _F32(m["dt_bias"][rep]), _F32(m["d_skip"][rep])
  a = -jnp.exp(_F32(m["a_log"][rep]))                          # [Hm]
  w_norm, ln = m["norm_scale"][rep], layer["ln"]["scale"][rep]
  hm, (e, _) = a.shape[0], w_out.shape
  k, c = conv_w.shape
  p = e // hm
  g, n = _ARCH["groups"], _ARCH["state_dim"]
  assert c == e + 2 * g * n, (c, e, g, n)

  def _Block(i, carry):
    y_all, state, tail = carry                  # [S, D], [Hm,P,N], [K-1, C]
    xb = _Slice(x, i, blk)
    proj = _RmsNorm(xb, ln) @ w_in
    z, xbc, dt = proj[:, :e], proj[:, e:e + c], proj[:, e + c:]
    padded = jnp.concatenate([tail, xbc])                       # [K-1+blk, C]
    conv = sum(conv_w[j] * padded[j:j + blk] for j in range(k))
    act = jax.nn.silu(conv + conv_b)
    u = act[:, :e].reshape(blk, hm, p)
    b_t = act[:, e:e + g * n].reshape(blk, g, n)
    c_t = act[:, e + g * n:].reshape(blk, g, n)
    delta = jax.nn.softplus(dt + dt_bias)                       # [blk, Hm]

    def _Token(s, xs):
      dd, uu, bb, cc = xs
      bb, cc = jnp.repeat(bb, hm // g, 0), jnp.repeat(cc, hm // g, 0)
      s = (jnp.exp(dd * a)[:, None, None] * s
           + (dd[:, None] * uu)[:, :, None] * bb[:, None, :])
      return s, jnp.sum(s * cc[:, None, :], -1) + d_skip[:, None] * uu

    state, y = jax.lax.scan(_Token, state, (delta, u, b_t, c_t))
    gated = (y.reshape(blk, e) * jax.nn.silu(z)).reshape(blk, g, e // g)
    ms = jnp.mean(jnp.square(gated), -1, keepdims=True)
    normed = (gated * jax.lax.rsqrt(ms + _ARCH["eps"])).reshape(blk, e)
    out = xb + _ARCH["residual_multiplier"] * (
        (normed * (1.0 + _F32(w_norm))) @ w_out)
    return (jax.lax.dynamic_update_slice_in_dim(y_all, out, i * blk, 0),
            state, padded[blk:])

  y_all, _, _ = jax.lax.fori_loop(0, n_blocks, _Block, (
      x, jnp.zeros((hm, p, n), jnp.float32),
      jnp.zeros((k - 1, c), jnp.float32)))
  return y_all


def _Gated(u, w_gate, w_up, w_down):
  return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _Routed(ff, rep, u):
  """u [B, D] normed tokens -> [B, D]: each token's k experts, weighted; of
  those only the ones the weights hold add anything."""
  b, d = u.shape
  logits = u @ _F32(ff["w_router"][rep])                         # [B, experts]
  e = logits.shape[-1]
  top, idx = jax.lax.top_k(logits, _ARCH["experts_per_token"])
  w = jax.nn.softmax(top, axis=-1)
  # weight of (token, expert), 0 where the token did not choose the expert
  weight = jnp.zeros((b, e), jnp.float32).at[
      jnp.arange(b)[:, None], idx].set(w)
  mask = jnp.zeros((b, e), bool).at[jnp.arange(b)[:, None], idx].set(True)
  u_pad = jnp.concatenate([u, jnp.zeros((1, d), u.dtype)])
  held = ff["w_up"].shape[1]
  first = _ARCH["first_expert"]

  def _Expert(j, y):
    k = first + j                     # the router's column of held expert j
    routed = jnp.nonzero(mask[:, k], size=b, fill_value=b)[0]
    count = jnp.sum(mask[:, k])
    w_gate, w_up, w_down = (_F32(ff[name][rep, j]) for name in (
        "w_gate", "w_up", "w_down"))

    def _Piece(c, y):
      tok = jax.lax.dynamic_slice(
          jnp.concatenate([routed, jnp.full((_PIECE,), b, routed.dtype)]),
          (c * _PIECE,), (_PIECE,))
      out = _Gated(u_pad[tok], w_gate, w_up, w_down)
      share = jnp.concatenate([weight[:, k], jnp.zeros((1,))])[tok]
      return y.at[tok].add(out * share[:, None], mode="drop")

    return jax.lax.fori_loop(0, (count + _PIECE - 1) // _PIECE, _Piece, y)

  return jax.lax.fori_loop(0, held, _Expert, jnp.zeros((b, d), jnp.float32))


def _Experts(layer, rep, x, n_blocks):
  ff = layer["fflayer"]
  blk = min(_BLOCK, x.shape[0])
  ln = ff["ln"]["scale"][rep]
  shared = [_F32(ff[name][rep]) for name in (
      "w_shared_gate", "w_shared_up", "w_shared_down")]

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    u = _RmsNorm(xb, ln)
    out = _Routed(ff, rep, u) + _Gated(u, *shared)
    return jax.lax.dynamic_update_slice_in_dim(
        y, xb + _ARCH["residual_multiplier"] * out, i * blk, 0)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _Attention(layer, rep, x, n_blocks):
  at = layer["atten"]
  s_len, _ = x.shape
  blk = min(_BLOCK, s_len)
  w_q, w_k, w_v, w_o = (_F32(at[k][rep]) for k in (
      "w_query", "w_key", "w_value", "w_post"))
  ln = layer["ln"]["scale"][rep]
  n, h = w_q.shape[1:]
  n_kv = w_k.shape[1]
  group = n // n_kv

  # keys and values of every block first: a query block reads back to 0
  def _KeysValues(i, kv):
    k_all, v_all = kv
    u = _RmsNorm(_Slice(x, i, blk), ln)
    return (jax.lax.dynamic_update_slice_in_dim(
        k_all, jnp.einsum("td,dnh->tnh", u, w_k), i * blk, 0),
            jax.lax.dynamic_update_slice_in_dim(
                v_all, jnp.einsum("td,dnh->tnh", u, w_v), i * blk, 0))

  zeros = jnp.zeros((s_len, n_kv, h), jnp.float32)
  k_all, v_all = jax.lax.fori_loop(0, n_blocks, _KeysValues, (zeros, zeros))

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    pos = i * blk + jnp.arange(blk)
    q = jnp.einsum("td,dnh->tnh", _RmsNorm(xb, ln), w_q)
    q = (q * _ARCH["attention_multiplier"]).reshape(blk, n_kv, group, h)

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
    return jax.lax.dynamic_update_slice_in_dim(
        y, xb + _ARCH["residual_multiplier"] * jnp.einsum(
            "tnh,dnh->td", ctx, w_o), i * blk, 0)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _Blocks(theta) -> list:
  """[(a block's layers, its repeats)] in stack order."""
  stack, out = theta["stack"], []
  while f"block_{len(out)}" in stack:
    layers = stack[f"block_{len(out)}"]["x_layers"]
    reps = jax.tree_util.tree_leaves(layers[0])[0].shape[0]
    out.append((layers, reps))
  return out


def _LayerFn(layer):
  if "fflayer" in layer:
    return _Experts
  return _Mamba2 if "w_in" in layer["atten"] else _Attention


def _RowHidden(theta, ids, at):
  """ids [S] one right-padded sequence, at a scalar -> the final norm's
  output [D] there."""
  s_len = ids.shape[0]
  blk = min(_BLOCK, s_len)
  x = _ARCH["embedding_multiplier"] * _F32(
      theta["emb"]["emb"][jnp.pad(ids, (0, -s_len % blk))])
  n_blocks = at // blk + 1
  for layers, reps in _Blocks(theta):
    def _Repeat(rep, x, layers=layers):
      for layer in layers:
        x = _LayerFn(layer)(layer, rep, x, n_blocks)
      return x
    x = jax.lax.fori_loop(0, reps, _Repeat, x)
  return _RmsNorm(x[at], theta["final_ln"]["scale"])


def LogitsAt(theta, ids, at, logit_cap: float = 0.0):
  """ids [B, W] right-padded sequences, at [B] one position in each -> f32
  logits [B, V] there over the rows of the table the weights hold, one row
  after the other; the tied head by slices of the vocabulary."""
  head = theta["emb"]["emb"]
  v, d = head.shape
  _ARCH.clear()
  _ARCH.update(_Arch(d))
  x = jax.lax.map(lambda row: _RowHidden(theta, row[0], row[1]), (ids, at))
  pieces = next(c for c in (64, 32, 16, 8, 4, 2, 1) if v % c == 0)
  logits = jax.lax.map(lambda w: x @ _F32(w).T,
                       head.reshape(pieces, v // pieces, d))
  logits = logits.transpose(1, 0, 2).reshape(x.shape[0], v)
  logits = logits / _ARCH["logits_scaling"]
  if logit_cap > 0:
    logits = logit_cap * jnp.tanh(logits / logit_cap)
  return logits
