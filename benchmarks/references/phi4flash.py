"""Plain reference of Phi-4-mini-flash-reasoning's layer equations, written
from the published `config.json`, `modeling_phi4flash.py`, arXiv:2507.06607
and arXiv:2410.05258 in plain jax.numpy and float32: no kernel, no cache, no
page, no packed axis, and none of the program's layer code. It reads only the
names and shapes of the program's weights.

Layer l of n (0-indexed), input h [T, D]; LN is LayerNorm with scale and
bias, eps 1e-5:

    h += Mixer_l(LN_1(h));   h += (silu(g W_gate) * (g W_up)) W_down, g = LN_2(h)

- Mamba-1 (l even, l <= n/2): [u; z] = x W_in; c_t = silu(b_conv + sum_k
  w_conv[k] u_{t-K+1+k}) (zeros before the first token); [r; B; C] = c W_x;
  delta = softplus(r W_dt + b_dt); A = -exp(A_log); s_t = exp(delta_t (x) A)
  s_{t-1} + (delta_t c_t) (x) B_t; y_t = s_t C_t + D c_t; out = (y_t
  silu(z_t)) W_out. Layer n/2 also hands m_t = y_t to the layers after it.
- Differential attention (l odd): q as N heads of H, k and v as Nk heads of
  H; query pair j = heads (2j, 2j+1), K pair i = j // (pairs / K pairs) =
  heads (2i, 2i+1), V_i = [v_2i; v_2i+1]; a1 = softmax(q_2j k_2i^T /
  sqrt(H)), a2 = softmax(q_2j+1 k_2i+1^T / sqrt(H)), causal; o_j = (1 -
  lambda_init) RMSNorm_2H((a1 - lambda a2) V_i); out = W_o concat_j o_j;
  lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, lambda_init = 0.8
  - 0.6 exp(-0.3 l). l < n/2: within `window` keys, the query's own
  included. l = n/2 + 1: over everything. l > n/2 + 1: over everything,
  with layer n/2 + 1's k and v (the layer has none of its own).
- Gated memory unit (l even, l > n/2): out = (silu(x W_1) * m_t) W_2.

Final LayerNorm, the head tied to the embedding table, no embedding scale,
no position encoding, no logit cap unless one is passed.

Where the program departs from these lines, and the reference follows the
WEIGHTS it is handed: a norm's scale is stored as an offset from one (1 +
scale, init 0), the pair norm's too; `A_log` is stored [N, E] and the
convolution's taps [K, E]; the feed-forward's gate is named `ffn_in` and its
up projection `ffn_gate`; the stack's layers come in blocks, each stacked
over its repeats. Nothing else.

Heads, head size, state indices, taps and ranks are read off the weights'
shapes; a layer's kind off the names of its weights and its depth. What no
shape tells (the window, eps) is read from this configuration's own file,
`benchmarks/configs/phi4flash.json`: the published keys where the weights
have the published model dim, the `rehearsal` group's where they have its. A
test at another size states its own through `SeededWeights`.

How it is computed (`LogitsAt`), to stay inside what a chip has free beside
the served model: one row at a time (`lax.map`), only the blocks of `_BLOCK`
tokens up to the row's own `at` (a dynamic trip count), attention by blocks
of queries against blocks of keys with a running softmax, the scan as a
`lax.scan` over the tokens of a block with the state carried from block to
block, weights upcast where they are used, the head by slices of the
vocabulary.

On a TPU an f32 matmul runs in lower precision unless told otherwise, so the
caller wraps this in jax.default_matmul_precision("highest").
"""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp

_BLOCK = 1024   # tokens a block of queries, of keys, and of the scan

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "phi4flash.json")
_STATED: dict = {}
_ARCH: dict = {}


def _Arch(model_dim: int) -> dict:
  with open(_CONFIG) as f:
    cfg = json.load(f)
  arch = {"window": cfg["sliding_window"], "eps": float(cfg["layer_norm_eps"])}
  small = cfg["rehearsal"]
  if model_dim == small["model_dim"] != cfg["model_dim"]:
    arch["window"] = small["task_params"]["sliding_window_size"]
  arch.update(_STATED)
  return arch


def SeededWeights(theta, attention_out_scale: float = 1.0,
                  window: int | None = None):
  """The weights a cell makes from its seed (the program's own init, which
  is the published one for `A_log`, the step sizes and `D`), with every
  attention layer's output projection scaled (the configuration file's
  `weights_reason` says why). `window` states the one size of `_ARCH` that a
  smaller configuration changes."""
  _STATED.clear()
  if window is not None:
    _STATED["window"] = int(window)
  scales = {"w_post": attention_out_scale}

  def _Leaf(path, x):
    name = str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1])))
    scale = scales.get(name, 1.0)
    if scale != 1.0:
      x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    return x

  return jax.tree_util.tree_map_with_path(_Leaf, theta)


def _F32(x):
  return jnp.asarray(x, jnp.float32)


def _LayerNorm(x, ln, rep):
  mean = jnp.mean(x, -1, keepdims=True)
  var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
  return ((x - mean) * jax.lax.rsqrt(var + _ARCH["eps"])
          * (1.0 + _F32(ln["scale"][rep])) + _F32(ln["bias"][rep]))


def _Slice(a, i, blk):
  return jax.lax.dynamic_slice_in_dim(a, i * blk, blk, 0)


def _Put(a, piece, i, blk):
  return jax.lax.dynamic_update_slice_in_dim(a, piece, i * blk, 0)


def _FeedForward(ff, rep, x, n_blocks, blk):
  w_gate, w_up, w_down = (_F32(ff[k]["w"][rep]) for k in (
      "ffn_in", "ffn_gate", "ffn_out"))

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    g = _LayerNorm(xb, ff["ln"], rep)
    return _Put(y, xb + (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down,
                i, blk)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _Mamba(layer, rep, x, n_blocks, blk):
  """-> (x + the mixer's output, y [S, E] the scan's output before the
  gate)."""
  m = layer["atten"]
  w_in, w_x, w_dt, w_out = (_F32(m[k][rep]) for k in (
      "w_in", "w_x", "w_dt", "w_out"))
  conv_w, conv_b = _F32(m["conv_w"][rep]), _F32(m["conv_b"][rep])  # [K, E]
  a = -jnp.exp(_F32(m["a_log"][rep])).T                            # [E, N]
  b_dt, d_skip = _F32(m["b_dt"][rep]), _F32(m["d_skip"][rep])
  taps, e = conv_w.shape
  r, n = w_dt.shape[0], a.shape[1]

  def _Block(i, carry):
    out, y_all, s, tail = carry
    xb = _Slice(x, i, blk)
    uz = _LayerNorm(xb, layer["ln"], rep) @ w_in
    u, z = uz[:, :e], uz[:, e:]
    seq = jnp.concatenate([tail, u])                 # the taps - 1 before it
    c = jax.nn.silu(conv_b + sum(
        conv_w[k] * seq[k:k + blk] for k in range(taps)))
    proj = c @ w_x
    delta = jax.nn.softplus(proj[:, :r] @ w_dt + b_dt)
    b_t, c_t = proj[:, r:r + n], proj[:, r + n:]

    def _Token(s, xs):
      d, cc, bb, rd = xs
      s = jnp.exp(d[:, None] * a) * s + (d * cc)[:, None] * bb[None, :]
      return s, s @ rd + d_skip * cc

    s, y = jax.lax.scan(_Token, s, (delta, c, b_t, c_t))
    out = _Put(out, xb + (y * jax.nn.silu(z)) @ w_out, i, blk)
    return out, _Put(y_all, y, i, blk), s, seq[blk:]

  out, y_all, _, _ = jax.lax.fori_loop(0, n_blocks, _Block, (
      x, jnp.zeros((x.shape[0], e), jnp.float32),
      jnp.zeros((e, n), jnp.float32), jnp.zeros((taps - 1, e), jnp.float32)))
  return out, y_all


def _MemoryUnit(layer, rep, x, memory, n_blocks, blk):
  w_1, w_2 = _F32(layer["atten"]["w_1"][rep]), _F32(layer["atten"]["w_2"][rep])

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    g = jax.nn.silu(_LayerNorm(xb, layer["ln"], rep) @ w_1)
    return _Put(y, xb + (g * _Slice(memory, i, blk)) @ w_2, i, blk)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _KeysValues(layer, rep, x, n_blocks, blk):
  at = layer["atten"]
  w_k, w_v = _F32(at["w_key"][rep]), _F32(at["w_value"][rep])

  def _Block(i, kv):
    u = _LayerNorm(_Slice(x, i, blk), layer["ln"], rep)
    return (_Put(kv[0], jnp.einsum("td,dnh->tnh", u, w_k), i, blk),
            _Put(kv[1], jnp.einsum("td,dnh->tnh", u, w_v), i, blk))

  zeros = jnp.zeros((x.shape[0],) + w_k.shape[1:], jnp.float32)
  return jax.lax.fori_loop(0, n_blocks, _Block, (zeros, zeros))


def _Attention(layer, rep, depth, x, k_all, v_all, n_blocks, blk, windowed):
  at = layer["atten"]
  w_q, w_o = _F32(at["w_query"][rep]), _F32(at["w_post"][rep])
  n, h = w_q.shape[1:]
  nk = k_all.shape[1]
  pairs, k_pairs = n // 2, nk // 2
  group = pairs // k_pairs
  window = _ARCH["window"]
  lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * depth.astype(jnp.float32))
  lam = (jnp.exp(jnp.sum(_F32(at["lambda_q1"][rep]) * _F32(at["lambda_k1"][rep])))
         - jnp.exp(jnp.sum(_F32(at["lambda_q2"][rep])
                           * _F32(at["lambda_k2"][rep]))) + lam_init)
  subln = 1.0 + _F32(at["subln_scale"][rep])

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    pos = i * blk + jnp.arange(blk)
    q = jnp.einsum("td,dnh->tnh", _LayerNorm(xb, layer["ln"], rep), w_q)
    # [T, K pairs, query pairs a K pair, which of the pair, H]
    q = (q / math.sqrt(h)).reshape(blk, k_pairs, group, 2, h)

    def _Keys(j, carry):
      m, l, acc = carry
      key_pos = j * blk + jnp.arange(blk)
      k = _Slice(k_all, j, blk).reshape(blk, k_pairs, 2, h)
      v = _Slice(v_all, j, blk).reshape(blk, k_pairs, 2 * h)
      s = jnp.einsum("qigrh,kirh->igrqk", q, k)
      seen = key_pos[None, :] <= pos[:, None]
      if windowed:
        seen &= key_pos[None, :] > pos[:, None] - window
      s = jnp.where(seen, s, -1e30)
      m_new = jnp.maximum(m, jnp.max(s, -1))
      p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
      alpha = jnp.exp(m - m_new)
      acc = acc * alpha[..., None] + jnp.einsum("igrqk,kih->igrqh", p, v)
      return m_new, alpha * l + jnp.sum(p, -1), acc

    first = jnp.maximum(i * blk - window + 1, 0) // blk if windowed else 0
    _, l, acc = jax.lax.fori_loop(first, i + 1, _Keys, (
        jnp.full((k_pairs, group, 2, blk), -1e30, jnp.float32),
        jnp.zeros((k_pairs, group, 2, blk), jnp.float32),
        jnp.zeros((k_pairs, group, 2, blk, 2 * h), jnp.float32)))
    o = acc / l[..., None]                              # a V, a softmax each
    o = o[:, :, 0] - lam * o[:, :, 1]                   # [i, g, blk, 2H]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + _ARCH["eps"]) * subln * (1.0 - lam_init)
    o = o.transpose(2, 0, 1, 3).reshape(blk, pairs, 2 * h)
    return _Put(y, xb + jnp.einsum("tjh,djh->td", o, w_o), i, blk)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _Blocks(theta):
  """[(the block's layers' weights, repeats, depth of its first layer)] in
  stack order, and the stack's depth."""
  stack = theta["stack"]
  out, depth = [], 0
  for b in range(len(stack)):
    layers = stack[f"block_{b}"]["x_layers"]
    reps = layers[0]["ln"]["scale"].shape[0]
    out.append((layers, reps, depth))
    depth += reps * len(layers)
  return out, depth


def _RowHidden(theta, ids, at):
  """ids [S] one right-padded sequence, at a scalar -> the final norm's
  output [D] there."""
  s_len = ids.shape[0]
  blk = min(_BLOCK, s_len)
  x = _F32(theta["emb"]["emb"][jnp.pad(ids, (0, -s_len % blk))])
  n_blocks = at // blk + 1
  blocks, n_layers = _Blocks(theta)
  half = n_layers // 2
  memory = kv = None
  for layers, reps, first in blocks:
    names = [set(layer["atten"]) for layer in layers]
    # what a block hands on besides the stream
    exports = first <= half < first + reps * len(layers)
    owns_full = first <= half + 1 < first + reps * len(layers)
    if exports:
      e = layers[(half - first) % len(layers)]["atten"]["w_out"].shape[1]
      memory = jnp.zeros((x.shape[0], e), jnp.float32)
    if owns_full:
      wk = layers[(half + 1 - first) % len(layers)]["atten"]["w_key"]
      zeros = jnp.zeros((x.shape[0],) + wk.shape[2:], jnp.float32)
      kv = (zeros, zeros)

    def _Repeat(rep, carry, layers=layers, names=names, first=first):
      x, memory, kv = carry
      for j, (layer, has) in enumerate(zip(layers, names)):
        depth = first + rep * len(layers) + j
        if "a_log" in has:
          x, y = _Mamba(layer, rep, x, n_blocks, blk)
          if memory is not None:
            memory = jnp.where(depth == half, y, memory)
        elif "w_1" in has:
          x = _MemoryUnit(layer, rep, x, memory, n_blocks, blk)
        elif "w_key" in has:
          mine = _KeysValues(layer, rep, x, n_blocks, blk)
          # a block's layers are of one kind: its first repeat's depth says
          # which (window below the middle, full at n/2 + 1)
          windowed = first + j < half
          x = _Attention(layer, rep, depth, x, *mine, n_blocks, blk, windowed)
          if kv is not None:
            kv = jax.tree_util.tree_map(
                lambda new, old: jnp.where(depth == half + 1, new, old),
                mine, kv)
        else:
          x = _Attention(layer, rep, depth, x, *kv, n_blocks, blk, False)
        x = _FeedForward(layer["fflayer"], rep, x, n_blocks, blk)
      return x, memory, kv

    x, memory, kv = jax.lax.fori_loop(0, reps, _Repeat, (x, memory, kv))
  ln = theta["final_ln"]
  row = x[at]
  mean = jnp.mean(row)
  var = jnp.mean(jnp.square(row - mean))
  return ((row - mean) * jax.lax.rsqrt(var + _ARCH["eps"])
          * (1.0 + _F32(ln["scale"])) + _F32(ln["bias"]))


def LogitsAt(theta, ids, at, logit_cap: float = 0.0):
  """ids [B, W] right-padded sequences, at [B] one position in each -> f32
  logits [B, V] there, one row after the other; the head (the embedding
  table) by slices of the vocabulary."""
  table = theta["emb"]["emb"]
  v, d = table.shape
  _ARCH.clear()
  _ARCH.update(_Arch(d))
  x = jax.lax.map(lambda row: _RowHidden(theta, row[0], row[1]), (ids, at))
  pieces = next(c for c in (64, 32, 16, 8, 4, 2, 1) if v % c == 0)
  logits = jax.lax.map(lambda w: x @ _F32(w).T,
                       table.reshape(pieces, v // pieces, d))
  logits = logits.transpose(1, 0, 2).reshape(x.shape[0], v)
  if logit_cap > 0:
    logits = logit_cap * jnp.tanh(logits / logit_cap)
  return logits
