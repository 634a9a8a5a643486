"""Plain reference of Brumby-14B-Base's layer equations (`model_type`
brumby), written from the published `config.json` (Qwen3's keys), the model
card and the power-retention paper (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239) in plain jax.numpy and float32: no
kernel, no cache, no slot state, no feature map, no chunks, and none of the
program's layer code. It reads only the names and shapes of the program's
weights.

D the model dim, N query heads over Nkv KV heads of H (query head a reads KV
head a // (N / Nkv)); pre-norm, as the Qwen3-14B checkpoint the model was
retrained from:

    h <- h + Retention(RMSNorm(h));  h <- h + W_down(silu(W_gate x) * (W_up x)),  x = RMSNorm(h)

    Retention(x), token t:
      q_t[a] = RoPE_t(RMSNorm_H(W_q x_t)[a])      k_t[c] = RoPE_t(RMSNorm_H(W_k x_t)[c])
      v_t[c] = (W_v x_t)[c]                       log g_t[c] = log sigmoid((W_g x_t)[c])
      w_ts   = (q_t[a] . k_s[c] / sqrt(H))^2 * exp(sum_{r=s+1..t} log g_r[c])   s <= t
      y_t[a] = sum_s w_ts v_s[c] / (sum_s w_ts + eps)
      out_t  = W_o concat_a y_t[a]

the ATTENTION form, the one the paper defines the layer by; its recurrent
and chunked forms are the program's business. Then a final RMSNorm and an
untied head; embedding unscaled; no bias; no logit cap unless one is passed.

Departures from the published description, each because `config.json` has
Qwen3's keys and none of the mechanism's (the configuration file's `assumed`
has the reasons): the degree is 2; one gate a KV head from a bias-free
projection `[D, Nkv]`; the normaliser is the sum of the weights plus eps
(1e-6); the scale 1 / sqrt(H) stands inside the power; q and k are normed a
head (a learned scale, eps `rms_norm_eps`) and rotated with the halves of a
head split, at `rope_theta`, as Qwen3 does.

Where the program departs from these lines, and the reference follows the
WEIGHTS it is handed: a norm's scale is stored as an offset from one
(multiply by 1 + scale; the init is 0), the heads' norms' too; the head is
stored [V, D] like the embedding table; the feed-forward's gate is named
`ffn_in` and its up projection `ffn_gate`; `w_post` is stored [D, N, H];
the stack is stored as blocks in sequence, each a short list of layers
stacked over its repeats (`stack.block_<b>.x_layers[j]`). Nothing else.

What no shape tells (the two eps, the rotation's base) is read from this
configuration's own file, `benchmarks/configs/brumby14b.json`; a test states
its own through `SeededWeights`' keyword arguments.

How it is computed (`LogitsAt`), to stay inside what a chip has free beside
the served model and its states: one row at a time (`lax.map`), only the
blocks of `_BLOCK` tokens up to the row's own `at` (causality keeps what
follows out of sight; a dynamic trip count); retention by blocks of queries
against blocks of keys, numerator and denominator summed as they come (the
weights are a polynomial: nothing to rescale); weights upcast where they are
used.

On a TPU an f32 matmul runs in lower precision unless told otherwise, so the
caller wraps this in jax.default_matmul_precision("highest").
"""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp

_BLOCK = 512    # tokens a block: of queries, of keys, of the feed-forward

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "brumby14b.json")
# what a caller stated through SeededWeights; it outranks the file
_STATED: dict = {}
_ARCH: dict = {}


def _Arch() -> dict:
  with open(_CONFIG) as f:
    cfg = json.load(f)
  arch = {"eps": float(cfg["rms_norm_eps"]),
          "rope_theta": float(cfg["rope_theta"]),
          "normalizer_eps": float(cfg["normalizer_eps"]),
          # what a control states otherwise (benchmarks/tools/
          # brumby_controls.py): the power, and whether the sum of the
          # weights divides
          "degree": 2, "normalise": True}
  arch.update(_STATED)
  return arch


_TOKEN_CONSTANT = 2.0 ** -3   # what the table's first dimension holds for
#                               every token where the gates have an offset


def _Key(k):
  return getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))


def SeededWeights(theta, gate_offset: float = 0.0, gate_head_spread: float = 0.0,
                  gate_scale: float = 1.0, gate_layer_gain=(),
                  retention_out_scale: float = 1.0, **stated):
  """The weights a cell makes from its seed (the program's own init), with:

  gate_offset        where > 0, every gate's logit is raised by about this
                     much, so that a state remembers thousands of tokens
                     (under the seed's own weights a sigmoid gate sits at one
                     half and a state forgets in a few). The layer has no
                     bias, so the offset rides a constant: the embedding
                     table's first dimension holds one constant for every
                     token, no layer writes that dimension of the stream
                     (that row of every output projection is zero), no
                     projection but the gates' reads it (that row of the q,
                     k, v and feed-forward input projections is zero), and
                     the gates' first row holds the offset over the
                     constant;
  gate_layer_gain    one factor a layer, in stack order, on that row: the
                     gate reads its layer's NORMED input, in which the
                     constant shrinks as the stream grows from layer to
                     layer (its rms where each layer reads it is what the
                     file states here);
  gate_head_spread   the offset of KV head c is `gate_offset + spread * (c /
                     (Nkv - 1) - 1 / 2)`: the heads forget at rates that
                     differ, so that one gate for all of them would show;
  gate_scale         the rest of every gate projection scaled (the gate's
                     own reading of the token);
  retention_out_scale  every retention layer's output projection scaled.

  The configuration file's `weights_reason` has the readings that set them.
  `**stated`: what `_ARCH` holds, for a test at other values (eps,
  rope_theta, normalizer_eps) or a control of another layer (degree,
  normalise)."""
  _STATED.clear()
  _STATED.update(stated)
  n_layers = sum(reps for _, reps in _Blocks(theta))
  # a shallower stack (the rehearsal's) takes the list's first entries
  assert not gate_layer_gain or len(gate_layer_gain) >= n_layers, (
      gate_layer_gain, n_layers)
  # each layer's gain, by where it lies: {(block, place in the block): [a
  # gain a repeat]}
  gains, at = {}, 0
  for b, (layers, reps) in enumerate(_Blocks(theta)):
    for rep in range(reps):
      for j in range(len(layers)):
        gains.setdefault((f"block_{b}", j), []).append(
            float(gate_layer_gain[at]) if gate_layer_gain else 1.0)
        at += 1

  def _Leaf(path, x):
    keys = [_Key(k) for k in path]
    name = str(keys[-1])
    dtype = x.dtype
    if name == "w_post":                    # [.., D, N, H]
      x = x.astype(jnp.float32) * retention_out_scale
      if gate_offset:
        x = x.at[..., 0, :, :].set(0.0)
    if name == "w" and str(keys[-2]) == "ffn_out" and gate_offset:
      x = x.at[..., 0].set(0.0)             # [.., F, D]
    # ... and nothing but the gates reads it: a constant in every token's q,
    # k and v would be a common part of every score
    if name in ("w_query", "w_key", "w_value") and gate_offset:
      x = x.at[..., 0, :, :].set(0.0)       # [.., D, n, H]
    if name == "w" and str(keys[-2]) in ("ffn_in", "ffn_gate") and gate_offset:
      x = x.at[..., 0, :].set(0.0)          # [.., D, F]
    if name == "w_gate":                    # [.., D, Nkv]
      x = x.astype(jnp.float32) * gate_scale
      if gate_offset:
        nk = x.shape[-1]
        heads = gate_offset + gate_head_spread * (
            jnp.arange(nk) / max(nk - 1, 1) - 0.5)
        gain = jnp.asarray(gains[keys[1], keys[3]], jnp.float32)
        x = x.at[..., 0, :].set(gain[:, None] * heads[None] / _TOKEN_CONSTANT)
    if keys[:2] == ["emb", "emb"] and gate_offset:
      x = x.at[:, 0].set(_TOKEN_CONSTANT)
    return x.astype(dtype)

  return jax.tree_util.tree_map_with_path(_Leaf, theta)


def _F32(x):
  return jnp.asarray(x, jnp.float32)


def _RmsNorm(x, scale):
  ms = jnp.mean(jnp.square(x), -1, keepdims=True)
  return x * jax.lax.rsqrt(ms + _ARCH["eps"]) * (1.0 + _F32(scale))


def _Slice(a, i, blk):
  return jax.lax.dynamic_slice_in_dim(a, i * blk, blk, 0)


def _Rotate(x, pos):
  """x [T, n, H], pos [T]: the halves of a head rotated against each other."""
  half = x.shape[-1] // 2
  timescale = _ARCH["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32)
                                      / half)
  angle = pos.astype(jnp.float32)[:, None, None] / timescale
  sin, cos = jnp.sin(angle), jnp.cos(angle)
  first, second = x[..., :half], x[..., half:]
  return jnp.concatenate([first * cos - second * sin,
                          second * cos + first * sin], -1)


def _Retention(layer, rep, x, n_blocks):
  """x [S, D] -> x + the retention branch, over the first n_blocks blocks."""
  at = layer["atten"]
  s_len, _ = x.shape
  blk = min(_BLOCK, s_len)
  w_q, w_k, w_v, w_o = (_F32(at[k][rep]) for k in (
      "w_query", "w_key", "w_value", "w_post"))
  w_g = _F32(at["w_gate"][rep])
  q_scale, k_scale = at["q_norm_scale"][rep], at["k_norm_scale"][rep]
  ln = layer["ln"]["scale"][rep]
  n, h = w_q.shape[1:]
  n_kv = w_k.shape[1]
  group = n // n_kv

  # keys, values and the log-gates cumulated from the sequence's start, of
  # every block first: a query block reads back to 0
  def _KeysValues(i, carry):
    k_all, v_all, c_all, before = carry
    u = _RmsNorm(_Slice(x, i, blk), ln)
    pos = i * blk + jnp.arange(blk)
    k = _Rotate(_RmsNorm(jnp.einsum("td,dnh->tnh", u, w_k), k_scale), pos)
    c = before + jnp.cumsum(jax.nn.log_sigmoid(u @ w_g), 0)      # [blk, Nkv]
    put = lambda a, b: jax.lax.dynamic_update_slice_in_dim(a, b, i * blk, 0)
    return (put(k_all, k), put(v_all, jnp.einsum("td,dnh->tnh", u, w_v)),
            put(c_all, c), c[-1])

  zeros = jnp.zeros((s_len, n_kv, h), jnp.float32)
  k_all, v_all, c_all, _ = jax.lax.fori_loop(0, n_blocks, _KeysValues, (
      zeros, zeros, jnp.zeros((s_len, n_kv), jnp.float32),
      jnp.zeros((n_kv,), jnp.float32)))

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    pos = i * blk + jnp.arange(blk)
    q = _Rotate(_RmsNorm(jnp.einsum("td,dnh->tnh", _RmsNorm(xb, ln), w_q),
                         q_scale), pos)
    q = (q / math.sqrt(h)).reshape(blk, n_kv, group, h)
    c_q = _Slice(c_all, i, blk)                                  # [blk, Nkv]

    def _Keys(j, carry):
      num, den = carry
      key_pos = j * blk + jnp.arange(blk)
      s = jnp.einsum("qgjh,kgh->gjqk", q, _Slice(k_all, j, blk))
      seen = key_pos[None, :] <= pos[:, None]                    # [q, k]
      decay = c_q.T[:, :, None] - _Slice(c_all, j, blk).T[:, None, :]
      w = jnp.where(seen[None, None], s ** _ARCH["degree"] * jnp.exp(
          jnp.where(seen[None], decay, -jnp.inf))[:, None], 0.0)  # [g,j,q,k]
      return (num + jnp.einsum("gjqk,kgh->gjqh", w, _Slice(v_all, j, blk)),
              den + jnp.sum(w, -1))

    num, den = jax.lax.fori_loop(0, i + 1, _Keys, (
        jnp.zeros((n_kv, group, blk, h), jnp.float32),
        jnp.zeros((n_kv, group, blk), jnp.float32)))
    ctx = (num / (den[..., None] + _ARCH["normalizer_eps"])
           if _ARCH["normalise"] else num)
    ctx = ctx.transpose(2, 0, 1, 3).reshape(blk, n, h)
    return jax.lax.dynamic_update_slice_in_dim(
        y, xb + jnp.einsum("tnh,dnh->td", ctx, w_o), i * blk, 0)

  return jax.lax.fori_loop(0, n_blocks, _Block, x)


def _FeedForward(layer, rep, x, n_blocks):
  ff = layer["fflayer"]
  blk = min(_BLOCK, x.shape[0])
  ln = ff["ln"]["scale"][rep]
  # the program's names: `ffn_in` is the gate, `ffn_gate` the up projection.
  # The three matrices are 1.07 GB in f32 at the published widths: they are
  # upcast a slice of the hidden width at a time, inside the loop
  w_gate, w_up, w_down = (ff[k]["w"][rep] for k in (
      "ffn_in", "ffn_gate", "ffn_out"))
  f = w_gate.shape[1]
  pieces = next(c for c in (8, 4, 2, 1) if f % (c * 128) == 0 or c == 1)
  cut = f // pieces

  def _Block(i, y):
    xb = _Slice(x, i, blk)
    u = _RmsNorm(xb, ln)

    def _Piece(p, acc):
      cols = lambda w: _F32(jax.lax.dynamic_slice_in_dim(w, p * cut, cut, 1))
      rows = _F32(jax.lax.dynamic_slice_in_dim(w_down, p * cut, cut, 0))
      return acc + (jax.nn.silu(u @ cols(w_gate)) * (u @ cols(w_up))) @ rows

    return jax.lax.dynamic_update_slice_in_dim(
        y, xb + jax.lax.fori_loop(0, pieces, _Piece, jnp.zeros_like(xb)),
        i * blk, 0)

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
  x = _F32(theta["emb"]["emb"][jnp.pad(ids, (0, -s_len % blk))])
  n_blocks = at // blk + 1
  for layers, reps in _Blocks(theta):
    def _Repeat(rep, x, layers=layers):
      for layer in layers:
        x = _Retention(layer, rep, x, n_blocks)
        x = _FeedForward(layer, rep, x, n_blocks)
      return x
    x = jax.lax.fori_loop(0, reps, _Repeat, x)
  return _RmsNorm(x[at], theta["final_ln"]["scale"])


def LogitsAt(theta, ids, at, logit_cap: float = 0.0):
  """ids [B, W] right-padded sequences, at [B] one position in each -> f32
  logits [B, V] there, one row after the other; the head by slices of the
  vocabulary (upcast whole it is 3.1 GB)."""
  head = theta["head"]["emb"]
  v, d = head.shape
  _ARCH.clear()
  _ARCH.update(_Arch())
  x = jax.lax.map(lambda row: _RowHidden(theta, row[0], row[1]), (ids, at))
  pieces = next(c for c in (64, 32, 16, 8, 4, 2, 1) if v % c == 0)
  logits = jax.lax.map(lambda w: x @ _F32(w).T,
                       head.reshape(pieces, v // pieces, d))
  logits = logits.transpose(1, 0, 2).reshape(x.shape[0], v)
  if logit_cap > 0:
    logits = logit_cap * jnp.tanh(logits / logit_cap)
  return logits
