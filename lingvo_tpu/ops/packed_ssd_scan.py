"""Mamba-2 (scalar-decay, SSD) scan over the serving engine's packed token axis.

The recurrence of one Mamba-2 layer, per head h of Hm with P channels and N
state indices, a step size `dt` per (token, head), an input `x` per (token,
head, channel), `b` and `c` per (token, group, state index) shared by the
Hm / G heads of a group, and ONE decay `a = -exp(A_log)` a head:

    S_t[h] = exp(dt_t[h] a[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) b_t[g(h)]   [P, N]
    y_t[h] = S_t[h] c_t[g(h)] + d_skip[h] x_t[h]                           [P]

The tokens are the PACKED axis of a serving step (`core/ragged.RaggedRows`,
chains only): a slot's tokens are one contiguous run, the valid tokens lead
the axis, a run starts from its slot's state `[Hm, P, N]` (zero where the row
is a request's first token) and leaves its last state there. A decode row is
a run of one token (one rank-one update and one read-out of its state), a
prefill chunk a run of up to the step's budget. Nothing is re-laid out by
row.

`_ChunkedPackedScan` is the chunked (matmul-shaped) form of
`ops/ssd_scan._ChunkBody`, over chunks of Q packed tokens whatever rows they
hold, every chunk at once:

  within a chunk   the quadratic form between tokens of the same row:
                   exp(L_i - L_j) (c_i . b_j) dt_j x_j, L the chunk's running
                   sum of log decay (a row's tokens are contiguous, so the
                   difference is the row's own decay between them);
  between chunks   only the row that is open at a chunk's end goes on into
                   the next: its state there, from this step's tokens and, if
                   it started inside the chunk, from its slot's state, is the
                   chunk's hand-over; a short scan over the chunks chains
                   them, and a token of the row that came in reads the
                   hand-over through exp(L_i);
  a row's start    a token in the chunk its row started in reads the SLOT's
                   state through the decay since the row's start: a product
                   of the row's first Q tokens' C with the slot's state;
  a row's end      the slot's new state is the state at the row's last
                   token: the hand-over into that chunk (or the slot's old
                   state, if the row started in it) decayed, plus the
                   chunk's tokens of that row up to it.

The last two touch every slot's state and are one pass over it: a Pallas
kernel on a TPU (`_PallasRowPass`: a program a (slot, channel tile) reads its
`[Wt, N]` block once, multiplies the row's first Q tokens' C into it,
decays and adds, and writes it once, in place; a group's `R * P` channels
are one tile or several, `ChannelTile`), the same arithmetic in XLA
elsewhere (`_XlaRowPass`). Everything else is XLA in both.

A program of the kernel is sized by what its row holds in the step, which it
reads from the row's flags (prefetched scalars; no Param, no model's name).
TWO BODIES in the one call: a row of two tokens or more runs the products
over a chunk's Q tokens (its first chunk's C, its last chunk's dt x and B,
the hand-over into that chunk if it came in from an earlier one); a row of
ONE token (a decode row; `_NARROW`), which is 63 of a serving step's 64, has
one rank-one update and one read-out to do, takes its token's dt x, decay, C
and B from two `[8, .]` blocks gathered in front of the call and leaves its
read-out in an `[8, Wt]` block of a second, narrow output. The index maps
read the same flags: an operand that only the other body reads, and the
hand-over block of a row that did not come in, stand still at one block,
which the pipeline holds and does not copy again. The narrow read-outs are
laid into `y_rows` at the rows' first tokens behind the call (64 rows written
in place), so `_ChunkedPackedScan` gathers from one array, as it did.

All arithmetic f32 (the recurrence compounds over thousands of tokens).
`_SequentialPackedScan` is the twin the tests hold both to: the row view
`[slots, wmax]` of the pack scanned a column at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu import observe
from lingvo_tpu.ops.flash_attention import LANES, SUBLANES

_HIGHEST = jax.lax.Precision.HIGHEST
_FRESH, _BEFORE, _LIVE, _NARROW = 1, 2, 4, 8     # bits of a row's flags


def _Einsum(spec, *args):
  return jnp.einsum(spec, *args, precision=_HIGHEST,
                    preferred_element_type=jnp.float32)


def _SequentialPackedScan(x, dt, a, b, c, d_skip, state, rows):
  """The recurrence a token at a time over the row view of the pack.
  Shapes as PackedSsdScan."""
  t, hm, _ = x.shape
  g = b.shape[1]
  r = hm // g
  wmax = rows.row_cols.shape[1]
  fresh = rows.row_q_pos == 0
  s0 = jnp.where(fresh[:, None, None, None], 0.0, state)
  cols = jnp.clip(rows.row_cols, 0, t - 1)
  row_len = rows.row_len.astype(jnp.int32)

  def _Col(s, j):
    tok = cols[:, j]                                          # [B]
    dd, xx = dt[tok], x[tok]                                  # [B,Hm] [B,Hm,P]
    bb = jnp.repeat(b[tok], r, axis=1)                        # [B, Hm, N]
    cc = jnp.repeat(c[tok], r, axis=1)
    s_new = (jnp.exp(dd * a[None])[..., None, None] * s
             + (dd[..., None] * xx)[..., None] * bb[:, :, None, :])
    y = jnp.sum(s_new * cc[:, :, None, :], axis=-1) + d_skip[None, :, None] * xx
    live = (j < row_len)[:, None, None, None]
    return jnp.where(live, s_new, s), y

  s, ys = jax.lax.scan(_Col, s0, jnp.arange(wmax))          # ys [wmax,B,Hm,P]
  row = jnp.clip(rows.row_of.astype(jnp.int32), 0, state.shape[0] - 1)
  y = ys[jnp.clip(rows.col_of.astype(jnp.int32), 0, wmax - 1), row]
  return jnp.where(rows.valid[:, None, None], y, 0.0), s


def _ChunkedPackedScan(x, dt, a, b, c, d_skip, state, rows, q, row_pass,
                       layer):
  """layer: this layer's index in `state` [L, B, Hm, P, N], the states of a
  scanned block's L layers in one stack (PackedSsdScan)."""
  t, hm, p = x.shape
  g, n = b.shape[1:]
  r = hm // g
  w = r * p                    # a group's channels: the lanes of x and y
  slots = state.shape[1]
  nc = -(-t // q)
  pad = nc * q - t
  valid = rows.valid
  row = jnp.where(valid, jnp.clip(rows.row_of.astype(jnp.int32), 0,
                                  slots - 1), -1)            # padding: no row
  col = rows.col_of.astype(jnp.int32)
  dt = jnp.where(valid[:, None], dt, 0.0)
  fresh = rows.row_q_pos == 0
  row_len = rows.row_len.astype(jnp.int32)

  def _Chunks(v, fill=0):
    v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1),
                constant_values=fill)
    return v.reshape((nc, q) + v.shape[1:])

  def _Wide(v):
    """[..., Hm] a head -> [..., G, R * P]: beside a group's channels."""
    return jnp.repeat(v.reshape(v.shape[:-1] + (g, r)), p, axis=-1)

  rowc, colc = _Chunks(row, -1), _Chunks(col)                 # [nc, q]
  la = _Chunks(dt * a[None])                                  # [nc, q, Hm]
  xdt = _Chunks(x * dt[..., None]).reshape(nc, q, g, w)
  bc, cc = _Chunks(b), _Chunks(c)                             # [nc, q, G, N]
  run = jnp.cumsum(la, axis=1)                                # L, inclusive
  idx = jnp.arange(q, dtype=jnp.int32)
  first = idx[None] - colc         # the row's first token, in the chunk's
  #                                  own indices (< 0: an earlier chunk's)
  before = jnp.where(
      (first > 0)[..., None],
      jnp.take_along_axis(run, jnp.clip(first - 1, 0, q - 1)[..., None],
                          axis=1), 0.0)
  since = run - before             # log decay since the row's start, or
  #                                  since the chunk's where it came in
  live = rowc >= 0

  # -- within a chunk --------------------------------------------------------
  same = ((rowc[:, :, None] == rowc[:, None, :]) & live[:, :, None]
          & (idx[:, None] >= idx[None, :])[None])             # [nc, q, q]
  scores = _Einsum("cign,cjgn->cgij", cc, bc)                 # [nc, G, q, q]
  decay = jnp.exp(jnp.where(same[..., None],
                            run[:, :, None] - run[:, None, :], -jnp.inf))
  decay = decay.reshape(nc, q, q, g, r)
  y = _Einsum("cgij,cijgr,cjgrp->cigrp", scores, decay,
              xdt.reshape(nc, q, g, r, p)).reshape(nc, q, g, w)

  # -- the hand-over from chunk to chunk ------------------------------------
  r_last, first_last = rowc[:, -1], first[:, -1]              # [nc]
  open_ = r_last >= 0
  tail = jnp.where(((rowc == r_last[:, None]) & open_[:, None])[..., None],
                   jnp.exp(run[:, -1:] - run), 0.0)           # [nc, q, Hm]
  local = _Einsum("cjgx,cjgn->cgxn", xdt * _Wide(tail), bc)
  # the open row started inside the chunk: its slot's state goes on with it
  # (a request's first token: zeros)
  inject = jnp.where((open_ & (first_last >= 0)
                      & ~fresh[jnp.clip(r_last, 0)])[:, None],
                     jnp.exp(since[:, -1]), 0.0)              # [nc, Hm]
  local = local + _Wide(inject)[..., None] * state[
      layer, jnp.clip(r_last, 0)].reshape(nc, g, w, n)
  carry_on = _Wide(jnp.where((open_ & (first_last < 0))[:, None],
                             jnp.exp(run[:, -1]), 0.0))       # [nc, G, W]

  def _Chain(s_in, xs):
    keep, add = xs
    return keep[..., None] * s_in + add, s_in

  _, came_in = jax.lax.scan(_Chain, jnp.zeros((g, w, n), jnp.float32),
                            (carry_on, local))                # [nc, G, W, N]
  goes_on = (live & (first < 0))[..., None]
  y = y + _Einsum("cign,cgxn->cigx", cc, came_in) * _Wide(
      jnp.where(goes_on, jnp.exp(run), 0.0))

  # -- a row's start and its end: the one pass over the slots' states --------
  flat = lambda v: v.reshape((nc * q,) + v.shape[2:])
  start = jnp.clip(rows.row_cols[:, 0].astype(jnp.int32), 0, nc * q - 1)
  j = idx[None]                                               # [1, q]
  at = jnp.clip(start[:, None] + j, 0, nc * q - 1)            # [B, q]
  in_chunk = (j < row_len[:, None]) & ((start % q)[:, None] + j < q)
  reads = jnp.where(in_chunk[..., None], jnp.exp(flat(since)[at]), 0.0)
  end = jnp.clip(start + row_len - 1, 0, nc * q - 1)          # [B]
  c_end, i_end = end // q, end % q
  run_end = run[c_end]                                        # [B, q, Hm]
  mine = (rowc[c_end] == jnp.arange(slots)[:, None]) & (j <= i_end[:, None])
  upto = jnp.where(mine[..., None],
                   jnp.exp(run_end[jnp.arange(slots), i_end][:, None]
                           - run_end), 0.0)                   # [B, q, Hm]
  flags = (fresh * _FRESH + (start < c_end * q) * _BEFORE
           + (row_len > 0) * _LIVE + (row_len <= 1) * _NARROW
           ).astype(jnp.int32)
  y_rows, new_state = row_pass(
      state.reshape(-1, g * w, n), came_in.reshape(nc, g * w, n),
      flat(cc)[at].reshape(slots, q, g * n), reads,
      xdt.reshape(nc, q, g * w), upto, bc.reshape(nc, q, g * n),
      jnp.exp(flat(since)[end]), end, flags, layer * slots)
  # a token in the chunk its row started in reads the slot's state
  started_here = flat(live & (first >= 0))
  place = jnp.clip(flat(rowc), 0) * q + jnp.clip(flat(colc), 0, q - 1)
  y = flat(y) + jnp.where(
      started_here[:, None, None],
      y_rows.reshape(-1, g, w)[place], 0.0)

  y = y.reshape(nc * q, hm, p)[:t] + d_skip[None, :, None] * x
  return (jnp.where(valid[:, None, None], y, 0.0),
          new_state.reshape(state.shape))


# -- the pass over the slots' states ------------------------------------------
#
# state [B, G * W, N]: every slot's state, a group's W = R * P channels
# together; came_in [nc, G * W, N]: the hand-over into every chunk; c_rows
# [B, q, G * N]: C of a row's first q tokens, and reads [B, q, Hm] the decay
# through which each of them reads the slot's state (zero where the token is
# not the row's or lies in a later chunk); xdt [nc, q, G * W] and bc [nc, q,
# G * N]: dt x and B of every chunk's tokens, and upto [B, q, Hm] the decay
# of the tokens of the row's LAST chunk to the row's end (zero where a token
# is not the row's or lies behind its end); dec [B, Hm]: the decay from the
# row's start (or its last chunk's) to its end; end [B]: the row's last
# token in the padded pack (chunk end // q, index end % q); flags [B]: _FRESH
# the row starts a request (its slot's state reads as zeros), _BEFORE it came
# into its last chunk from an earlier one (the state to decay is that chunk's
# hand-over, not the slot's), _LIVE it has tokens in this step, _NARROW it
# has at most one (reads[b, 0] and dec[b] are then the same decay, and upto
# is 1 at the token). What is a head's reaches its P channels inside.
# -> (y_rows [>= B, q, G * W]: reads * (C_j . state) for the row's first q
#     tokens (of a _NARROW row the first alone), new state [B, G * W, N]).


def _XlaRowPass(stack, came_in, c_rows, reads, xdt, upto, bc, dec, end,
                flags, first, *, g):
  """The twin takes the layer's states out of the stack and lays them back
  (it may copy); one body for every row."""
  with observe.Scope("ssd_row_pass"):
    slots, q = reads.shape[:2]
    y_rows, new = _XlaRows(
        jax.lax.dynamic_slice_in_dim(stack, first, slots), came_in, c_rows,
        reads, xdt, upto, bc, dec, end // q, flags, g)
    return y_rows, jax.lax.dynamic_update_slice_in_dim(stack, new, first, 0)


def _XlaRows(state, came_in, c_rows, reads, xdt, upto, bc, dec, c_end, flags,
             g):
  slots, gw, n = state.shape
  q, hm = reads.shape[1:]
  w = gw // g
  wide = lambda v: jnp.repeat(v, gw // hm, axis=-1).reshape(
      v.shape[:-1] + (g, w))
  held = jnp.where(((flags & _FRESH) != 0)[:, None, None], 0.0,
                   state).reshape(slots, g, w, n)
  y_rows = _Einsum("bjgn,bgxn->bjgx", c_rows.reshape(slots, q, g, n),
                   held) * wide(reads)
  base = jnp.where(((flags & _BEFORE) != 0)[:, None, None, None],
                   came_in.reshape(-1, g, w, n)[c_end], held)
  added = _Einsum("bjgx,bjgn->bgxn",
                  xdt.reshape(-1, q, g, w)[c_end] * wide(upto),
                  bc.reshape(-1, q, g, n)[c_end])
  new = jnp.where(((flags & _LIVE) != 0)[:, None, None, None],
                  wide(dec)[..., None] * base + added, held)
  return y_rows.reshape(slots, q, gw), new.reshape(slots, gw, n)


def _Dot(a, b, contract):
  return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)


def _RowKernel(end_ref, flag_ref, first_ref, state_ref, came_ref, c_ref,
               reads_ref, xdt_ref, upto_ref, b_ref, dec_ref, expand_ref,
               tok_w_ref, tok_g_ref, y_ref, out_ref, y_tok_ref):
  """One (slot, channel tile): its [Wt, N] block of the state, read once and
  written once; C and B are its group's, whatever tile of the group it is.
  Two bodies, the row's flags decide: a row of at most one token (`_NARROW`)
  reads its token's operands from two [8, .] blocks and writes its read-out
  to one; a row of more runs the products over a chunk's q tokens."""
  del end_ref, first_ref              # the index maps read them
  flag = flag_ref[pl.program_id(0)]
  held = jnp.where((flag & _FRESH) != 0, 0.0, state_ref[0])    # [Wt, N]
  live = (flag & _LIVE) != 0
  narrow = (flag & _NARROW) != 0

  @pl.when(narrow)
  def _OneToken():
    # rows of tok_w: the token's dt x, the decay of its step, each a
    # channel; of tok_g: its C, its B
    tok_w, tok_g = tok_w_ref[0], tok_g_ref[0]        # [8, Wt], [8, N]
    y_tok_ref[0] = _Dot(tok_g, held, (1, 1)) * tok_w[1:2]      # row 0: C's
    # what is a channel's lies on the lanes; the state wants it down its
    # rows, the same across the N lanes: the row broadcast and transposed
    # (exact, and no product: the probe's fastest form, PERF.md section 6)
    col = lambda r: jnp.transpose(
        jnp.broadcast_to(tok_w[r:r + 1], held.shape[::-1]))    # [Wt, N]
    out_ref[0] = jnp.where(live, col(1) * held + col(0) * tok_g[1:2], held)

  @pl.when(jnp.logical_not(narrow))
  def _Chunk():
    expand = expand_ref[...]          # [Hm, Wt] 0 / 1: a head to its channels
    wide = lambda v: _Dot(v, expand, (1, 0))                      # [., Wt]
    y_ref[0] = _Dot(c_ref[0], held, (1, 1)) * wide(reads_ref[0])  # [q, Wt]
    base = jnp.where((flag & _BEFORE) != 0, came_ref[0], held)
    added = _Dot(xdt_ref[0] * wide(upto_ref[0]), b_ref[0], (0, 0))  # [Wt, N]
    # the decay a channel lies on the lanes ([1, Wt]); the state wants it a
    # row: a diagonal mask over the tile moves it exactly (one nonzero a sum)
    w = held.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (w, w), 1))
    dec = jnp.sum(jnp.where(eye, wide(dec_ref[0])[:1], 0.0), axis=1,
                  keepdims=True)                                  # [Wt, 1]
    out_ref[0] = jnp.where(live, dec * base + added, held)


_MAX_CHANNEL_TILE = 512    # channels a program: a [512, 128] f32 block is
#                            256 KB, and the diagonal mask 512 x 512


def ChannelTile(group_channels: int) -> int:
  """Channels Wt of a group's W that one program of the row pass holds: the
  largest whole number of lane tiles that divides W and is at most 512 (W
  itself up to there: a group of 512 is one program, as it was before the
  pass tiled)."""
  assert group_channels % LANES == 0, group_channels
  return max(t for t in range(LANES, _MAX_CHANNEL_TILE + 1, LANES)
             if group_channels % t == 0)


CUTS = ("hand_over", "narrow")   # what a program no longer fetches or
#                                   multiplies in vain; tools/kernel_probe.py
#                                   times the kernel with each alone


@functools.partial(jax.jit, static_argnames=("g", "interpret", "cuts"))
def _PallasRowPass(state, came_in, c_rows, reads, xdt, upto, bc, dec, end,
                   flags, first, *, g: int, interpret: bool, cuts=CUTS):
  """The kernel over its grid (slots, channel tiles): tile k of the G * W
  channels lies in group k // (W / Wt) and reads that group's B and C. Where
  a group is one tile the grid is (slots, groups). first: the row of `state`
  [L * B, G * W, N] (a scanned block's stack of L layers' states) at which
  this layer's B slots start: a third prefetched scalar that the state's two
  index maps add, so that the kernel reads and writes its layer's blocks
  where they lie and the rest of the stack is the aliased buffer's. A `jit`
  of its own, as selective_scan._ScanCall: the layers of a stack share one
  trace, and the scope keeps the kernel's name.

  A program moves what its row holds in this step, by the row's flags in the
  index maps: an operand that only the other body reads, and the hand-over of
  a row that did not come in, stand still at one block, which the pipeline
  keeps and does not copy again."""
  gw, n = state.shape[1:]
  slots, q, hm = reads.shape
  wt = ChannelTile(gw // g)
  per_group = gw // g // wt
  group = (lambda k: k) if per_group == 1 else (lambda k: k // per_group)
  if "narrow" not in cuts:
    flags = flags & ~_NARROW
  # a one-token row's operands, 8 rows a slot: its token's dt x and its
  # step's decay (a head's, beside the head's channels); its C and its B
  c_end, i_end = end // q, end % q
  tok_w = jnp.stack([xdt[c_end, i_end], jnp.repeat(dec, gw // hm, axis=-1)],
                    axis=1)
  tok_g = jnp.stack([c_rows[:, 0], bc[c_end, i_end]], axis=1)
  rows8 = lambda v: jnp.pad(v, ((0, 0), (0, SUBLANES - v.shape[1]), (0, 0)))

  def _Wide(b, flags):
    return 1 - ((flags[b] & _NARROW) != 0).astype(jnp.int32)

  def _Came(b, k, end, flags, first):
    on = (((flags[b] & _BEFORE) != 0).astype(jnp.int32)
          if "hand_over" in cuts else 1)
    return end[b] // q * on, k * on, 0

  def _Slot(tile):      # a chunk row's own [q, .] block of its slot's
    def _Map(b, k, end, flags, first):
      on = _Wide(b, flags)
      return b * on, 0, tile(k) * on
    return _Map

  def _LastChunk(tile):       # ... and of the chunk it ends in
    def _Map(b, k, end, flags, first):
      on = _Wide(b, flags)
      return end[b] // q * on, 0, tile(k) * on
    return _Map

  def _Token(tile):           # a one-token row's [8, .] block
    def _Map(b, k, end, flags, first):
      return b, 0, tile(k) * (1 - _Wide(b, flags))
    return _Map

  def _Rows(b, k, end, flags, first):
    # y_rows has one block row more than slots: where the one-token rows'
    # programs leave what they did not write
    on = _Wide(b, flags)
    return b * on + slots * (1 - on), 0, k * on

  mine = lambda b, k, end, flags, first: (first[0] + b, k, 0)
  tile, whole = (lambda k: k), (lambda k: 0)
  expand = (jnp.arange(hm)[:, None] == jnp.arange(gw)[None] // (gw // hm)
            ).astype(jnp.float32)
  with observe.Scope("ssd_row_pass"):
    y_rows, new, y_tok = pl.pallas_call(
        _RowKernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, gw // wt),
            in_specs=[
                pl.BlockSpec((1, wt, n), mine),
                pl.BlockSpec((1, wt, n), _Came),
                pl.BlockSpec((1, q, n), _Slot(group)),
                pl.BlockSpec((1, q, hm), _Slot(whole)),
                pl.BlockSpec((1, q, wt), _LastChunk(tile)),
                pl.BlockSpec((1, q, hm), _Slot(whole)),
                pl.BlockSpec((1, q, n), _LastChunk(group)),
                pl.BlockSpec((1, SUBLANES, hm), _Slot(whole)),
                pl.BlockSpec((hm, wt), lambda b, k, end, flags, first: (
                    0, k * _Wide(b, flags))),
                pl.BlockSpec((1, SUBLANES, wt), _Token(tile)),
                pl.BlockSpec((1, SUBLANES, n), _Token(group)),
            ],
            out_specs=[
                pl.BlockSpec((1, q, wt), _Rows),
                pl.BlockSpec((1, wt, n), mine),
                pl.BlockSpec((1, SUBLANES, wt), _Token(tile)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((slots + 1, q, gw), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((slots, SUBLANES, gw), jnp.float32)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(end, flags, jnp.asarray(first, jnp.int32).reshape(1), state, came_in,
      c_rows, reads, xdt, upto, bc,
      jnp.broadcast_to(dec[:, None], (slots, SUBLANES, hm)), expand,
      rows8(tok_w), rows8(tok_g))
  # a one-token row's read-out goes where a chunk row's first token's lies
  # (64 rows written into the kernel's own output, in place)
  narrow = ((flags & _NARROW) != 0)[:, None]
  return y_rows.at[:slots, 0].set(
      jnp.where(narrow, y_tok[:, 0], y_rows[:slots, 0])), new


def SupportedOnTpu(chunk_size: int, group_channels: int,
                   state_dim: int) -> bool:
  """Mosaic's tiling: a group's channels whole lane tiles (`ChannelTile`
  then finds a tile of at most 512 that divides them, however wide the
  group: 8 groups of 512 and one group of 8,192 alike), the state indices on
  whole lanes, a chunk's tokens on whole sublanes. What a program holds in
  VMEM at a tile of 512, N = 128, Q = 64, Hm = 128: the state's block three
  times (in, hand-over, out; 256 KB each), the head-to-channel expansion
  [Hm, 512] (256 KB), dt x and y of the chunk (128 KB each) and under 200 KB
  of the rest (the one-token body's three [8, .] blocks among it), each twice
  for the pipeline: about 3 MB of the 16 the compiler grants."""
  return (chunk_size % SUBLANES == 0 and group_channels % LANES == 0
          and state_dim % LANES == 0)


def PackedSsdScan(x, dt, a, b, c, d_skip, state, rows, *, chunk_size: int = 64,
                  lowering: str = "auto", interpret: bool | None = None,
                  layer=None):
  """The packed step's scan (module docstring). x: [T, Hm, P]; dt: [T, Hm],
  positive; a: [Hm], negative; b, c: [T, G, N], G dividing Hm; d_skip: [Hm];
  state: [B, Hm, P, N]; rows: the step's `core/ragged.RaggedRows` (chains
  only). All f32 inside, whatever arrives. -> (y [T, Hm, P] f32, zeros at
  padding tokens; new state [B, Hm, P, N] f32). layer: where given (a traced
  index), `state` is [L, B, Hm, P, N], the states of a scanned block's L
  layers in one stack, and this call reads and writes layer `layer`'s where
  they lie: the new state is the whole stack, the kernel's aliased buffer
  (a block's scan that sliced a layer's 268 MB out and stacked them back
  would copy them twice a layer). One layer's state alone is a stack of one.
  lowering: 'auto' (the chunked form, its pass over the slots' states the
  kernel on a TPU where `SupportedOnTpu`: any group whose channels are whole
  lane tiles; XLA elsewhere) | 'pallas' | 'xla' | 'sequential' (the twin a
  token at a time)."""
  assert lowering in ("auto", "pallas", "xla", "sequential"), lowering
  x, dt, a, b, c, d_skip, state = (
      v.astype(jnp.float32) for v in (x, dt, a, b, c, d_skip, state))
  hm, g = x.shape[1], b.shape[1]
  assert hm % g == 0, (x.shape, b.shape)
  on_tpu = jax.default_backend() == "tpu"
  if lowering == "auto":
    lowering = ("pallas" if on_tpu and SupportedOnTpu(
        chunk_size, hm // g * x.shape[2], b.shape[2]) else "xla")
  alone = layer is None
  if alone:
    state, layer = state[None], 0
  if lowering == "sequential":
    with observe.Scope("ssd_scan"):
      y, new = _SequentialPackedScan(x, dt, a, b, c, d_skip, state[layer],
                                     rows)
      new = state.at[layer].set(new)
  else:
    y, new = _ChunkedCall(
        x, dt, a, b, c, d_skip, state, rows, layer, q=int(chunk_size),
        kernel=lowering != "xla",
        interpret=(not on_tpu) if interpret is None else interpret)
  return y, new[0] if alone else new


@functools.partial(jax.jit, static_argnames=("q", "kernel", "interpret"),
                   inline=True)
def _ChunkedCall(x, dt, a, b, c, d_skip, state, rows, layer, *, q: int,
                 kernel: bool, interpret: bool):
  """`_ChunkedPackedScan` as a `jit` of its own, inlined where it is called:
  the Mamba-2 layers of a stack (and a probe's program) share ONE trace of
  its hundred and fifty `jnp` calls, which the step program would else
  repeat a traced layer body (PERF.md section 6, PR 51); what is lowered is
  what was."""
  g = b.shape[1]
  row_pass = (functools.partial(_PallasRowPass, g=g, interpret=interpret)
              if kernel else functools.partial(_XlaRowPass, g=g))
  with observe.Scope("ssd_scan"):
    return _ChunkedPackedScan(x, dt, a, b, c, d_skip, state, rows, q,
                              row_pass, layer)
