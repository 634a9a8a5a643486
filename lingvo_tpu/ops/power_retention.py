"""Power retention of degree 2 (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): attention whose weight is a power
of the score under a learned decay, and so has an exact recurrent form.

Per KV head c with its group of query heads a, q pre-scaled by 1 / sqrt(H),
`lg_t = log g_t[c] <= 0`:

    w_ts = (q_t[a] . k_s[c])^2 * exp(sum_{r=s+1..t} lg_r)          s <= t
    y_t[a] = sum_s w_ts v_s[c] / (sum_s w_ts + eps)

`phi: R^H -> R^D` with `phi_q(a) . phi_k(b) = (a . b)^2` turns the sums into a
state of fixed size, `S_t = g_t S_{t-1} + phi_k(k_t) v_t^T`, `z_t = g_t
z_{t-1} + phi_k(k_t)`, `y_t = phi_q(q_t)^T S_t / (phi_q(q_t) . z_t + eps)`.
The distinct products `a_i a_j`, `i <= j`, are H (H + 1) / 2 (`MonomialDim`:
8,256 at H = 128). What is STORED is what a kernel builds with a rotation
and a product (`StoredDim`: H (H / 2 + 1), 8,320 at H = 128): feature (o, i)
is `a_i a_{(i + o) mod H}` for the offsets o = 0 .. H / 2, so the features of
one offset are the vector times itself rotated by o lanes. Offsets o and H - o
name the same pairs, so the query side weighs an offset by 2, and by 1 the
offset 0 (the squares) and the offset H / 2 (which holds each of its pairs
twice already: the 64 features more than 8,256); 2 is exact at every
precision, sqrt 2 is not. `Phi` is the map in plain jax.numpy.

Three forms over whole sequences (`AttentionForm`, `RecurrentForm`,
`ChunkedForm`; tests hold them to each other), and the serving step's,
`PackedRetention`, on the engine's packed token axis:

- a slot's state `S^T` `[Nkv, H, D]` and `z` `[Nkv, H / 2 + 1, H]` (f32) hold
  every token before the row's OPEN chunk, the tokens since the last page
  boundary; the open chunk's K, V and cumulated log-gates (from its page's
  first token) live in pages of the stack's one pool, written by the step
  that computes them;
- a token reads the state through the decay since the chunk's start, and
  the open chunk and the step's own tokens before it in the attention form
  (the chunked form with the span since the open chunk's start as its one
  chunk: a prefill chunk of several pages queries the state once);
- every page a step completes is folded into the state, a page after the
  other, and a slot whose row starts a request (`row_q_pos == 0`) reads as
  zeros and is written as zeros or as its first fold.

Lowerings: 'xla' (gathers by token; the CPU's serving path and the twin the
kernels are held to) and 'pallas', four kernels: `retention_chunk` (a grid of
the (query block, page) pairs the step holds), and under `retention_state`
the state's query for rows of one token (the VPU: a decode row reads its
state once, in f32), its query for the blocks of longer rows (the MXU; the
features built in the kernel by lane rotations) and
the fold (a grid of the pages the step completes; in place, so a state no
page was folded into is not written).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu import observe
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.ops import run_write
from lingvo_tpu.ops.flash_attention import LANES, SUBLANES
from lingvo_tpu.ops.ragged_block_attend import Lowering, _HeadPages

_HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 64     # packed tokens of one row a kernel's block holds
_TILE_OFFSETS = 8    # offsets a kernel's tile of the state holds at most
_VMEM_LIMIT = 64 * 1024 * 1024


# -- the feature map ----------------------------------------------------------


def MonomialDim(h: int) -> int:
  """Distinct products a_i a_j, i <= j, of an h-vector."""
  return h * (h + 1) // 2


def Offsets(h: int) -> int:
  """Offsets 0 .. h / 2 the stored feature map holds."""
  assert h % 2 == 0, h
  return h // 2 + 1


def StoredDim(h: int) -> int:
  """Features the state stores a (KV head, value dimension)."""
  return h * Offsets(h)


def TileOffsets(h: int) -> int:
  """Offsets a kernel's tile of the state holds: a divisor of Offsets(h)."""
  n = Offsets(h)
  return max(c for c in range(1, _TILE_OFFSETS + 1) if n % c == 0)


def QueryWeights(h: int) -> np.ndarray:
  """[Offsets(h)] the query side's weight of each offset."""
  w = np.full((Offsets(h),), 2.0, np.float32)
  w[0] = w[-1] = 1.0
  return w


def Phi(x, query: bool = False):
  """x [..., H] -> [..., D] f32: the stored feature map, the key side's or
  (`query`) the query's; feature o * H + i is x_i x_{(i + o) mod H}."""
  h = x.shape[-1]
  x = x.astype(jnp.float32)
  rolled = jnp.stack([jnp.roll(x, -o, axis=-1) for o in range(Offsets(h))],
                     axis=-2)                           # [..., offsets, H]
  out = x[..., None, :] * rolled
  if query:
    out = out * jnp.asarray(QueryWeights(h))[:, None]
  return out.reshape(x.shape[:-1] + (StoredDim(h),))


# -- whole sequences ----------------------------------------------------------


def _Grouped(q, nk):
  b, t, n, h = q.shape
  return q.astype(jnp.float32).reshape(b, t, nk, n // nk, h)


def AttentionForm(q, k, v, log_g, eps: float):
  """q [B, T, N, H] pre-scaled; k, v [B, T, Nkv, H]; log_g [B, T, Nkv].
  -> [B, T, N, H] f32, the quadratic form."""
  b, t, n, h = q.shape
  nk = k.shape[2]
  f32 = jnp.float32
  qg = _Grouped(q, nk)
  cum = jnp.cumsum(log_g.astype(f32), axis=1)                  # [B, T, Nkv]
  s = jnp.einsum("btcgh,bsch->bcgts", qg, k.astype(f32), precision=_HIGHEST)
  decay = cum[:, :, None, :] - cum[:, None, :, :]              # [B, t, s, c]
  seen = jnp.tril(jnp.ones((t, t), bool))
  decay = jnp.where(seen[None, :, :, None], decay, -jnp.inf)
  w = jnp.square(s) * jnp.exp(decay).transpose(0, 3, 1, 2)[:, :, None]
  num = jnp.einsum("bcgts,bsch->btcgh", w, v.astype(f32), precision=_HIGHEST)
  den = jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)[..., None]   # [B,T,c,g,1]
  return (num / (den + eps)).reshape(b, t, n, h)


def RecurrentForm(q, k, v, log_g, eps: float):
  """The same a token at a time over the state (S, z)."""
  b, t, n, h = q.shape
  nk = k.shape[2]
  f32 = jnp.float32
  d = StoredDim(h)

  def _Token(carry, xs):
    s, z = carry                                       # [B,c,D,H], [B,c,D]
    qt, kt, vt, lg = xs
    g = jnp.exp(lg)[..., None]
    pk = Phi(kt)                                # [B, c, D]
    s = g[..., None] * s + pk[..., None] * vt.astype(f32)[:, :, None, :]
    z = g * z + pk
    pq = Phi(qt.reshape(b, nk, n // nk, h), query=True)
    num = jnp.einsum("bcgd,bcdh->bcgh", pq, s, precision=_HIGHEST)
    den = jnp.einsum("bcgd,bcd->bcg", pq, z, precision=_HIGHEST)[..., None]
    return (s, z), (num / (den + eps)).reshape(b, n, h)

  init = (jnp.zeros((b, nk, d, h), f32), jnp.zeros((b, nk, d), f32))
  _, ys = jax.lax.scan(_Token, init, tuple(
      jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_g.astype(f32))))
  return jnp.moveaxis(ys, 0, 1)


def ChunkedForm(q, k, v, log_g, eps: float, chunk: int):
  """The same in chunks of `chunk` tokens: the state at a chunk's start read
  through the decay since, the chunk's own tokens in the attention form, and
  the chunk folded into the state at its end. T a multiple of `chunk`."""
  b, t, n, h = q.shape
  nk = k.shape[2]
  f32 = jnp.float32
  assert t % chunk == 0, (t, chunk)
  d = StoredDim(h)
  seen = jnp.tril(jnp.ones((chunk, chunk), bool))

  def _Chunk(carry, xs):
    s, z = carry
    qc, kc, vc, lg = xs             # [B, chunk, ...]
    qg = _Grouped(qc, nk)
    kc, vc = kc.astype(f32), vc.astype(f32)
    cum = jnp.cumsum(lg, axis=1)                             # [B, chunk, c]
    pq = Phi(qg, query=True)                                 # [B,t,c,g,D]
    gt = jnp.exp(cum)[..., None, None]
    num = gt * jnp.einsum("btcgd,bcdh->btcgh", pq, s, precision=_HIGHEST)
    den = gt[..., 0] * jnp.einsum("btcgd,bcd->btcg", pq, z,
                                  precision=_HIGHEST)
    sc = jnp.einsum("btcgh,bsch->bcgts", qg, kc, precision=_HIGHEST)
    decay = jnp.where(seen[None, :, :, None],
                      cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf)
    w = jnp.square(sc) * jnp.exp(decay).transpose(0, 3, 1, 2)[:, :, None]
    num = num + jnp.einsum("bcgts,bsch->btcgh", w, vc, precision=_HIGHEST)
    den = den + jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)
    y = (num / (den[..., None] + eps)).reshape(b, chunk, n, h)
    to_end = jnp.exp(cum[:, -1:, :] - cum)                   # [B, chunk, c]
    pk = Phi(kc)                                      # [B,chunk,c,D]
    ge = jnp.exp(cum[:, -1, :])[..., None]                   # [B, c, 1]
    s = ge[..., None] * s + jnp.einsum(
        "bscd,bsch->bcdh", pk * to_end[..., None], vc, precision=_HIGHEST)
    z = ge * z + jnp.einsum("bscd,bsc->bcd", pk, to_end, precision=_HIGHEST)
    return (s, z), y

  def _Chunks(x):
    return jnp.moveaxis(x.reshape((b, t // chunk, chunk) + x.shape[2:]), 1, 0)

  init = (jnp.zeros((b, nk, d, h), f32), jnp.zeros((b, nk, d), f32))
  _, ys = jax.lax.scan(_Chunk, init, tuple(
      _Chunks(x) for x in (q, k, v, log_g.astype(f32))))
  return jnp.moveaxis(ys, 0, 1).reshape(b, t, n, h)


# -- the serving step ---------------------------------------------------------


def InitState(num_slots: int, nk: int, h: int):
  """(S^T [slots, Nkv, H, D], z [slots, Nkv, H / 2 + 1, H]) of zeros, f32; z
  a row an offset. Neither looks like a page pool to a reader that knows one
  by its shape (third from last = the page size): that dimension is Nkv in
  both."""
  return (jnp.zeros((num_slots, nk, h, StoredDim(h)), jnp.float32),
          jnp.zeros((num_slots, nk, Offsets(h), h), jnp.float32))


def StateBytes(nk: int, h: int) -> int:
  """Bytes of one slot's S and z as stored."""
  return 4 * nk * StoredDim(h) * (h + 1)


def SpanPages(page: int, wmax: int) -> int:
  """Pages the span from a row's open chunk's start to its step's last
  token can touch, a row's step at most `wmax` tokens."""
  return (page - 1 + max(wmax, 1) - 1) // page + 1


def SupportedOnTpu(page: int, h: int) -> bool:
  """Mosaic's tiling: a page's tokens and a head on whole lanes (an offset's
  features are then a lane tile, and a rotation by an offset one of lanes)."""
  return page % LANES == 0 and h == LANES


class StepPlan(NamedTuple):
  """What a step's rows, the tables' shape and static sizes alone decide for
  `PackedRetention`, and so the same in every layer of a stack: built once a
  step (`BuildStepPlan`)."""
  row: jnp.ndarray        # [T] a token's row, inside the table
  jj: jnp.ndarray         # [T] its page, counted from its row's open chunk's
  off: jnp.ndarray        # [T] its offset in that page
  j0: jnp.ndarray         # [B] logical page of the row's open chunk
  off0: jnp.ndarray       # [B] tokens the open chunk holds at the step's start
  start: jnp.ndarray      # [B] the row's first packed token
  live: jnp.ndarray       # [B] the row has tokens in this step
  fresh: jnp.ndarray      # [B] ... and starts a request: its state reads zero
  folds: jnp.ndarray      # [B] pages the row completes in this step
  # blocks of up to QUERY_BLOCK consecutive tokens of one row, packed order
  blk_row: jnp.ndarray    # [NB]
  blk_first: jnp.ndarray  # [NB] first packed token
  blk_n: jnp.ndarray      # [NB] tokens held; 0: no such block
  tok_at: jnp.ndarray     # [T] a token's place in block space, blk * Bq + i
  # (block, page) pairs of the chunk kernel, a block's pages ascending
  pair_blk: jnp.ndarray   # [NB * span]
  pair_jj: jnp.ndarray
  pairs: jnp.ndarray      # [] the live ones
  # blocks of rows of more than one token that read a state
  sblk: jnp.ndarray       # [NB]
  sblks: jnp.ndarray      # []
  decode: jnp.ndarray     # [B] rows of one token that read a state
  # pages folded (and fresh slots zeroed), a row's ascending
  e_row: jnp.ndarray      # [NE]
  e_jj: jnp.ndarray       # [NE] the page, counted as `jj`
  e_zero: jnp.ndarray     # [NE] the state before it reads as zeros
  e_add: jnp.ndarray      # [NE] a page is folded (else the slot is zeroed)
  e_cnt: jnp.ndarray      # [B] a row's entries
  entries: jnp.ndarray    # [] the live ones


def PlanSizes(b: int, t: int, page: int, wmax: int, bq: int = QUERY_BLOCK):
  """(blocks, span pages, fold entries): the static room of a StepPlan."""
  return b + t // bq, SpanPages(page, wmax), b + t // page + 1


def BuildStepPlan(rows, b: int, page: int, bq: int = QUERY_BLOCK,
                  reset: bool = True) -> StepPlan:
  """b: rows of the block tables. reset: False leaves a slot as its last
  occupant left it where a row starts a request (what a control and a test
  break, nothing else).

  Written in `jax.lax` over constants of numpy, as ops/run_write.
  BuildWriteRuns is and for its reason: the step program is traced in every
  process and each `jnp` call on a tracer is a trace of its own (these lists
  through `jnp` were 182 of them, a quarter of a second on an idle host:
  PERF.md section 6, PR 51). A list's owner (`searchsorted`) is a comparison
  against the cumulated counts, summed (run_write.Owner), and what a list
  reads of its owners is one gather a list (run_write.TakeRows)."""
  lax, i32 = jax.lax, np.int32
  t = rows.row_of.shape[0]
  nb, span, ne = PlanSizes(b, t, page, rows.row_cols.shape[1], bq)
  to_i32 = lambda x: lax.convert_element_type(x, i32)
  zeros = lambda n: np.zeros((n,), np.int32)
  cols = lambda *leaves: lax.concatenate(
      [lax.reshape(to_i32(x), x.shape + (1,)) for x in leaves], 1)
  col = lambda table, j: lax.index_in_dim(table, j, 1, keepdims=False)
  last = lambda x: lax.index_in_dim(x, x.shape[0] - 1, 0, keepdims=False)
  both = lax.bitwise_and
  p0 = to_i32(rows.row_q_pos)
  ln = to_i32(rows.row_len)
  live = lax.gt(ln, i32(0))
  fresh = (both(live, lax.eq(p0, i32(0))) if reset
           else lax.full_like(live, False))
  j0 = lax.div(p0, i32(page))
  start = lax.clamp(i32(0), to_i32(col(rows.row_cols, 0)), i32(t - 1))
  row = lax.clamp(i32(0), to_i32(rows.row_of), i32(b - 1))
  pos = to_i32(rows.pos)
  column = lax.max(to_i32(rows.col_of), i32(0))
  folds = lax.select(live, lax.sub(lax.div(lax.add(p0, ln), i32(page)), j0),
                     zeros(b))
  # blocks
  nblk = lax.div(lax.add(ln, i32(bq - 1)), i32(bq))
  cum = lax.cumsum(nblk)
  before = lax.sub(cum, nblk)
  reads = both(live, lax.bitwise_not(fresh))
  owner, i = run_write.Owner(cum, nb)
  blk_row = lax.min(owner, i32(b - 1))
  mine = run_write.TakeRows(
      cols(before, ln, start, p0, j0, both(reads, lax.gt(ln, i32(1)))),
      blk_row, nb)
  into = lax.mul(lax.sub(i, col(mine, 0)), i32(bq))   # tokens of the row before
  blk_live = lax.lt(i, lax.broadcast(last(cum), (nb,)))
  blk_n = lax.select(
      blk_live, lax.clamp(i32(0), lax.sub(col(mine, 1), into), i32(bq)),
      zeros(nb))
  blk_first = lax.select(blk_live, lax.add(col(mine, 2), into), zeros(nb))
  blk_pages = lax.select(
      blk_live,
      lax.add(lax.sub(lax.div(lax.sub(lax.add(lax.add(col(mine, 3), into),
                                              blk_n), i32(1)), i32(page)),
                      col(mine, 4)), i32(1)),
      zeros(nb))
  wide = both(blk_live, lax.ne(col(mine, 5), i32(0)))
  # tokens
  theirs = run_write.TakeRows(cols(j0, before), row, t)
  jj = lax.clamp(i32(0), lax.sub(lax.div(pos, i32(page)), col(theirs, 0)),
                 i32(span - 1))
  valid = lax.convert_element_type(rows.valid, np.bool_)
  off = lax.select(valid, lax.rem(pos, i32(page)),
                   np.arange(t, dtype=np.int32) % page)
  tok_at = lax.select(
      valid,
      lax.add(lax.mul(lax.add(col(theirs, 1), lax.div(column, i32(bq))),
                      i32(bq)), lax.rem(column, i32(bq))),
      zeros(t))
  # pairs
  pcum = lax.cumsum(blk_pages)
  owner, m = run_write.Owner(pcum, nb * span)
  pair_blk = lax.min(owner, i32(nb - 1))
  held = run_write.TakeRows(cols(lax.sub(pcum, blk_pages)), pair_blk,
                            nb * span)
  pair_jj = lax.clamp(i32(0), lax.sub(m, col(held, 0)), i32(span - 1))
  # blocks that read a state on the MXU (the wide ones, ascending: the j-th
  # is the block as many of the cumulated count do not pass j), rows that
  # read one on the VPU
  wcum = lax.cumsum(to_i32(wide))
  owner, j = run_write.Owner(wcum, nb)
  sblk = lax.select(lax.lt(j, lax.broadcast(last(wcum), (nb,))), owner,
                    zeros(nb))
  # fold entries
  cnt = lax.add(folds, to_i32(both(fresh, lax.eq(folds, i32(0)))))
  ecum = lax.cumsum(cnt)
  owner, e = run_write.Owner(ecum, ne)
  e_row = lax.min(owner, i32(b - 1))
  mine = run_write.TakeRows(cols(lax.sub(ecum, cnt), fresh, folds), e_row, ne)
  e_jj = lax.clamp(i32(0), lax.sub(e, col(mine, 0)), i32(span - 1))
  return StepPlan(
      row=row, jj=jj, off=off, j0=j0, off0=lax.rem(p0, i32(page)),
      start=start, live=live, fresh=fresh, folds=folds, blk_row=blk_row,
      blk_first=blk_first, blk_n=blk_n, tok_at=tok_at, pair_blk=pair_blk,
      pair_jj=pair_jj, pairs=last(pcum), sblk=sblk, sblks=last(wcum),
      decode=both(reads, lax.eq(ln, i32(1))), e_row=e_row, e_jj=e_jj,
      e_zero=both(lax.ne(col(mine, 1), i32(0)), lax.eq(e_jj, i32(0))),
      e_add=lax.gt(col(mine, 2), e_jj), e_cnt=cnt, entries=last(ecum))


def StepCounts(row_q_pos, row_len, page: int) -> tuple[int, int, int]:
  """(rows with a state, pages folded, tokens attended in open chunks) of a
  step, from the host's own view of its rows (numpy): what the engine
  counts a step."""
  p0 = np.asarray(row_q_pos, np.int64)
  ln = np.asarray(row_len, np.int64)
  live = ln > 0
  folds = np.where(live, (p0 + ln) // page - p0 // page, 0)
  # token i of a row attends the open chunk's tokens and the row's own up to
  # itself: off0 + i + 1
  off0 = p0 % page
  attended = np.where(live, ln * off0 + ln * (ln + 1) // 2, 0)
  return int(np.sum(live)), int(np.sum(folds)), int(np.sum(attended))


def _Gates(plan: StepPlan, rows, log_g, gate_pool, tables, page: int):
  """-> (C [T, Nkv]: a token's cumulated log-gate since its row's open
  chunk's start, offs [B, span, Nkv]: that sum at the start of each page of
  the span, c_page [T, Nkv]: the same since the token's own page's start,
  which is what the pages keep)."""
  t = log_g.shape[0]
  lg = jnp.where(rows.valid[:, None], log_g.astype(jnp.float32), 0.0)
  cs = jnp.cumsum(lg, axis=0)
  before = (cs - lg)[plan.start]                              # [B, Nkv]
  open_page = tables[jnp.arange(tables.shape[0]),
                     jnp.clip(plan.j0, 0, tables.shape[1] - 1)]
  held = gate_pool[open_page, :,
                   jnp.clip(plan.off0 - 1, 0, page - 1)]      # [B, Nkv]
  carry = jnp.where(((plan.off0 > 0) & plan.live)[:, None], held, 0.0)
  c = carry[plan.row] + cs - before[plan.row]
  n_span = plan.pair_jj.shape[0] // plan.blk_row.shape[0]
  p0 = plan.j0 * page + plan.off0
  first = (plan.j0[:, None] + jnp.arange(n_span)[None]) * page  # [B, span]
  idx = plan.start[:, None] + first - 1 - p0[:, None]
  ln = rows.row_len.astype(jnp.int32)
  ok = (jnp.arange(n_span)[None] >= 1) & (first - 1 >= p0[:, None]) & (
      first - 1 < (p0 + ln)[:, None])
  offs = jnp.where(ok[..., None], c[jnp.clip(idx, 0, t - 1)], 0.0)
  # a page of the span before the step's first token is the open chunk's own
  # (jj == 0) and starts the count
  return c, offs, c - offs[plan.row, plan.jj]


def _WritePages(plan: StepPlan, rows, pool, tables, k, v, c_page, page: int):
  np_total = pool.key.shape[0]
  t_pages = tables.shape[1]
  logical = jnp.clip(rows.pos.astype(jnp.int32) // page, 0, t_pages - 1)
  phys = jnp.where(rows.valid, tables[plan.row, logical], np_total - 1)
  return NestedMap(
      key=pool.key.at[phys, plan.off].set(k.astype(pool.key.dtype)),
      value=pool.value.at[phys, plan.off].set(v.astype(pool.value.dtype)),
      gate=pool.gate.at[phys, :, plan.off].set(c_page))


def _SpanTable(plan: StepPlan, tables, span: int):
  """[B, span] physical pages of each row's span."""
  t_pages = tables.shape[1]
  lp = jnp.clip(plan.j0[:, None] + jnp.arange(span)[None], 0, t_pages - 1)
  return jnp.take_along_axis(tables, lp, axis=1)


# .. the XLA lowering ..........................................................


def _XlaChunk(plan, rows, q, pool, span_table, c, offs, page: int):
  """-> (num [T, N, H], den [T, N]) of the open chunk and the step's own
  tokens, every token over its row's gathered span."""
  t, n, h = q.shape
  nk = pool.key.shape[2]
  f32 = jnp.float32
  pages = span_table[plan.row]                                 # [T, span]
  kd = pool.key[pages].astype(f32)                             # [T,span,P,c,H]
  vd = pool.value[pages].astype(f32)
  gd = pool.gate[pages]                                        # [T,span,c,P]
  qg = q.astype(f32).reshape(t, nk, n // nk, h)
  s = jnp.einsum("tcgh,tmpch->tcgmp", qg, kd, precision=_HIGHEST)
  span = pages.shape[1]
  slot = ((plan.j0[plan.row][:, None] + jnp.arange(span)[None]) * page
          )[..., None] + jnp.arange(page)[None, None]          # [T, span, P]
  keep = rows.valid[:, None, None] & (
      slot <= rows.pos.astype(jnp.int32)[:, None, None])
  at = offs[plan.row][..., None] + gd                          # [T,span,c,P]
  decay = jnp.where(keep[:, :, None, :], c[:, None, :, None] - at, -jnp.inf)
  w = jnp.where(keep[:, None, None],
                jnp.square(s) * jnp.exp(decay).transpose(0, 2, 1, 3)[:, :, None],
                0.0)
  num = jnp.einsum("tcgmp,tmpch->tcgh", w, vd, precision=_HIGHEST)
  return num.reshape(t, n, h), jnp.sum(w, axis=(-1, -2)).reshape(t, n)


def _XlaState(plan, rows, q, state, norm, base=0):
  """-> (num [T, N, H], den [T, N]) of every token against its slot's state
  as the step found it (zeros where the row starts a request)."""
  t, n, h = q.shape
  slots, nk = state.shape[:2]
  pq = Phi(q.reshape(t, nk, n // nk, h), query=True)           # [T,c,g,D]
  reads = (rows.valid & ~plan.fresh[plan.row])[:, None, None]
  st = state[plan.row + base]                                  # [T,c,H,D]
  zt = norm.reshape(slots, nk, -1)[plan.row + base]
  num = jnp.einsum("tcgd,tchd->tcgh", pq, st, precision=_HIGHEST)
  den = jnp.einsum("tcgd,tcd->tcg", pq, zt, precision=_HIGHEST)
  return (jnp.where(reads[..., None], num, 0.0).reshape(t, n, h),
          jnp.where(reads, den, 0.0).reshape(t, n))


def _FoldOperands(plan, pool, span_table, page: int):
  """The pages the step folds, gathered by entry: (k [NE, Nkv, P, H]; va
  [NE, Nkv, P, H + 8] f32: v weighted by the decay to the page's end and, in
  column H, that decay itself (what z takes); w [NE, Nkv, 8, P]: row 1 the
  page's whole decay, 0 where the state before it reads as zeros)."""
  phys = span_table[plan.e_row, plan.e_jj]                     # [NE]
  kf = jnp.swapaxes(pool.key[phys], 1, 2)                      # [NE,c,P,H]
  vf = jnp.swapaxes(pool.value[phys], 1, 2).astype(jnp.float32)
  cc = pool.gate[phys]                                         # [NE, c, P]
  total = cc[:, :, page - 1:]
  to_end = jnp.where(plan.e_add[:, None, None], jnp.exp(total - cc), 0.0)
  whole = jnp.where(plan.e_zero[:, None, None], 0.0, jnp.exp(total))
  w = jnp.zeros(cc.shape[:2] + (SUBLANES, page), jnp.float32)
  w = w.at[:, :, 1].set(jnp.broadcast_to(whole, to_end.shape))
  va = jnp.concatenate(
      [vf * to_end[..., None], to_end[..., None],
       jnp.zeros(vf.shape[:-1] + (SUBLANES - 1,), jnp.float32)], axis=-1)
  return kf, va, w


def _XlaFold(plan, state, norm, kf, va, w, base=0):
  slots, nk, h = state.shape[:3]
  zshape = norm.shape[1:]

  def _Entry(carry, xs):
    s, z = carry
    e, kk, vv, ww, slot, zero = xs
    vv, to_end = vv[..., :h], vv[..., h]
    pk = Phi(kk)                                               # [c, P, D]
    s_old = jnp.where(zero, 0.0, s[slot])
    z_old = jnp.where(zero, 0.0, z[slot])
    g = ww[:, 1, :1]                                           # [c, 1]
    s_new = g[..., None] * s_old + jnp.einsum(
        "cph,cpd->chd", vv, pk, precision=_HIGHEST)
    z_new = g * z_old.reshape(nk, -1) + jnp.einsum(
        "cp,cpd->cd", to_end, pk, precision=_HIGHEST)
    on = e < plan.entries
    s = s.at[slot].set(jnp.where(on, s_new, s[slot]))
    z = z.at[slot].set(jnp.where(on, z_new.reshape(zshape), z[slot]))
    return (s, z), None

  ne = plan.e_row.shape[0]
  (state, norm), _ = jax.lax.scan(_Entry, (state, norm), (
      jnp.arange(ne), kf, va, w, plan.e_row + base, plan.e_zero))
  return state, norm


# .. the Pallas lowering .......................................................


def _Dot(a, b, contract, precision=None):
  return jax.lax.dot_general(
      a, b, ((contract[:1], contract[1:]), ((), ())), precision=precision,
      preferred_element_type=jnp.float32)


def _ChunkKernel(blk_ref, jj_ref, phys_ref, q_ref, aux_ref, k_ref, v_ref,
                 g_ref, num_ref, den_ref, *, heads: int, group: int):
  """One (query block, page of its row's span): the block's queries against
  the page's keys in the attention form, summed into the block's output."""
  del blk_ref, phys_ref
  h = k_ref.shape[2]
  page = g_ref.shape[2]

  @pl.when(jj_ref[pl.program_id(0)] == 0)
  def _Init():
    num_ref[...] = jnp.zeros(num_ref.shape, num_ref.dtype)
    den_ref[...] = jnp.zeros(den_ref.shape, den_ref.dtype)

  keys, values = _HeadPages(k_ref, heads), _HeadPages(v_ref, heads)
  aux = aux_ref[0, 0]                                   # [Bq, Nkv + 1]
  bq = aux.shape[0]
  slot = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1).astype(jnp.float32)
  keep = slot <= aux[:, heads:heads + 1]                # [Bq, P]
  lane = jax.lax.broadcasted_iota(jnp.int32, (bq, den_ref.shape[1]), 1)
  den = den_ref[...]
  for c in range(heads):
    decay = jnp.exp(jnp.where(keep, aux[:, c:c + 1] - g_ref[0, c:c + 1, :],
                              -jnp.inf))                # [Bq, P]
    for g in range(group):
      n = c * group + g
      lanes = pl.ds(n * h, h)
      s = _Dot(q_ref[:, lanes], keys[c], (1, 1))        # [Bq, P]
      w = jnp.where(keep, s * s * decay, 0.0)
      num_ref[:, lanes] += _Dot(w.astype(values[c].dtype), values[c], (1, 0))
      den = den + jnp.where(lane == n, jnp.sum(w, axis=1, keepdims=True), 0.0)
  den_ref[...] = den


@functools.partial(jax.jit, static_argnames=("heads", "group", "interpret"))
def _ChunkCall(pairs, pair_blk, pair_jj, pair_phys, qb, aux, k_pages, v_pages,
               gates, *, heads: int, group: int, interpret: bool):
  """qb [NB * Bq, N * H]; aux [NB, span, Bq, Nkv + 1] (a query's cumulated
  log-gate less the page's start's, a KV head, and the last key of the page
  it sees); pages as rows [NP, P * Nkv, H]; gates [NP, Nkv, P] ->
  (num [NB * Bq, N * H] f32, den [NB * Bq, L] f32: lane n a head's), written
  where a live block is, and nowhere else."""
  nb, _, bq, _ = aux.shape
  width = qb.shape[1]
  rows_p, h = k_pages.shape[1:]
  lanes = max(LANES, -(-heads * group // LANES) * LANES)
  by_blk = lambda m, blk, *_: (blk[m], 0)
  page = lambda m, blk, jj, phys: (phys[m], 0, 0)
  with observe.Scope("retention_chunk"):
    return pl.pallas_call(
        functools.partial(_ChunkKernel, heads=heads, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pairs,),
            in_specs=[
                pl.BlockSpec((bq, width), by_blk),
                pl.BlockSpec((1, 1, bq, heads + 1),
                             lambda m, blk, jj, _: (blk[m], jj[m], 0, 0)),
                pl.BlockSpec((1, rows_p, h), page),
                pl.BlockSpec((1, rows_p, h), page),
                pl.BlockSpec((1, heads, gates.shape[2]), page),
            ],
            out_specs=[pl.BlockSpec((bq, width), by_blk),
                       pl.BlockSpec((bq, lanes), by_blk)]),
        out_shape=[jax.ShapeDtypeStruct((nb * bq, width), jnp.float32),
                   jax.ShapeDtypeStruct((nb * bq, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(pair_blk, pair_jj, pair_phys, qb, aux, k_pages, v_pages, gates)


def _DotF32(a, b, contract, exact: bool):
  """A product of two f32 operands to f32: at the highest precision (six
  passes of the MXU) where `exact`, else in three passes of 16-bit halves,
  a_hi b_hi + a_hi b_lo + a_lo b_hi, whose error is 2^-16 of a term."""
  if exact:
    return _Dot(a, b, contract, _HIGHEST)
  bf = jnp.bfloat16
  a_hi, b_hi = a.astype(bf), b.astype(bf)
  a_lo = (a - a_hi.astype(jnp.float32)).astype(bf)
  b_lo = (b - b_hi.astype(jnp.float32)).astype(bf)
  return (_Dot(a_hi, b_hi, contract) + _Dot(a_hi, b_lo, contract)
          + _Dot(a_lo, b_hi, contract))


def _LaneSum(x, lanes: int):
  """x [R, k * lanes] -> [R, lanes]: the sum of its lane tiles."""
  acc = x[:, :lanes]
  for u in range(1, x.shape[1] // lanes):
    acc = acc + x[:, u * lanes:(u + 1) * lanes]
  return acc


def _PhiTile(x, first_offset, offsets: int, query: bool):
  """x [R, H] f32 -> [R, offsets * H]: the features of the `offsets` offsets
  from `first_offset` (traced) on: x times itself rotated by the offset, a
  lane rotation and a product an offset."""
  h = x.shape[1]
  chunks = []
  for u in range(offsets):
    o = first_offset + u
    chunk = x * pltpu.roll(x, (h - o) % h, axis=1)       # x_i x_{i + o}
    if query:
      chunk = chunk * jnp.where((o == 0) | (o == h // 2), 1.0, 2.0)
    chunks.append(chunk)
  return chunks[0] if offsets == 1 else jnp.concatenate(chunks, axis=1)


def _NormDot(phi, z_ref, first_offset, offsets: int):
  """phi [R, offsets * H] against z's rows of those offsets -> [R, H], to be
  summed over its lanes."""
  h = z_ref.shape[3]
  zz = phi[:, :h] * z_ref[0, 0, pl.ds(first_offset, 1), :]
  for u in range(1, offsets):
    zz = zz + phi[:, u * h:(u + 1) * h] * z_ref[
        0, 0, pl.ds(first_offset + u, 1), :]
  return zz


def _DecodeKernel(src_ref, lo_ref, hi_ref, on_ref, phi_ref, s_ref, z_ref,
                  num_ref, den_ref, acc_ref, zacc_ref, *, group: int,
                  offsets: int):
  """One (KV head, slot, tile of the state) for a row of ONE token: its
  queries' features times the tile on the VPU, in f32; the state is read
  once and nothing else of its size moves."""
  del src_ref, lo_ref, hi_ref
  d = pl.program_id(2)
  h = s_ref.shape[2]

  @pl.when(d == 0)
  def _Init():
    acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
    zacc_ref[...] = jnp.zeros(zacc_ref.shape, zacc_ref.dtype)

  @pl.when(on_ref[pl.program_id(1)] == 1)
  def _Tile():
    s = s_ref[0, 0]                                        # [H, Dt]
    for g in range(group):
      ph = phi_ref[0, 0, g:g + 1, :]                       # [1, Dt]
      acc_ref[g] += _LaneSum(s * ph, h)                    # [H, H]
      zacc_ref[g:g + 1, :] += _NormDot(ph, z_ref, d * offsets, offsets)

  @pl.when(d == pl.num_programs(2) - 1)
  def _Emit():
    eye = (jax.lax.broadcasted_iota(jnp.int32, (h, h), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (h, h), 1))
    for g in range(group):
      col = jnp.sum(acc_ref[g], axis=1, keepdims=True)     # [H, 1]
      num_ref[0, 0, g:g + 1, :] = jnp.sum(
          jnp.where(eye, col, 0.0), axis=0, keepdims=True)
      den_ref[0, 0, g:g + 1, :] = jnp.broadcast_to(
          jnp.sum(zacc_ref[g:g + 1, :], axis=1, keepdims=True), (1, h))
    for g in range(group, num_ref.shape[2]):
      num_ref[0, 0, g:g + 1, :] = jnp.zeros((1, h), jnp.float32)
      den_ref[0, 0, g:g + 1, :] = jnp.zeros((1, h), jnp.float32)


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def _DecodeCall(src, lo, hi, on, phi, state, norm, *, group: int,
                interpret: bool):
  """phi [B, Nkv, G8, D] f32: the features of each row's first token's
  queries; state [slots, Nkv, H, D]; norm [slots, Nkv, H / 2 + 1, H] -> (num
  [B, Nkv, G8, H], den [B, Nkv, G8, H]: every lane the sum), zeros where a
  row is not `on`. A row that is not reads no tile: its index maps name the
  tile the last row that was left in place (`src`, `lo`, `hi`)."""
  b, nk, g8, _ = phi.shape
  h = state.shape[2]
  offsets = TileOffsets(h)
  tile = offsets * h
  nd = Offsets(h) // offsets

  def _Tile(c, r, k, src, lo, hi, _):
    return (src[r], c, 0, jnp.clip(k, lo[r], hi[r]))

  mine = lambda c, r, k, *_: (r, c, 0, 0)
  with observe.Scope("retention_state"):
    return pl.pallas_call(
        functools.partial(_DecodeKernel, group=group, offsets=offsets),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nk, b, nd),
            in_specs=[
                pl.BlockSpec((1, 1, g8, tile),
                             lambda c, r, k, *_: (r, c, 0, k)),
                pl.BlockSpec((1, 1, h, tile), _Tile),
                pl.BlockSpec((1, 1, Offsets(h), h),
                             lambda c, r, k, src, *_: (src[r], c, 0, 0)),
            ],
            out_specs=[pl.BlockSpec((1, 1, g8, h), mine),
                       pl.BlockSpec((1, 1, g8, h), mine)],
            scratch_shapes=[pltpu.VMEM((group, h, h), jnp.float32),
                            pltpu.VMEM((g8, h), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, nk, g8, h), jnp.float32),
                   jax.ShapeDtypeStruct((b, nk, g8, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(src, lo, hi, on, phi, state, norm)


def _BlockKernel(sblk_ref, slot_ref, q_ref, s_ref, z_ref, num_ref, den_ref, *,
                 group: int, nd: int, offsets: int):
  """One (block of a row of several tokens, KV head, tile of the state): the
  block's queries' features, built by lane rotations, times the tile on the
  MXU."""
  del sblk_ref, slot_ref
  d = pl.program_id(0) % nd
  h = s_ref.shape[2]
  bq = q_ref.shape[0]

  @pl.when(d == 0)
  def _Init():
    num_ref[...] = jnp.zeros(num_ref.shape, num_ref.dtype)
    den_ref[...] = jnp.zeros(den_ref.shape, den_ref.dtype)

  s = s_ref[0, 0]                                          # [H, Dt] f32
  mx = q_ref.dtype
  if mx == jnp.float32:
    parts, precision = (s,), _HIGHEST
  else:
    hi = s.astype(mx)
    parts, precision = (hi, (s - hi.astype(jnp.float32)).astype(mx)), None
  lane = jax.lax.broadcasted_iota(jnp.int32, (bq, den_ref.shape[1]), 1)
  den = den_ref[...]
  for g in range(group):
    cols = pl.ds(g * h, h)
    phi = _PhiTile(q_ref[:, cols].astype(jnp.float32), d * offsets, offsets,
                   query=True)                             # [Bq, Dt] f32
    pm = phi.astype(mx)
    for part in parts:
      num_ref[:, cols] += _Dot(pm, part, (1, 1), precision)
    zz = _NormDot(phi, z_ref, d * offsets, offsets)
    den = den + jnp.where(lane == g, jnp.sum(zz, axis=1, keepdims=True), 0.0)
  den_ref[...] = den


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def _BlockCall(sblks, sblk, slot_of, qb, state, norm, *, group: int,
               interpret: bool):
  """qb [NB * Bq, N * H]; sblk [NB] the blocks to run, `sblks` of them live;
  slot_of [NB] a block's slot -> (num [NB * Bq, N * H] f32, den [NB * Bq,
  Nkv * L] f32: lane g of a KV head's L a head's), written where one of
  those blocks is."""
  nb = sblk.shape[0]
  bq = qb.shape[0] // nb
  _, nk, h, _ = state.shape
  offsets = TileOffsets(h)
  tile = offsets * h
  nd = Offsets(h) // offsets
  per = nk * nd

  def _C(m):
    return (m // nd) % nk

  def _Out(m, sblk, _):
    return (sblk[m // per], _C(m))

  with observe.Scope("retention_state"):
    return pl.pallas_call(
        functools.partial(_BlockKernel, group=group, nd=nd, offsets=offsets),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(sblks * per,),
            in_specs=[
                pl.BlockSpec((bq, group * h), _Out),
                pl.BlockSpec((1, 1, h, tile), lambda m, sblk, slot: (
                    slot[sblk[m // per]], _C(m), 0, m % nd)),
                pl.BlockSpec((1, 1, Offsets(h), h), lambda m, sblk, slot: (
                    slot[sblk[m // per]], _C(m), 0, 0)),
            ],
            out_specs=[pl.BlockSpec((bq, group * h), _Out),
                       pl.BlockSpec((bq, LANES), _Out)]),
        out_shape=[jax.ShapeDtypeStruct(qb.shape, jnp.float32),
                   jax.ShapeDtypeStruct((nb * bq, nk * LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(sblk, slot_of, qb, state, norm)


def _FoldKernel(entry_ref, c_ref, d_ref, first_ref, zero_ref, slot_ref, k_ref,
                v_ref, w_ref, s_ref, z_ref, s_out, z_out, *, offsets: int):
  """One (slot, KV head, tile of the state, page folded): the tile times the
  page's decay plus the page's keys' features against its weighted values.
  A slot's pages follow each other on one tile, which stays where it is
  until the last is in (and z's rows with it, whole). What is folded
  compounds over a request: the products are f32, in three passes of 16-bit
  halves."""
  del c_ref, slot_ref
  m = pl.program_id(0)
  first = first_ref[m] == 1
  zero = zero_ref[entry_ref[m]] == 1
  h = s_ref.shape[2]
  row0 = d_ref[m] * offsets
  kk = k_ref[0, 0]                                         # [P, H]
  phi = _PhiTile(kk.astype(jnp.float32), row0, offsets, query=False)
  w = w_ref[0, 0]                                          # [8, P]
  # the page's decay lies along row 1's lanes, as often as they go into a
  # tile's: Mosaic broadcasts one way at a time, and this way is down

  def _Along(width):
    page = w.shape[1]
    if width % page:
      return jnp.broadcast_to(w[1:2, 0:1], (1, width))     # not on a TPU
    return jnp.concatenate([w[1:2, :]] * (width // page), axis=1)

  g, g_tile = _Along(h), _Along(phi.shape[1])
  # [H + 8, Dt]: the values' rows, then z's
  both = _DotF32(v_ref[0, 0], phi, (0, 0), exact=kk.dtype == jnp.float32)
  inc, zinc = both[:h], both[h:]

  @pl.when(first)
  def _First():
    s_out[0, 0] = g_tile * jnp.where(zero, 0.0, s_ref[0, 0]) + inc
    for u in range(offsets):
      z_old = jnp.where(zero, 0.0, z_ref[0, 0, pl.ds(row0 + u, 1), :])
      z_out[0, 0, pl.ds(row0 + u, 1), :] = (
          g * z_old + zinc[0:1, u * h:(u + 1) * h])

  @pl.when(jnp.logical_not(first))
  def _Next():
    s_out[0, 0] = g_tile * s_out[0, 0] + inc
    for u in range(offsets):
      z_out[0, 0, pl.ds(row0 + u, 1), :] = (
          g * z_out[0, 0, pl.ds(row0 + u, 1), :]
          + zinc[0:1, u * h:(u + 1) * h])


def _FoldGrid(plan: StepPlan, nk: int, nd: int):
  """The fold kernel's grid, flat: a slot's (KV head, tile) pairs in order,
  and inside a pair the slot's pages. -> (programs [], and a program's
  entry, KV head, tile and whether it is its pair's first: each [NE * per])."""
  per = nk * nd
  ne = plan.e_row.shape[0]
  cnt = plan.e_cnt
  steps = jnp.cumsum(cnt * per)
  m = jnp.arange(ne * per, dtype=jnp.int32)
  r = jnp.clip(jnp.searchsorted(steps, m, side="right"), 0, cnt.shape[0] - 1)
  n_r = jnp.maximum(cnt[r], 1)
  u = m - (steps - cnt * per)[r]
  j = u % n_r
  pair = jnp.clip(u // n_r, 0, per - 1)
  entry = jnp.clip((jnp.cumsum(cnt) - cnt)[r] + j, 0, ne - 1)
  return (steps[-1].astype(jnp.int32), entry.astype(jnp.int32),
          (pair // nd).astype(jnp.int32), (pair % nd).astype(jnp.int32),
          (j == 0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _FoldCall(programs, m_entry, m_c, m_d, m_first, e_slot, e_zero, kf, va, w,
              state, norm, *, interpret: bool):
  """kf [NE, Nkv, P, H]; va [NE, Nkv, P, H + 8] f32; w [NE, Nkv, 8, P]
  (`_FoldOperands`) -> (state, norm), in place: a tile no entry names is not
  touched."""
  _, nk, page, h = kf.shape
  offsets = TileOffsets(h)
  tile = offsets * h
  # the slot rides the entry's place in the list
  m_slot = e_slot[m_entry]

  by_entry = lambda m, entry, c, *_: (entry[m], c[m], 0, 0)
  tile_of = lambda m, entry, c, dd, first, zero, slot: (
      slot[m], c[m], 0, dd[m])
  rows_of = lambda m, entry, c, dd, first, zero, slot: (slot[m], c[m], 0, 0)
  with observe.Scope("retention_state"):
    return pl.pallas_call(
        functools.partial(_FoldKernel, offsets=offsets),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(programs,),
            in_specs=[
                pl.BlockSpec((1, 1, page, h), by_entry),
                pl.BlockSpec((1, 1, page, h + SUBLANES), by_entry),
                pl.BlockSpec((1, 1, SUBLANES, page), by_entry),
                pl.BlockSpec((1, 1, h, tile), tile_of),
                pl.BlockSpec((1, 1, Offsets(h), h), rows_of),
            ],
            out_specs=[pl.BlockSpec((1, 1, h, tile), tile_of),
                       pl.BlockSpec((1, 1, Offsets(h), h), rows_of)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
        input_output_aliases={9: 0, 10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(m_entry, m_c, m_d, m_first, e_zero.astype(jnp.int32), m_slot, kf, va,
      w, state, norm)


def _QueryBlocks(plan: StepPlan, q, bq: int):
  """q [T, N, H] -> [NB * Bq, N * H]: every block's tokens, gathered."""
  t, n, h = q.shape
  idx = jnp.clip(plan.blk_first[:, None] + jnp.arange(bq)[None], 0, t - 1)
  return q.reshape(t, n * h)[idx.reshape(-1)]


def _PallasChunk(plan, rows, q, qb, pool, span_table, c, offs, page: int,
                 bq: int, interpret: bool):
  t, n, h = q.shape
  npg, _, nk, _ = pool.key.shape
  nb = plan.blk_row.shape[0]
  span = span_table.shape[1]
  # a query of block i against page jj of its row's span: its cumulated
  # log-gate less the page's start's, and the last key of the page it sees
  tok = jnp.clip(plan.blk_first[:, None] + jnp.arange(bq)[None], 0, t - 1)
  ok = jnp.arange(bq)[None] < plan.blk_n[:, None]               # [NB, Bq]
  r = plan.blk_row
  rel = c[tok][:, None] - offs[r][:, :, None]                   # [NB,span,Bq,c]
  first = (plan.j0[r][:, None] + jnp.arange(span)[None]) * page  # [NB, span]
  last = jnp.where(ok[:, None], rows.pos.astype(jnp.int32)[tok][:, None]
                   - first[..., None], -1)                       # [NB,span,Bq]
  aux = jnp.concatenate([rel, last[..., None].astype(jnp.float32)], axis=-1)
  phys = span_table[r[plan.pair_blk], plan.pair_jj]
  num, den = _ChunkCall(
      plan.pairs, plan.pair_blk, plan.pair_jj, phys, qb, aux,
      pool.key.reshape(npg, page * nk, h),
      pool.value.reshape(npg, page * nk, h), pool.gate,
      heads=nk, group=n // nk, interpret=interpret)
  at = plan.tok_at
  return num[at].reshape(t, n, h), den[at][:, :n]


def _PallasState(plan, rows, q, qb, state, norm, bq: int, interpret: bool,
                 base=0):
  t, n, h = q.shape
  nk = state.shape[1]
  b = plan.live.shape[0]
  group = n // nk
  # rows of one token: the VPU kernel over every slot's tiles, their
  # features made here (a few hundred vectors, in f32)
  g8 = -(-group // SUBLANES) * SUBLANES
  phi = jnp.pad(Phi(q[plan.start].reshape(b, nk, group, h), query=True),
                ((0, 0), (0, 0), (0, g8 - group), (0, 0)))
  on = plan.decode
  nd = Offsets(h) // TileOffsets(h)
  # a row that is not `on` names the tile the last one that was left behind
  # (before the first: the first one's first tile), so nothing is fetched
  idx = jnp.arange(b, dtype=jnp.int32)
  prev = jax.lax.cummax(jnp.where(on, idx, -1))
  nxt = jnp.min(jnp.where(on, idx, b - 1))
  src = jnp.where(prev >= 0, prev, nxt).astype(jnp.int32)
  lo = jnp.where(on, 0, jnp.where(prev >= 0, nd - 1, 0)).astype(jnp.int32)
  hi = jnp.where(on, nd - 1, lo).astype(jnp.int32)
  num1, den1 = _DecodeCall(src + base, lo, hi, on.astype(jnp.int32), phi,
                           state, norm, group=group, interpret=interpret)
  num1 = num1[:, :, :group].reshape(b, n, h)
  den1 = den1[:, :, :group, 0].reshape(b, n)
  # rows of several: the MXU kernel over their blocks
  numb, denb = _BlockCall(plan.sblks, plan.sblk, plan.blk_row + base, qb,
                          state, norm, group=group, interpret=interpret)
  at = plan.tok_at
  denb = denb[at].reshape(t, nk, LANES)[:, :, :group].reshape(t, n)
  ln = rows.row_len.astype(jnp.int32)[plan.row]
  reads = rows.valid & ~plan.fresh[plan.row]
  one = (reads & (ln == 1))[:, None]
  wide = (reads & (ln > 1))[:, None]
  num = jnp.where(one[..., None], num1[plan.row], jnp.where(
      wide[..., None], numb[at].reshape(t, n, h), 0.0))
  den = jnp.where(one, den1[plan.row], jnp.where(wide, denb, 0.0))
  return num, den


def PackedRetention(q, k, v, log_g, state, norm, pool, tables, rows, *,
                    eps: float, plan: StepPlan | None = None, lowering: str = "auto",
                    interpret: bool | None = None, layer=None):
  """One layer's retention over a packed serving step (module docstring).

  q [T, N, H], normed, rotated and scaled by 1 / sqrt(H); k, v [T, Nkv, H];
  log_g [T, Nkv] <= 0; state [slots, Nkv, H, D] and norm [slots, Nkv, H / 2 +
  1, H] f32 (`InitState`); pool: `key`, `value` [NP, P, Nkv, H] and `gate` [NP,
  Nkv, P] f32, the last page the trash page; tables [B, t_pages], this
  layer's own; rows: core/ragged.RaggedRows (chains only), row b in slot b.
  layer: None, or a scalar index where `state` and `norm` arrive stacked over
  a scanned block's repeats ([L, slots, ...]): the stack is read and written
  as ONE array of L * slots slots with this layer's from `layer * slots`,
  so nothing slices or re-assembles a layer's 0.6 GB.
  -> (y [T, N, H] f32, zeros at padding; state; norm; pool).
  """
  lowering = Lowering(lowering)
  stacked = state.shape[:2] if layer is not None else None
  base = 0
  if layer is not None:
    base = jnp.asarray(layer, jnp.int32) * state.shape[1]
    state = state.reshape((-1,) + state.shape[2:])
    norm = norm.reshape((-1,) + norm.shape[2:])
  t, n, h = q.shape
  page = pool.key.shape[1]
  b = tables.shape[0]
  bq = QUERY_BLOCK
  if plan is None:
    plan = BuildStepPlan(rows, b, page, bq)
  span = plan.pair_jj.shape[0] // plan.blk_row.shape[0]
  on_tpu = jax.default_backend() == "tpu"
  interpret = (not on_tpu) if interpret is None else interpret
  tables = jnp.clip(tables.astype(jnp.int32), 0, pool.key.shape[0] - 1)
  with observe.Scope("retention_gate"):
    c, offs, c_page = _Gates(plan, rows, log_g, pool.gate, tables, page)
    span_table = _SpanTable(plan, tables, span)
  with observe.Scope("kv_write"):
    pool = _WritePages(plan, rows, pool, tables, k, v, c_page, page)
  if lowering == "xla":
    with observe.Scope("retention_chunk"):
      num_c, den_c = _XlaChunk(plan, rows, q, pool, span_table, c, offs, page)
    with observe.Scope("retention_state"):
      num_s, den_s = _XlaState(plan, rows, q, state, norm, base)
  else:
    with observe.Scope("retention_gate"):
      qb = _QueryBlocks(plan, q.astype(pool.key.dtype), bq)
    with observe.Scope("retention_chunk"):
      num_c, den_c = _PallasChunk(plan, rows, q, qb, pool, span_table, c,
                                  offs, page, bq, interpret)
    with observe.Scope("retention_state"):
      num_s, den_s = _PallasState(plan, rows, q, qb, state, norm, bq,
                                  interpret, base)
  with observe.Scope("retention_out"):
    group = n // k.shape[1]
    since = jnp.repeat(jnp.exp(c), group, axis=1)               # [T, N]
    den = since * den_s + den_c + eps
    y = (since[..., None] * num_s + num_c) / den[..., None]
    y = jnp.where(rows.valid[:, None, None], y, 0.0)
  with observe.Scope("retention_state"):
    kf, vw, w = _FoldOperands(plan, pool, span_table, page)
    if lowering == "xla":
      state, norm = _XlaFold(plan, state, norm, kf, vw, w, base)
    else:
      state, norm = _FoldCall(
          *_FoldGrid(plan, state.shape[1], Offsets(h) // TileOffsets(h)),
          plan.e_row + base, plan.e_zero, kf, vw, w, state, norm,
          interpret=interpret)
  if stacked is not None:
    state = state.reshape(stacked + state.shape[1:])
    norm = norm.reshape(stacked + norm.shape[1:])
  return y, state, norm, pool
