"""The page write of a packed serving step, done by runs.

A step's new K and V arrive packed on one token axis (`[T, N, H]`,
core/ragged.py): a row's tokens side by side, in slot order, token t of row
r bound for kv slot `row_q_pos[r] + col`. So what a row adds to its pages is
one contiguous span of the packed axis that lands in consecutive slots, cut
only where a page ends: a RUN is (first packed token, page, first offset in
the page, length <= P). A decode row is a run of one token, a 480-token
chunk four or five runs of up to a page each; padding tokens belong to no
run and are written nowhere (the scatter this replaces sent each of them to
the trash page, one row at a time like every other token).

A copy's length is static on the chip, so a run moves as PIECES of a few
static widths (`Widths`: 1, 16 and a whole page of 128): the widest that is
no longer than the run, side by side, the last one laid back over the one
before it so that it ends where the run ends (tokens written twice are
written the same). A decode row is one piece, a whole page one, any other
run at most 15.

- `BuildWriteRuns` lists the step's pieces from its rows alone, a list a
  width, each with a traced count of the live ones: a few integer ops on
  `[B]`- and `[R]`-sized vectors (`R = MaxRuns(B, T, P)` bounds the runs)
  and a lookup a list. A stack builds them once a step
  (core/attention.BuildRaggedPlan); a layer adds its table's lookup and its
  pool's page base, the pieces' physical pages.
- `WriteRuns` moves them, K and V in one call, the pools aliased in and out:
  - `_RunWriteKernel` (a TPU): one program, every operand left in HBM. A
    loop a width over the LIVE pieces starts a copy `new[tok : tok + w] ->
    pool[page, off : off + w]` for K and for V; a second loop a width waits
    for as many: every copy of the step is in flight before the first is
    waited for. No branch, no arithmetic: the kernel is the same whatever T,
    R or the counts, and costs 20 ms to trace where a kernel that cut each
    run up itself (a branch a power of two) cost 60-90 (PR 49; the
    benchmark's host traces several times slower, and set-up is judged in
    every cell).
  - `_XlaWriteRuns` (elsewhere, and the twin the kernel is held to): the
    same pieces, a `fori_loop` a width of `dynamic_update_slice`.
  Both leave every live token's row bitwise where the scatter put it, and
  every other slot of the pool, the trash page's too, as it was.
- `RunCounts` is the host's twin of the count of runs (numpy, from the
  scheduler's own rows): what the engine's `kv_write_runs` and
  `kv_write_tokens` count.
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
from lingvo_tpu.ops import ragged_block_attend as rba
from lingvo_tpu.ops.diff_attend import PageWrites
from lingvo_tpu.ops.flash_attention import LANES


def MaxRuns(b: int, t: int, page_size: int) -> int:
  """Static bound on the runs of a step of `b` rows and `t` packed tokens:
  they are the (row, page) pairs the step touches, which
  ops/diff_attend.PageWrites bounds."""
  return PageWrites(b, t, page_size)


def Widths(page_size: int, t: int) -> tuple[int, ...]:
  """The static widths a run moves in, ascending: single tokens, 16 and a
  whole page, as far as a run can be that long (no longer than a page, or
  than the pack)."""
  longest = min(page_size, t)
  return tuple(w for w in sorted({1, 16, page_size}) if w <= longest)


def _Pieces(b: int, t: int, page_size: int):
  """[(width, the next width up, static room for the pieces of this width)]
  over `Widths`. A run of n tokens whose width is w (w <= n < the next) takes
  ceil(n / w) pieces; at most `t // w` runs are that long, and their tokens
  at most `t`."""
  widths = Widths(page_size, t)
  out = []
  for w, above in zip(widths, widths[1:] + (min(page_size, t) + 1,)):
    runs = min(MaxRuns(b, t, page_size), t // w)
    out.append((w, above, min(runs * (-(-(above - 1) // w)),
                              (t + (w - 1) * runs) // w)))
  return out


class Runs(NamedTuple):
  """A step's runs as the pieces that move them, the lists of the widths
  (`Widths`, ascending) one after the other; list c starts at `first[c]` (a
  constant of the shapes) and `counts[c]` of its entries are live. All the
  step's rows' alone, none of it a layer's."""
  row: jnp.ndarray      # [S] int32 the piece's row, inside the block table
  logical: jnp.ndarray  # [S] int32 its logical page, inside the table
  tok: jnp.ndarray      # [S] int32 its first token on the packed axis
  off: jnp.ndarray      # [S] int32 its first slot's offset in the page
  first: jnp.ndarray    # [C] int32 where each width's list starts
  counts: jnp.ndarray   # [C] int32 the live pieces of each width
  runs: jnp.ndarray     # [] int32 the runs they move


def TakeRows(table, index, room: int):
  """table[index] over the leading axis, index [room] -> [room, K], an index
  past the end read as the last."""
  return jax.lax.gather(
      table, jax.lax.reshape(index, (room, 1)),
      jax.lax.GatherDimensionNumbers(offset_dims=(1,),
                                     collapsed_slice_dims=(0,),
                                     start_index_map=(0,)),
      slice_sizes=(1, table.shape[1]), mode="clip")


def Owner(ends, room: int):
  """For k in [0, room): how many of the ascending `ends` are <= k, which is
  the entry that owns place k of the list the `ends` cut up; and k."""
  k = np.arange(room, dtype=np.int32)
  over = jax.lax.le(
      jax.lax.broadcast_in_dim(ends, (room, ends.shape[0]), (1,)),
      np.broadcast_to(k[:, None], (room, ends.shape[0])))
  return jax.lax.reduce_sum(jax.lax.convert_element_type(over, jnp.int32),
                            (1,)), k


def BuildWriteRuns(rows, b: int, t_pages: int, page_size: int) -> Runs:
  """rows: the step's core/ragged.RaggedRows (row r's tokens contiguous from
  `row_cols[r, 0]`, token j of them bound for slot `row_q_pos[r] + j`); block
  tables [b, t_pages] of pages of `page_size` slots.

  Written in `jax.lax` over constants of numpy: the step program is traced in
  every process, set-up is judged in every cell, and each `jnp` call on a
  tracer is a trace of its own (the same list through `jnp` was 207 of them,
  0.18 s on an idle host and half a second of `setup_s` on the benchmark's:
  PERF.md section 6, PR 49)."""
  lax, i32 = jax.lax, jnp.int32
  t = rows.row_of.shape[0]
  size = MaxRuns(b, t, page_size)
  cols = lambda *leaves: lax.concatenate(
      [lax.reshape(x, x.shape + (1,)) for x in leaves], 1)
  col = lambda table, j: lax.index_in_dim(table, j, 1, keepdims=False)
  last = lambda x: lax.index_in_dim(x, x.shape[0] - 1, 0, keepdims=False)
  p0 = lax.convert_element_type(rows.row_q_pos, i32)
  n = lax.convert_element_type(rows.row_len, i32)
  # the runs: rows in slot order, a row's pages ascending
  first_page = lax.div(p0, i32(page_size))
  end = lax.add(p0, n)
  n_pages = lax.select(
      lax.gt(n, i32(0)),
      lax.add(lax.sub(lax.div(lax.sub(end, i32(1)), i32(page_size)),
                      first_page), i32(1)),
      lax.full_like(n, 0))
  cum = lax.cumsum(n_pages)
  r, i = Owner(cum, size)
  r = lax.min(r, i32(b - 1))
  runs = lax.min(last(cum), i32(size))
  mine = TakeRows(cols(first_page, lax.sub(cum, n_pages), p0, end,
                    lax.convert_element_type(col(rows.row_cols, 0), i32)), r,
               size)
  first_page, before, p0, end, col0 = (col(mine, j) for j in range(5))
  page = lax.sub(lax.add(first_page, i), before)
  page0 = lax.mul(page, i32(page_size))
  start = lax.max(p0, page0)
  length = lax.select(lax.lt(i, lax.broadcast(runs, (size,))),
                      lax.sub(lax.min(end, lax.add(page0, i32(page_size))),
                              start),
                      np.zeros((size,), np.int32))
  tok = lax.add(col0, lax.sub(start, p0))
  off = lax.sub(start, page0)
  logical = lax.clamp(i32(0), page, i32(t_pages - 1))
  # the pieces, a list a width: the runs that long, each cut into its pieces
  lists, counts, first = [], [], []
  for w, above, room in _Pieces(b, t, page_size):
    pieces = lax.select(
        lax.bitwise_and(lax.ge(length, i32(w)), lax.lt(length, i32(above))),
        lax.div(lax.add(length, i32(w - 1)), i32(w)),
        np.zeros((size,), np.int32))
    cum = lax.cumsum(pieces)
    at, k = Owner(cum, room)
    mine = TakeRows(cols(r, logical, tok, off, length, lax.sub(cum, pieces)),
                 lax.min(at, i32(size - 1)), room)
    # the run's j-th piece; the last ends where the run ends
    into = lax.min(lax.mul(lax.sub(k, col(mine, 5)), i32(w)),
                   lax.sub(col(mine, 4), i32(w)))
    into = lax.select(lax.lt(k, lax.broadcast(last(cum), (room,))), into,
                      np.zeros((room,), np.int32))
    first.append(sum(leaf.shape[0] for leaf, *_ in lists))
    lists.append((col(mine, 0), col(mine, 1),
                  lax.clamp(i32(0), lax.add(col(mine, 2), into), i32(t - w)),
                  lax.clamp(i32(0), lax.add(col(mine, 3), into),
                            i32(page_size - w))))
    counts.append(lax.min(last(cum), i32(room)))
  return Runs(*(lax.concatenate(leaf, 0) for leaf in zip(*lists)),
              first=np.asarray(first, np.int32),
              counts=lax.concatenate([lax.reshape(c, (1,)) for c in counts],
                                     0),
              runs=runs)


def RunCounts(row_q_pos, row_len, page_size: int) -> tuple[int, int]:
  """(runs, tokens in them) of the step with these rows, on the host
  (numpy): `BuildWriteRuns`' count and the sum of its lengths."""
  p0 = np.asarray(row_q_pos, np.int64)
  n = np.asarray(row_len, np.int64)
  pages = np.where(n > 0, (p0 + n - 1) // page_size - p0 // page_size + 1, 0)
  return int(pages.sum()), int(n.sum())


def SupportedOnTpu(h: int) -> bool:
  """Whether the kernel's copies can run on a TPU: a token is whole tiles of
  its page where the head's features fill the 128 lanes."""
  return h % LANES == 0


# -- XLA twin (the CPU serving path) -----------------------------------------


def _XlaWriteRuns(pools, news, pages, runs: Runs):
  """pools: `[NP, P, ...]` each, news: `[T, ...]` each (a pool's token rows)
  -> the pools, every live piece written."""
  pools = tuple(pools)
  for c, w in enumerate(Widths(pools[0].shape[1], news[0].shape[0])):
    def _Piece(i, pools, w=w, first=runs.first[c]):
      j = first + i
      return tuple(
          jax.lax.dynamic_update_slice(
              pool, jax.lax.dynamic_slice_in_dim(new, runs.tok[j], w)[None],
              (pages[j], runs.off[j]) + (0,) * (pool.ndim - 2))
          for pool, new in zip(pools, news))
    pools = jax.lax.fori_loop(0, runs.counts[c], _Piece, pools)
  return pools


# -- Pallas kernel ------------------------------------------------------------


def _RunWriteKernel(first_ref, counts_ref, page_ref, tok_ref, off_ref, k_new,
                    v_new, k_old, v_old, k_pool, v_pool, sems):
  """All operands in HBM; the pools are their own outputs. A pass a width
  over its live pieces starts every copy, then a pass a width waits for as
  many: a width has a semaphore of its own, and a wait takes its copy's size
  off it, whichever copy of that size it names."""
  del k_old, v_old
  widths = Widths(k_pool.shape[1], k_new.shape[0])
  both = ((k_new, k_pool), (v_new, v_pool))

  for c, w in enumerate(widths):
    def _Start(i, carry, c=c, w=w):
      j = first_ref[c] + i
      for new, pool in both:
        pltpu.make_async_copy(
            new.at[pl.ds(tok_ref[j], w)],
            pool.at[page_ref[j], pl.ds(off_ref[j], w)], sems.at[c]).start()
      return carry
    jax.lax.fori_loop(0, counts_ref[c], _Start, 0)

  for c, w in enumerate(widths):
    def _Wait(i, carry, c=c, w=w):
      for new, pool in both:
        pltpu.make_async_copy(new.at[pl.ds(0, w)],
                              pool.at[0, pl.ds(0, w)], sems.at[c]).wait()
      return carry
    jax.lax.fori_loop(0, counts_ref[c], _Wait, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _RunWriteCall(first, counts, pages, tok, off, k_new, v_new, k_pool,
                  v_pool, *, interpret: bool):
  """A `jit` of its own, as ops/ragged_block_attend._GroupedCall is: a
  kernel's body is traced anew at every `pallas_call`, and the layers of a
  stack (and every later program of the process) that call at the same
  shapes share this one trace. Inside the step program it is no call of its
  own. The scope here keeps the kernel's name `kv_write`."""
  hbm = pl.BlockSpec(memory_space=pl.ANY)
  with observe.Scope("kv_write"):
    return pl.pallas_call(
        _RunWriteKernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[hbm, hbm, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.SemaphoreType.DMA((first.shape[0],))]),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={7: 0, 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(first, counts, pages, tok, off, k_new, v_new, k_pool, v_pool)


# -- public entry ------------------------------------------------------------


def WriteRuns(k_pool, v_pool, k_new, v_new, pages, runs: Runs, *,
              lowering: str = "auto", interpret: bool | None = None):
  """Every piece's tokens of `k_new` / `v_new` `[T, N, H]` (the pools'
  dtype) into `pool[pages[j], off : off + w]` of `[NP, P, N, H]` pools.
  pages: [S] int32, the physical page of each piece of `runs` (the layer's
  own lookup, inside the pool; a dead piece's is never looked at). ->
  (k_pool, v_pool).

  lowering: 'auto' is the kernel on a TPU where `SupportedOnTpu`, else the
  XLA twin."""
  assert k_new.dtype == k_pool.dtype and v_new.dtype == v_pool.dtype, (
      k_new.dtype, k_pool.dtype)
  if lowering == "auto" and not SupportedOnTpu(k_pool.shape[-1]):
    lowering = "xla"
  if rba.Lowering(lowering) == "xla":
    return _XlaWriteRuns((k_pool, v_pool), (k_new, v_new), pages, runs)
  if interpret is None:
    interpret = jax.default_backend() != "tpu"
  return tuple(_RunWriteCall(
      runs.first, runs.counts, pages.astype(jnp.int32), runs.tok, runs.off,
      k_new, v_new, k_pool, v_pool, interpret=interpret))


def WriteRowRuns(pool, new, pages, runs: Runs):
  """`WriteRuns` for ONE pool whose token is a single row of its page,
  `[NP, P, W]` with `new` `[T, W]` (core/mla.py's latent rows): the same
  pieces through the XLA lowering on every backend. The chip tiles such a
  page over (P, W), a token's row is a sixteenth of a bf16 tile, and the
  kernel's copies move whole tiles; a `dynamic_update_slice` a piece writes
  in place under the loop. -> pool."""
  assert new.dtype == pool.dtype and pool.ndim == 3, (new.dtype, pool.shape)
  return _XlaWriteRuns((pool,), (new,), pages.astype(jnp.int32), runs)[0]
