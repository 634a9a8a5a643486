"""Ragged row descriptors: the shared contract of the serving step.

One compiled serving program carries every step shape (pure decode,
mixed prefill+decode, spec-verify). Its activations are PACKED on a
single token axis of static width T: a decode row contributes 1 token,
a prefill row a chunk of tokens, a spec-verify row its last committed
token plus k drafted tokens — and every layer sees the same flat [1, T, D]
activation with per-token routing metadata instead of a padded [B, C, D]
grid. `RaggedRows` is that metadata: a pytree of device arrays (no static
members, so one jit signature covers every admit/decode/spec/retire mix).

Two views of the same pack:

- the TOKEN view (`row_of`, `col_of`, `pos`, `valid`, all [T]): what
  attention needs — each token's K/V lands through its row's block table
  at global slot `pos` (a row's tokens as runs, ops/run_write.py) and the
  token attends over its own prefix. Padding tokens (`valid == False`)
  are in no run (a scatter a token, where one remains, sends them to the
  trash page) and produce garbage outputs the engine discards.
- the ROW view (`row_q_pos`, `row_len` [B]; `row_cols` [B, wmax]): what
  O(1)-state mixers need — ssm.GatedSSMLayer gathers its [B, wmax, D]
  per-row chunk via `row_cols`, runs the existing PagedStep recurrence
  (which already handles per-row lengths), and scatters results back to
  the token axis. wmax is implicit in `row_cols`' shape, so it stays a
  shape-static fact without being a python-level argument.

Invariants the builder (serving/scheduler.py BuildRaggedStep) maintains:

- row b's tokens occupy columns 0 .. row_len[b]-1 in kv order; token t
  has `pos[t] == row_q_pos[row_of[t]] + col_of[t]`.
- `row_cols[b, j]` is the token index of row b's j-th token for
  j < row_len[b] and an arbitrary VALID index (0) past it — gathered
  garbage is masked by the consumer via row_len, never read unmasked.
- rows with 0 tokens this step (live but out of budget, or empty slots)
  have row_len == 0 and row_q_pos == the sequence position (NOT 0 —
  q_pos == 0 is the SSM state-reset trigger); empty slots use
  row_q_pos == 1.
- `valid` padding tokens carry row_of/pos clipped into range so device
  gathers stay in bounds.

Tree speculation (PR 18) packs a token TREE per speculating row in DFS
preorder on the same axis: the root (last committed token) at column 0 and
draft node j at column j+1. Every node keeps its OWN kv slot (`pos` stays
`row_q_pos + col`, so the scatter has no sibling collisions), while the
LOGICAL position a node embeds/attends at is `row_q_pos + depth(node)` —
that is `pos_ids`, which only diverges from `pos` on tree rows. In-step
visibility is the ancestor chain: token t may attend step column c of its
row iff c is an ancestor-or-self, encoded as a 64-bit column bitmask split
into `anc_lo`/`anc_hi` (tree rows are capped at 64 packed columns; the
scheduler clamps width before depth under that cap). Chain rows ship the
sentinel -1/-1 (all columns visible), which keeps the attention mask
bitwise-identical to the pre-tree kernel. `col_parent` is the ROW-view
twin of the same structure: the parent COLUMN of each packed column
(-1 = no in-step parent, i.e. the row's incoming recurrent state), which
is what the SSM tree scan gathers its per-column initial state from.

The width a step runs (PR 50). `BuildRaggedRows` packs the rows from column 0
on with no gap, so a step's live tokens are the PREFIX [0, sum(row_len)) of
the packed axis and everything behind it is padding that nothing reads. The
pack is sized for a decode token (or verify window) a slot plus the widest row
it admits (the prefill budget's), so a step that carries no such row, most
steps of most flows, holds its tokens in the first W = T - wmax columns.
`LiveWidth` is that fact once a step (a step's plan carries it) and
`OverLiveRows` runs a row-wise block over W rows or over T by it, inside the
one step program.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class RaggedRows(NamedTuple):
  """Per-token + per-row routing for one packed ragged step.

  All members are arrays (a jit-transparent pytree). T = packed token
  width, B = engine slots, wmax = widest row this program admits.
  """
  row_of: jnp.ndarray    # [T] int32  slot index of each token
  col_of: jnp.ndarray    # [T] int32  token's column within its row
  pos: jnp.ndarray       # [T] int32  global kv slot the token writes/reads
  valid: jnp.ndarray     # [T] bool   False = padding token
  row_q_pos: jnp.ndarray  # [B] int32  row's first-token global position
  row_len: jnp.ndarray    # [B] int32  tokens the row carries this step
  row_cols: jnp.ndarray   # [B, wmax] int32  token-axis gather indices
  pos_ids: jnp.ndarray   # [T] int32  logical position (rotary); == pos on chains
  anc_lo: jnp.ndarray    # [T] int32  in-step ancestor bitmask, columns 0..31
  anc_hi: jnp.ndarray    # [T] int32  in-step ancestor bitmask, columns 32..63
  col_parent: jnp.ndarray  # [B, wmax] int32  parent column (-1 = row state)


MAX_TREE_COLS = 64  # anc_lo/anc_hi bit budget; scheduler clamps width first.


class TokenView(NamedTuple):
  """Where each packed token's K/V lands and what it may attend, as every
  attention layer of a step derives it from RaggedRows and the shape of the
  block tables ([b, t_pages], pages of page_size slots). All [T] int32; a
  layer adds what is its own: the table's lookup and its pool's page base."""
  row: jnp.ndarray      # the token's block-table row, inside the table
  logical: jnp.ndarray  # the logical page of its slot, inside the table
  off: jnp.ndarray      # its offset in that page (a padding token's: its
  #                       column's, so padding writes spread over the trash
  #                       page)
  q_end: jnp.ndarray    # one past its highest attendable slot; 0 = padding
  q_start: jnp.ndarray  # its row's first slot of this step (tree masks)


def BuildTokenView(rows: RaggedRows, b: int, t_pages: int,
                   page_size: int) -> TokenView:
  """The device-side token view of `rows` (jnp arrays) against block tables
  [b, t_pages]. In `jax.lax` over constants of numpy, as every list a step's
  rows alone decide: the step program is traced in every process, and a
  `jnp` call on a tracer is a trace of its own (PERF.md section 6, PRs 49,
  51)."""
  lax, i32 = jax.lax, np.int32
  t = rows.pos.shape[0]
  pos = lax.convert_element_type(rows.pos, i32)
  row = lax.clamp(i32(0), lax.convert_element_type(rows.row_of, i32),
                  i32(b - 1))
  valid = lax.convert_element_type(rows.valid, np.bool_)
  q_start = lax.gather(
      lax.convert_element_type(rows.row_q_pos, i32), lax.reshape(row, (t, 1)),
      lax.GatherDimensionNumbers(offset_dims=(), collapsed_slice_dims=(0,),
                                 start_index_map=(0,)),
      slice_sizes=(1,), mode="clip")
  return TokenView(
      row=row,
      logical=lax.clamp(i32(0), lax.div(pos, i32(page_size)),
                        i32(t_pages - 1)),
      off=lax.select(valid, lax.rem(pos, i32(page_size)),
                     np.arange(t, dtype=i32) % page_size),
      q_end=lax.select(valid, lax.add(pos, i32(1)), np.zeros((t,), i32)),
      q_start=q_start)


class LiveWidth(NamedTuple):
  """Whether a step's live tokens fit the pack's decode width."""
  fits: jnp.ndarray   # [] bool: sum(row_len) <= rows
  rows: int           # W, static: the pack less the widest row it admits


def DecodeWidth(t: int, wmax: int) -> int:
  """W of a pack of t columns whose widest row is wmax: what is left for the
  slots' decode tokens (or verify windows) when that row is out; 0: the pack
  has no narrower width."""
  return max(t - wmax, 0)


def BuildLiveWidth(rows: RaggedRows) -> LiveWidth | None:
  """The step's `LiveWidth`, from the shapes the pack was sized with and the
  row lengths on the device; None where the pack has no narrower width (a
  caller that sized `row_cols` as wide as the pack)."""
  w = DecodeWidth(rows.pos.shape[0], rows.row_cols.shape[1])
  if not w:
    return None
  live = jax.lax.reduce(jax.lax.convert_element_type(rows.row_len, np.int32),
                        np.int32(0), jax.lax.add, (0,))
  return LiveWidth(fits=jax.lax.le(live, np.int32(w)), rows=w)


def OverLiveRows(fn, plan, *xs, axis: int = 1):
  """fn(*xs) for a ROW-WISE fn (row i of every output depends on row i of
  every operand alone) over the rows a step holds: where the step's plan
  says its live tokens fit W rows (`plan.narrow`), `fn` runs over the first W
  rows of each operand and the outputs are laid back at full width with zeros
  behind (rows nothing reads); else over all of them. One `lax.cond`, both
  branches the same `fn`; what `fn` closes over (weights) enters both. xs:
  arrays whose axis `axis` is the packed one; fn returns a pytree of such
  arrays. No plan, or a plan without the width (every caller that is not the
  serving step's stack): `fn(*xs)` and nothing else."""
  narrow = getattr(plan, "narrow", None)
  if narrow is None:
    return fn(*xs)
  t, w = xs[0].shape[axis], narrow.rows

  def _Pad(y):
    config = [(0, 0, 0)] * y.ndim
    config[axis] = (0, t - w, 0)
    return jax.lax.pad(y, np.zeros((), y.dtype), config)

  def _Narrow(*xs):
    out = fn(*(jax.lax.slice_in_dim(x, 0, w, axis=axis) for x in xs))
    return jax.tree_util.tree_map(_Pad, out)

  return jax.lax.cond(narrow.fits, _Narrow, fn, *xs)


def TreeDepths(parents) -> np.ndarray:
  """Draft-node depths from DFS parent pointers.

  parents: [R] ints, parent DRAFT index of each draft node (-1 = child of
  the root/committed token). DFS preorder guarantees parents[j] < j.
  Returns [R] depths, root children at depth 1.
  """
  parents = np.asarray(parents, np.int32)
  depth = np.zeros(parents.shape, np.int32)
  for j, p in enumerate(parents):
    assert p < j, (j, p)
    depth[j] = 1 if p < 0 else depth[p] + 1
  return depth


def TreeAncestorMasks(parents) -> tuple[np.ndarray, np.ndarray]:
  """Per-COLUMN ancestor bitmasks (lo, hi) from DFS parent pointers.

  Column 0 is the root; draft j lives at column j+1. Bit c of column
  mask[j] is set iff step column c is an ancestor-or-self of column j.
  Returns two [R+1] int32 arrays (bits 0..31 / 32..63).
  """
  parents = np.asarray(parents, np.int32)
  r = parents.shape[0]
  assert r + 1 <= MAX_TREE_COLS, (r, MAX_TREE_COLS)
  masks = np.zeros((r + 1,), np.int64)
  masks[0] = 1
  for j, p in enumerate(parents):
    col = j + 1
    masks[col] = masks[p + 1] | (np.int64(1) << col)
  lo = (masks & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
  hi = ((masks >> 32) & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
  return lo, hi


def BuildRaggedRows(row_lens, row_q_pos, t: int, wmax: int,
                    row_parents=None) -> RaggedRows:
  """Host-side builder: per-row (q_pos, len) -> a packed RaggedRows.

  row_lens/row_q_pos: [B] ints. Rows are packed in slot order; the caller
  guarantees sum(row_lens) <= t and max(row_lens) <= wmax. Returns numpy
  arrays (the engine ships them device-side each step).

  row_parents: optional {slot: [row_len-1] parent pointers} for TREE rows
  (draft j's parent draft index, -1 = root). Rows absent from the dict are
  chains: pos_ids == pos, anc masks -1 (all visible), col_parent c-1 —
  all bitwise-neutral against the pre-tree program.
  """
  row_lens = np.asarray(row_lens, np.int32)
  row_q_pos = np.asarray(row_q_pos, np.int32)
  b = row_lens.shape[0]
  assert int(row_lens.sum()) <= t, (row_lens, t)
  assert int(row_lens.max(initial=0)) <= wmax, (row_lens, wmax)
  row_of = np.zeros((t,), np.int32)
  col_of = np.zeros((t,), np.int32)
  pos = np.zeros((t,), np.int32)
  valid = np.zeros((t,), bool)
  row_cols = np.zeros((b, wmax), np.int32)
  pos_ids = np.zeros((t,), np.int32)
  anc_lo = np.full((t,), -1, np.int32)
  anc_hi = np.full((t,), -1, np.int32)
  col_parent = np.tile(np.arange(-1, wmax - 1, dtype=np.int32), (b, 1))
  cursor = 0
  for i in range(b):
    n = int(row_lens[i])
    if n == 0:
      continue
    sl = slice(cursor, cursor + n)
    row_of[sl] = i
    col_of[sl] = np.arange(n)
    pos[sl] = row_q_pos[i] + np.arange(n)
    valid[sl] = True
    row_cols[i, :n] = np.arange(cursor, cursor + n)
    parents = None if row_parents is None else row_parents.get(i)
    if parents is not None:
      parents = np.asarray(parents, np.int32)
      assert parents.shape == (n - 1,), (parents.shape, n)
      depths = np.concatenate([[0], TreeDepths(parents)]).astype(np.int32)
      lo, hi = TreeAncestorMasks(parents)
      pos_ids[sl] = row_q_pos[i] + depths
      anc_lo[sl] = lo
      anc_hi[sl] = hi
      col_parent[i, 1:n] = parents + 1
    else:
      pos_ids[sl] = pos[sl]
    cursor += n
  return RaggedRows(row_of=row_of, col_of=col_of, pos=pos, valid=valid,
                    row_q_pos=row_q_pos, row_len=row_lens,
                    row_cols=row_cols, pos_ids=pos_ids,
                    anc_lo=anc_lo, anc_hi=anc_hi, col_parent=col_parent)


# -- what a step cost a stack's mixers, in the serving engine's counters -------


class StepGeometry(NamedTuple):
  """What every step of a serving engine is cut to (all static)."""
  page_size: int
  kv_cache_dtype: str | None  # the engine's override (None: each layer's own)
  max_batch: int              # B, a step's rows
  tokens: int                 # T, its packed width
  table_pages: int            # its block tables' width


class StepCount(NamedTuple):
  """Names of observe.schema.ENGINE_COUNTER_KEYS and what a step adds to each:
  the engine calls `count` once a step; all else is bound beforehand."""
  names: tuple[str, ...]
  count: Callable            # (row_q_pos, row_len: the host's numpy [B]
  #                            int64) -> a tuple of ints, one a name
  in_record: bool = False    # a step's trace record carries the names


def BlockFillCount(bq: int, laid: int = 1, own: int = 1) -> StepCount:
  """The block fill of an attention mixer whose kernel cuts a row's queries
  into blocks of `bq`: a token lays `laid` queries on the packed axis (a KV
  head's group, padded to whole tiles), `own` of them its own (the group)."""
  from lingvo_tpu.ops import ragged_block_attend
  rungs = ragged_block_attend.BlockRungs(bq, laid)

  def _Count(row_q_pos, row_len):
    # a row's queries fill whole blocks and one that holds the rest, whose
    # products run the rows of M that many queries need (BlockRungs)
    whole, rest = np.divmod(row_len * laid, bq)
    return (int(np.sum(whole + (rest > 0))), int(np.sum(row_len)) * own,
            int(np.sum(whole * bq
                       + ragged_block_attend.BlockRows(rest, rungs))))

  return StepCount(("attend_query_blocks", "attend_block_queries",
                    "attend_block_rows"), _Count)


_RUN_WRITES = ("kv_write_runs", "kv_write_tokens")


def RunWriteCount(page_size: int) -> StepCount:
  """A mixer that writes its pages by the step's runs: ops/run_write.py's."""
  from lingvo_tpu.ops import run_write
  return StepCount(_RUN_WRITES, lambda row_q_pos, row_len: run_write.RunCounts(
      row_q_pos, row_len, page_size))


def StackStepCounts(stack, cached_states, g: StepGeometry,
                    page_writes: bool = False) -> list[StepCount]:
  """A stack's `StepCounts(cached_states, geometry)`: the one list a serving
  engine adds up a step (docs/serving_engine.md). A mixer contributes through
  `StepCounts(geometry, layers)`, asked once a counting method with the layers
  that share it; a name is fed by the first mixer that offers it. Beside
  them, what the layers share (`page_writes`: the stack's own condition)."""
  from lingvo_tpu.ops import diff_attend, ragged_block_attend, run_write
  mixers = stack.MixerLayers()
  kinds = {}   # counting method -> [its first mixer, layers that share it]
  for m, reps in mixers:
    if hasattr(m, "StepCounts"):
      kinds.setdefault(type(m).StepCounts, [m, 0])[1] += reps
  counts, fed = [], set()
  for m, layers in kinds.values():
    for c in m.StepCounts(g, layers):
      if fed.isdisjoint(c.names):
        counts.append(c)
        fed.update(c.names)
  # the slot state a live row reads and writes, over every mixer that keeps
  # one; in the record beside a tail's rows (ShortConvLayer's stack)
  slot_bytes = 2 * sum(reps * m.StateBytesPerSlot() for m, reps in mixers
                       if hasattr(m, "StateBytesPerSlot"))
  if slot_bytes:
    counts.append(StepCount(("slot_state_bytes",), lambda row_q_pos, row_len: (
        slot_bytes * int((row_len > 0).sum()),), "conv_tail_rows" in fed))
  # the attend kernels' plans, one a DISTINCT key (not a layer): their pages,
  # unmasked pages and programs, in the record where a kernel reads `clear`;
  # and the pairs their lists have room for (the grids before PR 46)
  keys = {k for k in stack.RaggedPlanKeys(cached_states) if k.kernel}
  if keys:
    grid = sum(ragged_block_attend.GridPairs(k, g.max_batch, g.tokens,
                                             g.table_pages) for k in keys)
    pairs = lambda row_q_pos, row_len: tuple(int(n) for n in np.sum([
        ragged_block_attend.PairCounts(k, row_q_pos, row_len, g.table_pages)
        for k in keys], axis=0))
    counts += [
        StepCount(("attend_live_pairs", "attend_clear_pairs",
                   "attend_programs"), pairs, any(k.clear for k in keys)),
        StepCount(("attend_grid_pairs",), lambda row_q_pos, row_len: (grid,))]
  if page_writes:
    # ops/diff_attend.WritePages: a run is a (row, page) pair, under the
    # bound its grid ran before PR 56; one count where a layer writes by runs
    bound = diff_attend.PageWrites(g.max_batch, g.tokens, g.page_size)
    names = ("kv_page_writes", "kv_page_write_bound") + (
        _RUN_WRITES if _RUN_WRITES[0] in fed else ())

    def _Writes(row_q_pos, row_len):
      runs, tokens = run_write.RunCounts(row_q_pos, row_len, g.page_size)
      return (runs, bound, runs, tokens)[:len(names)]

    counts = [c for c in counts if c.names != _RUN_WRITES] + [
        StepCount(names, _Writes)]
  return counts
