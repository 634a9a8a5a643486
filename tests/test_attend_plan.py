"""What a step's rows alone decide is built once a step (docs/ragged_step.md,
"The step's plan").

- ops: `RaggedAttend`, `DiffAttend` and `WritePages` give BITWISE the output
  with the step's plan handed in (`core/attention.BuildRaggedPlan` over the
  step's rows) that they give when they build their own, over window 0 / a
  window, `G == 1` / grouped, chain rows / tree rows, in packs with padding
  tokens before, between and after the rows (Pallas, interpret mode),
- programs: the step of each registered serve family at the depth its cell
  serves (dense 24, SmallThinker 8, Phi-4-flash 32; tiny widths), with the
  kernels' lowering forced: its jaxpr holds `_BuildQueryBlocks`' ops once
  for every distinct plan and none inside a scan's body, every scan's body
  holds the kernels, `Stats()` counts what the stack declares, and the
  program's logits are the twins' program's within rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu import model_registry
from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.models.lm.params import phi4flash
from lingvo_tpu.ops import diff_attend
from lingvo_tpu.ops import ragged_block_attend as rba
from lingvo_tpu.ops import run_write
from lingvo_tpu.serving import engine as engine_lib

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

PAGE, T_PAGES, T, WMAX = 16, 6, 48, 32

# name -> ([tokens a slot], [first position a slot], {slot: tree parents})
PACKS = {
    # a decode row, an empty slot, a chunk over two pages, a short chunk
    "chain": ([1, 0, 30, 5], [37, 9, 10, 0], None),
    # the same pack with a 12-node tree where the chunk was
    "tree": ([1, 0, 13, 5], [37, 9, 10, 0],
             {2: [-1, 0, 0, 2, -1, 4, 4, 6, -1, 8, 9, 9]}),
}


def _Rows(pack):
  """The pack's RaggedRows with padding tokens moved in before its first row
  and between its rows (the scheduler packs rows back to back; the ops take
  padding anywhere)."""
  lens, q_pos, parents = PACKS[pack] if isinstance(pack, str) else pack
  rows = ragged_lib.BuildRaggedRows(np.array(lens), np.array(q_pos), T - 5,
                                    WMAX, row_parents=parents)
  gaps = np.cumsum([2] + [1 if n else 0 for n in lens])[:-1]     # per slot
  shift = gaps[rows.row_of] * rows.valid
  t_axis = {}
  for name in ("row_of", "col_of", "pos", "valid", "pos_ids", "anc_lo",
               "anc_hi"):
    src = getattr(rows, name)
    out = np.full((T,), -1 if name.startswith("anc") else 0, src.dtype)
    live = np.flatnonzero(rows.valid)
    out[live + shift[live]] = src[live]
    t_axis[name] = out
  cols = np.where(np.arange(WMAX)[None] < np.asarray(lens)[:, None],
                  rows.row_cols + gaps[:, None], 0)
  rows = rows._replace(row_cols=cols.astype(np.int32), **t_axis)
  return ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))


def _Pools(nk, h, seed=0):
  rng = np.random.RandomState(seed)
  b = len(PACKS["chain"][0])
  np_total = b * T_PAGES + 1
  f32 = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
  tables = rng.permutation(np_total - 1).reshape(b, T_PAGES).astype(np.int32)
  return (f32(np_total, PAGE, nk, h), f32(np_total, PAGE, nk, h),
          jnp.asarray(tables))


def _Same(got, want):
  np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("pack", list(PACKS))
@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("heads", [(2, 2, 8), (4, 2, 128)],
                         ids=["one_query_a_token", "grouped"])
def test_ragged_attend_with_the_steps_plan_is_bitwise_the_call_without(
    heads, window, pack):
  n, nk, h = heads
  rows = _Rows(pack)
  kp, vp, tables = _Pools(nk, h)
  q = jnp.asarray(np.random.RandomState(1).randn(T, n, h) * h ** -0.5,
                  jnp.float32)
  key = rba.AttendPlanKey(n, nk, h, PAGE, q.dtype, kp.dtype, window=window,
                          lowering="pallas")
  assert key.kernel and key.lanes == (8 if n != nk else 1)
  plan = attention_lib.BuildRaggedPlan([key, key], rows, *tables.shape)
  assert list(plan.blocks) == [key] and plan.writes is None
  tok = plan.tokens
  assert int(jnp.sum(tok.q_end > 0)) == sum(PACKS[pack][0])
  assert int(tok.q_end[0]) == 0 and int(tok.q_end[3]) == 0   # the padding

  def _Call(**kw):
    return rba.RaggedAttend(
        q, kp, vp, tables, tok.row, tok.q_end, page_size=PAGE,
        q_start=tok.q_start, anc_lo=rows.anc_lo, anc_hi=rows.anc_hi,
        window=window, lowering="pallas", interpret=True, **kw)

  out = _Call(plan=plan.blocks)
  _Same(out, _Call())
  twin = rba.RaggedAttend(
      q, kp, vp, tables, tok.row, tok.q_end, page_size=PAGE,
      q_start=tok.q_start, anc_lo=rows.anc_lo, anc_hi=rows.anc_hi,
      window=window, lowering="xla")
  np.testing.assert_allclose(np.asarray(out), np.asarray(twin), atol=5e-6)
  _Same(out[np.asarray(tok.q_end) == 0], 0.0)


@pytest.mark.parametrize("window", [0, 20])
def test_diff_attend_with_the_steps_plan_is_bitwise_the_call_without(window):
  nq, nk, h = 8, 4, 8
  rows = _Rows("chain")
  kp, vp, tables = _Pools(nk, h)
  q = jnp.asarray(np.random.RandomState(1).randn(T, nq, h), jnp.float32)
  key = diff_attend.DiffPlanKey(nq, nk, h, PAGE, q.dtype, kp.dtype,
                                window=window, lowering="pallas")
  assert key.kernel and not key.tree and key.bq == 512
  plan = attention_lib.BuildRaggedPlan([key], rows, *tables.shape)

  def _Call(**kw):
    return diff_attend.DiffAttend(
        q, kp, vp, tables, plan.tokens.row, plan.tokens.q_end, 0.3,
        page_size=PAGE, window=window, lowering="pallas", interpret=True,
        **kw)

  out = _Call(plan=plan.blocks)
  _Same(out, _Call())
  twin = diff_attend.DiffAttend(
      q, kp, vp, tables, plan.tokens.row, plan.tokens.q_end, 0.3,
      page_size=PAGE, window=window, lowering="xla")
  np.testing.assert_allclose(np.asarray(out), np.asarray(twin), atol=2e-5)


# name -> ([tokens a slot], [first position a slot]) of a step the whole-page
# write is held on beside PACKS' two, over tables of T_PAGES pages of PAGE
WRITE_PACKS = {
    # 64 one-token rows, every one a page of its own: the decode-only step
    "decode_only_64": ([1] * 64, [(7 * i + 3) % (T_PAGES * PAGE - 1)
                                  for i in range(64)]),
    # rows that start mid-page at an odd packed offset (behind a one-token
    # row and the padding `_Rows` moves in) and cross one page boundary, two
    "one_boundary": ([1, 12, 0, 1], [37, 9, 3, 80]),
    "two_boundaries": ([3, 0, 27, 1], [2, 0, 13, 95]),
    # one live pair where the list has room for eleven: the traced grid
    "far_under_the_bound": ([0, 0, 1, 0], [4, 4, 21, 4]),
    "empty": ([0, 0, 0, 0], [1, 1, 1, 1]),
}


def _WriteRows(pack):
  """(rows, packed tokens) of `pack`: PACKS' through `_Rows` (padding moved
  in between the rows), WRITE_PACKS' likewise where they fit its width."""
  if pack in PACKS:
    return _Rows(pack), T
  lens, q_pos = WRITE_PACKS[pack]
  if len(lens) == len(PACKS["chain"][0]):
    return _Rows((lens, q_pos, None)), T
  t = len(lens) + 16
  rows = ragged_lib.BuildRaggedRows(np.array(lens), np.array(q_pos), t, 17)
  return ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows)), t


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("pack", list(PACKS) + list(WRITE_PACKS))
def test_page_writes_with_the_steps_plan_are_bitwise_the_call_without(
    pack, dtype):
  """`WritePages`' kernel (interpret mode) with the step's plan and with its
  own against the XLA scatter, BITWISE, over pools whose every page the step
  does not write, the trash page among them, was poisoned beforehand (NaNs:
  arithmetic on one would show) and is compared bit for bit afterwards."""
  nk, h = 4, 8
  dtype = jnp.float32 if dtype == "f32" else jnp.bfloat16
  rows, t = _WriteRows(pack)
  lens, q_pos = (np.asarray(x) for x in (rows.row_len, rows.row_q_pos))
  b = lens.shape[0]
  rng = np.random.RandomState(2)
  np_total = b * T_PAGES + 1
  tables = rng.permutation(np_total - 1).reshape(b, T_PAGES).astype(np.int32)
  written = sorted({int(tables[r, lp]) for r in range(b) if lens[r]
                    for lp in range(q_pos[r] // PAGE,
                                    (q_pos[r] + lens[r] - 1) // PAGE + 1)})
  poisoned = np.setdiff1d(np.arange(np_total), written)
  assert np_total - 1 in poisoned

  def _Pool():
    pool = rng.randn(np_total, PAGE, nk, h).astype(np.float32)
    pool[poisoned] = np.nan
    return jnp.asarray(pool, dtype)

  kp, vp = _Pool(), _Pool()
  kn, vn = (jnp.asarray(rng.randn(t, nk, h), dtype) for _ in range(2))
  tables = jnp.asarray(tables)
  key = diff_attend.DiffPlanKey(8, nk, h, PAGE, kn.dtype, kp.dtype,
                                lowering="pallas")
  plan = attention_lib.BuildRaggedPlan([key], rows, *tables.shape,
                                       page_writes=True)
  bound = diff_attend.PageWrites(b, t, PAGE)
  assert plan.writes.tok0.shape == (bound,)
  # the grid's traced length is the pairs the step holds, which the host
  # counts from its own rows
  assert int(plan.writes.pairs) == int(plan.writes.live.sum()) == len(
      written) == run_write.RunCounts(q_pos, lens, PAGE)[0]
  if pack == "far_under_the_bound":
    assert (len(written), bound) == (1, 11)
  call = lambda **kw: diff_attend.WritePages(
      kp, vp, kn, vn, tables, rows, lowering="pallas", interpret=True, **kw)
  got, own = call(plan=plan.writes), call()
  scatter = diff_attend.WritePages(kp, vp, kn, vn, tables, rows,
                                   lowering="xla")
  bits = lambda x: np.asarray(x).view(
      np.uint32 if x.dtype == jnp.float32 else np.uint16)
  for a, b_, c, old in zip(got, own, scatter, (kp, vp)):
    _Same(bits(a), bits(b_))
    _Same(bits(a[:-1]), bits(c[:-1]))   # the trash page: the scatter's padding
    _Same(bits(a)[poisoned], bits(old)[poisoned])
    assert not np.isnan(np.asarray(a.astype(jnp.float32))[written]).any()


def _LookupDescriptors(row_of, ends, starts, lo, hi, *, bq, nb, page_size,
                       t_pages, window):
  """The descriptors by a lookup a query and value, as `_BuildQueryBlocks`
  made them before it took a slice a block (numpy; the reference)."""
  t = row_of.shape[0]
  idx = np.arange(t)
  valid = ends > 0
  prev_valid = np.concatenate([[False], valid[:-1]])
  prev_row = np.concatenate([row_of[:1], row_of[:-1]])
  run_start = valid & (~prev_valid | (row_of != prev_row))
  run_first = np.maximum.accumulate(np.where(run_start, idx, 0))
  csum = np.cumsum(valid & ((idx - run_first) % bq == 0))
  blk, n_live = csum - 1, csum[-1]
  k = np.arange(nb)
  src = np.minimum(k, max(n_live - 1, 0))
  first = np.minimum(np.sum(csum[None, :] <= src[:, None], axis=1), t - 1)
  tok = first[:, None] + np.arange(bq)[None, :]
  in_range = tok < t
  tok = np.minimum(tok, t - 1)
  member = in_range & valid[tok] & (blk[tok] == src[:, None])
  blk_ends = np.where(member, ends[tok], 0)
  last = np.clip((blk_ends.max(axis=1) + page_size - 1) // page_size - 1, 0,
                 t_pages - 1)
  page0 = np.zeros_like(last)
  if window:
    low = np.where(member, blk_ends, np.iinfo(np.int32).max).min(axis=1)
    page0 = np.minimum(np.maximum(low - window, 0) // page_size, last)
  cols = np.stack([blk_ends, starts[tok], lo[tok], hi[tok]], axis=-1)
  return dict(row=row_of[first], last=last, page0=page0,
              n=np.where(k < n_live, member.sum(axis=1), 0), first=first,
              cols=cols, col0=tuple(cols[:, 0].T))


@pytest.mark.parametrize("pack", list(PACKS))
@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("lanes,bq", [(1, 16), (8, 64)])
def test_a_slice_a_block_gives_the_descriptors_of_a_lookup_a_query(
    lanes, bq, window, pack):
  """Blocks of one token's lanes, of a whole row and of part of one (the
  30-token chunk at 8 lanes is 240 queries: three blocks of 64 and one of
  48), the last block's slice running past the packed axis."""
  rows = _Rows(pack)
  b = len(PACKS[pack][0])
  tok = ragged_lib.BuildTokenView(rows, b, T_PAGES, PAGE)
  key = rba.PlanKey(PAGE, window, bq, lanes, tree=True, kernel=True)
  got = rba.BuildAttendPlan(key, tok.row, tok.q_end, tok.q_start, rows.anc_lo,
                            rows.anc_hi, b=b, t_pages=T_PAGES)
  laid = [np.repeat(np.asarray(x, np.int32), lanes) for x in (
      tok.row, tok.q_end, tok.q_start, rows.anc_lo, rows.anc_hi)]
  want = _LookupDescriptors(
      *laid, bq=bq, nb=rba.NumQueryBlocks(b, T * lanes, bq), page_size=PAGE,
      t_pages=T_PAGES, window=window)
  for name, value in want.items():
    _Same(np.stack(getattr(got, name)) if name == "col0"
          else getattr(got, name), np.stack(value) if name == "col0" else value)
  assert int(np.max(got.n)) == min(bq, 30 * lanes if pack == "chain"
                                   else 13 * lanes)


# -- the list of live pairs ----------------------------------------------------


def _PairPack(kind, seed):
  """(tokens a slot, first position a slot, block tables) of a seeded random
  pack of `kind` over 4 slots, T packed tokens and tables of T_PAGES pages."""
  rng = np.random.RandomState(seed)
  slots = T_PAGES * PAGE
  b = 4
  tables = rng.permutation(b * T_PAGES).reshape(b, T_PAGES).astype(np.int32)
  decode = lambda: int(rng.randint(0, slots - 1))
  if kind == "decode_only":
    lens, q_pos = [1, 1, 0, 1], [decode(), decode(), 7, decode()]
  elif kind == "chunk_and_decode":
    n = int(rng.randint(2, 14))
    lens, q_pos = [1, n, 1, 0], [decode(), int(rng.randint(0, slots - n)),
                                  decode(), 3]
  elif kind == "row_cut_into_blocks":
    n = int(rng.randint(18, 33))      # over two blocks at either key below
    lens, q_pos = [1, 0, n, 1], [decode(), 5, int(rng.randint(0, slots - n)),
                                  decode()]
  elif kind == "shared_prefix":
    lens, q_pos = [1, 9, 1, 1], [40 + int(rng.randint(0, 50)), 33, 70, 64]
    tables[1:, :2] = tables[0, :2]    # the rows' first two pages are one
  elif kind == "full_pool":
    # every page of every table is live: the list holds what the grid held
    lens, q_pos = [12] * 4, [slots - 12] * 4
  else:
    assert kind == "empty", kind
    lens, q_pos = [0] * 4, [1] * 4
  return lens, q_pos, tables


PAIR_KINDS = ["decode_only", "chunk_and_decode", "row_cut_into_blocks",
              "shared_prefix", "full_pool", "empty"]


def _PairRows(kind, seed):
  lens, q_pos, tables = _PairPack(kind, seed)
  rows = ragged_lib.BuildRaggedRows(np.array(lens), np.array(q_pos), T, WMAX)
  return ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows)), tables


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("kind", PAIR_KINDS)
@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("lanes,bq", [(1, 16), (8, 64)])
def test_the_list_is_the_old_grids_live_programs_in_order(
    lanes, bq, window, kind, seed):
  """`blk`, `page`, `pairs` against the enumeration of the `(NB, grid_pages)`
  grid's programs that held work (`n[i] > 0`, `page0[i] <= page <= last[i]`;
  blocks in packed order, a block's pages ascending), and the host's count
  (`LivePairs`) against the plan's."""
  rows, tables = _PairRows(kind, seed)
  b = tables.shape[0]
  tok = ragged_lib.BuildTokenView(rows, b, T_PAGES, PAGE)
  key = rba.PlanKey(PAGE, window, bq, lanes, tree=False, kernel=True)
  plan = rba.BuildAttendPlan(key, tok.row, tok.q_end, b=b, t_pages=T_PAGES)
  nb = rba.NumQueryBlocks(b, T * lanes, bq)
  grid_pages = rba.WindowPages(window, bq, PAGE, T_PAGES)
  n, page0, last = (np.asarray(x) for x in (plan.n, plan.page0, plan.last))
  want = [(i, page0[i] + j) for i in range(nb) for j in range(grid_pages)
          if n[i] > 0 and page0[i] + j <= last[i]]
  pairs = int(plan.pairs)
  assert plan.blk.shape == plan.page.shape == (nb * grid_pages,)
  assert rba.GridPairs(key, b, T, T_PAGES) == nb * grid_pages
  assert pairs == len(want)
  got = list(zip(np.asarray(plan.blk).tolist(), np.asarray(plan.page).tolist()))
  assert got[:pairs] == want
  # past the live pairs the list repeats the last one: a name the grid never
  # reaches is still a live page's
  assert set(got[pairs:]) <= {want[-1] if want else (0, 0)}
  assert (pairs == 0) == (kind == "empty")
  if kind == "full_pool" and not window:
    assert pairs == int(np.sum(n > 0)) * T_PAGES
  assert rba.LivePairs(key, rows.row_q_pos, rows.row_len, T_PAGES) == pairs


@pytest.mark.parametrize("pack", list(PACKS))
@pytest.mark.parametrize("lanes,bq", [(1, 16), (8, 64), (16, 128)])
def test_clear_is_the_pages_under_every_members_reach(lanes, bq, pack):
  """`AttendPlan.clear` of a key that asks for it, against a numpy reading of
  the same rows a query at a time: a chain's query reaches its horizon, a
  tree's the slot its window starts at; a block clears the whole pages under
  its narrowest member. Every other field is the plan's without it, and a
  key that does not ask gets none."""
  rows = _Rows(pack)
  b = len(PACKS[pack][0])
  tok = ragged_lib.BuildTokenView(rows, b, T_PAGES, PAGE)
  args = (tok.row, tok.q_end, tok.q_start, rows.anc_lo, rows.anc_hi)
  key = rba.PlanKey(PAGE, 0, bq, lanes, tree=True, kernel=True, clear=True)
  got = rba.BuildAttendPlan(key, *args, b=b, t_pages=T_PAGES)
  plain = rba.BuildAttendPlan(key._replace(clear=False), *args, b=b,
                              t_pages=T_PAGES)
  assert plain.clear is None
  for a, b_ in zip(jax.tree.leaves(got._replace(clear=None)),
                   jax.tree.leaves(plain)):
    _Same(a, b_)
  ends, starts, lo, hi = (np.repeat(np.asarray(x, np.int64), lanes)
                          for x in args[1:])
  chain = (lo == -1) & (hi == -1)
  assert chain.all() == (pack == "chain")
  reach = np.where(chain, ends, np.where(lo & 1, np.minimum(ends, starts + 1),
                                         0))
  n, first = np.asarray(got.n), np.asarray(got.first)
  live = int(np.sum(n > 0))
  want = [reach[first[i]:first[i] + n[i]].min() // PAGE for i in range(live)]
  assert np.asarray(got.clear)[:live].tolist() == want
  assert max(want) > 0
  # without a window the clear pages start where the block's pages do
  assert got.clear_lo is got.page0 and plain.clear_lo is None


# the two packs, and one whose chunk sits deep enough in its row for a window
# to leave pages behind it
WINDOW_PACKS = {**PACKS, "deep": ([1, 0, 24, 3], [37, 9, 60, 5], None)}


def _Keep(ends, starts, lo, hi, window):
  """[queries, slots] what `_AncestorOk`, the horizon and the window let a
  query see, a (query, slot) at a time in numpy."""
  slot = np.arange(T_PAGES * PAGE, dtype=np.int64)[None]
  ends, starts, lo, hi = (x[:, None] for x in (ends, starts, lo, hi))
  cc = np.clip(slot - starts, 0, 63)
  word = np.where(cc < 32, lo, hi).astype(np.int64) & 0xFFFFFFFF
  ok = (word >> np.where(cc < 32, cc, cc - 32)) & 1 == 1
  keep = (slot < ends) & ok
  if window:
    keep &= slot >= ends - window
  return keep


@pytest.mark.parametrize("pack", list(WINDOW_PACKS))
@pytest.mark.parametrize("window", [0, 24, 37])
@pytest.mark.parametrize("lanes,bq", [(1, 16), (8, 64), (16, 128)])
def test_the_clear_range_is_pages_every_member_sees_whole(lanes, bq, window,
                                                         pack):
  """`clear_lo <= page < clear` of a key with a window (and without) against
  a brute-force reading of every (query, slot): at a page of the range `keep`
  is all ones for every query of the block; for chain rows the range is
  EVERY such page. Blocks start and end mid-page, 37 is no page multiple.
  The other fields are the plan's of the key that does not ask."""
  rows = _Rows(WINDOW_PACKS[pack])
  b = len(WINDOW_PACKS[pack][0])
  tok = ragged_lib.BuildTokenView(rows, b, T_PAGES, PAGE)
  args = (tok.row, tok.q_end, tok.q_start, rows.anc_lo, rows.anc_hi)
  key = rba.PlanKey(PAGE, window, bq, lanes, tree=True, kernel=True,
                    clear=True)
  got = rba.BuildAttendPlan(key, *args, b=b, t_pages=T_PAGES)
  plain = rba.BuildAttendPlan(key._replace(clear=False), *args, b=b,
                              t_pages=T_PAGES)
  for a, b_ in zip(jax.tree.leaves(got._replace(clear=None, clear_lo=None)),
                   jax.tree.leaves(plain)):
    _Same(a, b_)
  keep = _Keep(*(np.repeat(np.asarray(x, np.int64), lanes)
                 for x in args[1:]), window)
  whole = keep.reshape(keep.shape[0], T_PAGES, PAGE).all(-1)  # [queries, pages]
  n, first, lo, hi, page0, last = (np.asarray(x) for x in (
      got.n, got.first, got.clear_lo, got.clear, got.page0, got.last))
  found = 0
  for i in range(int(np.sum(n > 0))):
    seen = whole[first[i]:first[i] + n[i]].all(0)
    ranged = (np.arange(T_PAGES) >= lo[i]) & (np.arange(T_PAGES) < hi[i])
    assert not np.any(ranged & ~seen), (i, lo[i], hi[i], seen)
    if pack != "tree":
      assert ranged.tolist() == seen.tolist(), (i, lo[i], hi[i], seen)
    # a clear page is one of the block's live pages
    assert not np.any(ranged[:page0[i]]) and not np.any(ranged[last[i] + 1:])
    found += int(ranged.sum())
  assert found > 0 or (window == 24 and pack != "deep" and lanes > 1), found


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("kind", PAIR_KINDS)
@pytest.mark.parametrize("window", [0, 24, 37])
@pytest.mark.parametrize("lanes,bq", [(1, 16), (8, 64), (16, 16)])
def test_the_host_counts_the_clear_pairs_the_plan_holds(lanes, bq, window,
                                                        kind, seed):
  """`ClearPairs` (the host's rows) against the plan's own count: the pairs
  of a block of the widest rung whose page lies in its `clear_lo <= page <
  clear`, with a window and without. Never more than the live pairs, and
  none under a key that does not ask."""
  rows, tables = _PairRows(kind, seed)
  b = tables.shape[0]
  tok = ragged_lib.BuildTokenView(rows, b, T_PAGES, PAGE)
  key = rba.PlanKey(PAGE, window, bq, lanes, tree=False, kernel=True,
                    clear=True)
  plan = rba.BuildAttendPlan(key, tok.row, tok.q_end, b=b, t_pages=T_PAGES)
  n, last, lo, clear = (np.asarray(x) for x in (
      plan.n, plan.last, plan.clear_lo, plan.clear))
  wide = n > rba.ClearRung(rba.BlockRungs(bq, lanes))
  want = int(np.sum(np.where(
      wide, np.maximum(np.minimum(clear, last + 1) - lo, 0), 0)))
  got = rba.ClearPairs(key, rows.row_q_pos, rows.row_len, T_PAGES)
  assert got == want <= int(plan.pairs)
  if kind in ("decode_only", "empty") and lanes < bq:
    assert got == 0          # a decode row's rung runs one body
  if kind == "full_pool" and window != 24:
    assert got > 0
  assert rba.ClearPairs(key._replace(clear=False), rows.row_q_pos,
                        rows.row_len, T_PAGES) == 0


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kind", PAIR_KINDS)
@pytest.mark.parametrize("window", [0, 24, 37])
@pytest.mark.parametrize("lanes,bq,span", [(1, 16, 1), (8, 64, 1), (8, 64, 2),
                                           (8, 64, 4), (16, 16, 4)],
                         ids=["heads", "grouped_span1", "grouped_span2",
                              "grouped_span4", "one_rung_span4"])
def test_the_host_counts_the_programs_the_plans_list_holds(lanes, bq, span,
                                                           window, kind, seed):
  """`Programs` (the host's rows) against the plan's `pairs`, the grid's
  length, and the list against its enumeration: under a key of `span` G a
  block of the one-body rung (a decode row) takes an entry a span of G pages
  from its `page0` (wherever a window puts that) and every other block an
  entry a page, blocks in packed order, spans and pages ascending; `LivePairs`
  keeps counting pages. A key whose `span` is 1, and one whose blocks run one
  rung (none is a decode row's), lists a page an entry."""
  rows, tables = _PairRows(kind, seed)
  b = tables.shape[0]
  tok = ragged_lib.BuildTokenView(rows, b, T_PAGES, PAGE)
  key = rba.PlanKey(PAGE, window, bq, lanes, tree=False, kernel=True,
                    clear=lanes > 1, span=span)
  plan = rba.BuildAttendPlan(key, tok.row, tok.q_end, b=b, t_pages=T_PAGES)
  n, page0, last = (np.asarray(x) for x in (plan.n, plan.page0, plan.last))
  rung = rba.ClearRung(rba.BlockRungs(bq, lanes))
  want = []
  for i in np.flatnonzero(n > 0):
    stride = span if n[i] <= rung else 1
    want += [(int(i), int(p)) for p in range(page0[i], last[i] + 1, stride)]
  pairs = int(plan.pairs)
  assert pairs == len(want) == rba.Programs(key, rows.row_q_pos, rows.row_len,
                                            T_PAGES)
  got = list(zip(np.asarray(plan.blk).tolist(), np.asarray(plan.page).tolist()))
  assert got[:pairs] == want
  assert set(got[pairs:]) <= {want[-1] if want else (0, 0)}
  pages = rba.LivePairs(key, rows.row_q_pos, rows.row_len, T_PAGES)
  assert pages == int(np.sum(np.where(n > 0, last - page0 + 1, 0)))
  # the plan's other descriptors are the span-1 key's, to the bit
  flat = rba.BuildAttendPlan(key._replace(span=1), tok.row, tok.q_end, b=b,
                             t_pages=T_PAGES)
  for name in ("row", "last", "page0", "n", "first", "cols", "clear",
               "clear_lo"):
    if getattr(plan, name) is not None:
      _Same(getattr(plan, name), getattr(flat, name))
  assert int(flat.pairs) == pages
  if span == 1 or not rung:
    assert pairs == pages
  elif kind == "decode_only" and not window:
    # rows deep in their tables: fewer programs than pages
    decode = n[n > 0]
    assert np.all(decode <= rung) and pairs == int(np.sum(
        -(-(last - page0 + 1)[n > 0] // span)))
    assert pairs < pages or np.all((last - page0)[n > 0] == 0)


@pytest.mark.parametrize("kind", PAIR_KINDS)
@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("kernel", ["heads", "grouped", "diff"])
def test_a_grid_over_the_live_pairs_gives_the_twins_output(kernel, window,
                                                           kind):
  """Each of the three kernels over the pack's list (a grid of `pairs`
  programs, none for the empty step) against its XLA twin, with every pool
  page no query may see poisoned: a page the tables name past a row's
  horizon or behind its window, and every page no table names."""
  rows, tables = _PairRows(kind, 3)
  lens, q_pos = np.asarray(rows.row_len), np.asarray(rows.row_q_pos)
  b = tables.shape[0]
  tok = ragged_lib.BuildTokenView(rows, b, T_PAGES, PAGE)
  nq, nk, h = {"heads": (2, 2, 8), "grouped": (4, 2, 128),
               "diff": (8, 4, 8)}[kernel]
  rng = np.random.RandomState(4)
  np_total = b * T_PAGES + 1
  seen = np.zeros((np_total,), bool)
  for r in range(b):
    if lens[r]:
      lo = max(q_pos[r] + 1 - window, 0) // PAGE if window else 0
      seen[tables[r, lo:(q_pos[r] + lens[r] - 1) // PAGE + 1]] = True
  pools = [np.where(seen[:, None, None, None],
                    rng.randn(np_total, PAGE, nk, h), np.nan)
           for _ in range(2)]
  kp, vp = (jnp.asarray(x, jnp.float32) for x in pools)
  q = jnp.asarray(rng.randn(T, nq, h) * h ** -0.5, jnp.float32)
  args = (q, kp, vp, jnp.asarray(tables), tok.row, tok.q_end)
  if kernel == "diff":
    call = lambda lowering, **kw: diff_attend.DiffAttend(
        *args, 0.3, page_size=PAGE, window=window, lowering=lowering, **kw)
  else:
    call = lambda lowering, **kw: rba.RaggedAttend(
        *args, page_size=PAGE, window=window, lowering=lowering, **kw)
  out = np.asarray(call("pallas", interpret=True))
  assert np.all(np.isfinite(out))
  _Same(out[np.asarray(tok.q_end) == 0], 0.0)
  if kind == "empty":
    _Same(out, 0.0)
    return
  # the twin masks what it gathers, and a masked NaN is still one: it reads
  # the pools with the poison taken out
  clean = [jnp.nan_to_num(x) for x in (kp, vp)]
  args = (q, *clean, jnp.asarray(tables), tok.row, tok.q_end)
  np.testing.assert_allclose(out, np.asarray(call("xla")), atol=2e-5)


def test_a_plan_of_another_key_or_pack_is_refused():
  rows = _Rows("chain")
  kp, vp, tables = _Pools(2, 8)
  q = jnp.zeros((T, 2, 8), jnp.float32)
  key = rba.AttendPlanKey(2, 2, 8, PAGE, q.dtype, kp.dtype, window=24,
                          lowering="pallas")
  plan = attention_lib.BuildRaggedPlan([key], rows, *tables.shape)
  call = lambda q, **kw: rba.RaggedAttend(
      q, kp, vp, tables, plan.tokens.row[:q.shape[0]],
      plan.tokens.q_end[:q.shape[0]], page_size=PAGE, lowering="pallas",
      interpret=True, plan=plan.blocks, **kw)
  with pytest.raises(KeyError):
    call(q, window=0)                      # no descriptors at this window
  with pytest.raises(KeyError):
    call(q, window=24)                     # nor without the tree operands
  tree = dict(q_start=plan.tokens.q_start, anc_lo=rows.anc_lo,
              anc_hi=rows.anc_hi)
  call(q, window=24, **tree)
  with pytest.raises(AssertionError, match="another pack"):
    call(jnp.zeros((2 * T, 2, 8), jnp.float32), window=24, **{
        k: jnp.tile(v, 2) for k, v in tree.items()})
  assert attention_lib.BuildRaggedPlan([], rows, *tables.shape) is None


# -- the step programs ---------------------------------------------------------


def _Task(family, depth=None):
  name, served = {
      "dense": ("lm.synthetic_packed_input.DenseLmTiny", 24),
      "smallthinker": ("lm.smallthinker.SmallThinkerTiny", 8),
      "phi4flash": ("lm.phi4flash.Phi4MiniFlashTiny", 32),
  }[family]
  depth = depth or served
  mp = model_registry.GetParams(name, "Train")
  tp = mp.task
  tp.input = mp.input
  tp.num_layers = depth
  tp.fprop_dtype = jnp.float32
  if family == "phi4flash":
    tp.layer_kinds = phi4flash.LayerKinds(depth)
  if family == "smallthinker":
    tp.atten_tpl.dim_per_head = 128    # the grouped kernel's heads tile lanes
  task = tp.Instantiate()
  task.FinalizePaths()
  return task, task.InstantiateVariables(jax.random.PRNGKey(7))


# family -> (attend kernels a step calls, plans it builds for them, Pallas
# calls in its scans' bodies: dense one attend (its heads of 16 tile no lanes,
# so its page write is the twin's); SmallThinker a period of four, each
# layer the write of its runs and an attend; Phi-4-flash a write and an attend
# in the window block and in the full layer's, an attend in the cross block)
DECLARED = {"dense": (24, 1, 1), "smallthinker": (8, 2, 8),
            "phi4flash": (16, 2, 5)}


def _StepArgs(task, theta):
  """An engine driven to a step that holds a decode row, a finishing prompt,
  a mid-prompt chunk and an empty slot: (engine, every step's theta, states,
  tok_ids, rows, tables; that step's last)."""
  eng = engine_lib.ServingLoop(
      task, theta, page_size=PAGE, num_pages=48, max_batch=4,
      max_seq_len=128, prefill_token_budget=8)
  seen = []
  inner = eng._compile_log.Call

  def _Call(name, fn, *args):
    if name == "ragged":
      seen.append(jax.tree_util.tree_map(
          lambda x: jnp.array(x) if hasattr(x, "shape") else x, args[:5]))
    return inner(name, fn, *args)

  eng._compile_log.Call = _Call
  eng.Submit([5, 9, 2], 6, eos_id=None, seed=11)
  eng.StepOnce()
  eng.Submit([7, 1, 4], 6, eos_id=None, seed=12)
  eng.Submit(list(range(1, 31)), 6, eos_id=None, seed=13)
  eng.StepOnce()
  assert np.asarray(seen[-1][3].row_len).tolist() == [1, 3, 8, 0]
  return eng, seen


def _Census(jaxpr, in_scan=False, out=None):
  """{(primitive, inside a scan's body): count} over a jaxpr and every jaxpr
  its equations hold."""
  out = {} if out is None else out
  for eqn in jaxpr.eqns:
    name = eqn.primitive.name
    out[name, in_scan] = out.get((name, in_scan), 0) + 1
    for v in eqn.params.values():
      for sub in (v if isinstance(v, (tuple, list)) else (v,)):
        sub = getattr(sub, "jaxpr", sub)
        if hasattr(sub, "eqns"):
          _Census(sub, in_scan or name == "scan", out)
  return out


@pytest.fixture(scope="module", params=list(DECLARED))
def programs(request):
  """(family, the twins' engine's Stats(), the kernels' engine's Stats(), the
  kernels' step's census, the two programs' logits on one step's arguments)."""
  family = request.param
  task, theta = _Task(family)
  twin_eng, seen = _StepArgs(task, theta)
  args = seen[-1]

  def _Step():
    # a function object a program: JAX keeps traces by function, and the
    # two programs below differ in nothing it can see
    return lambda th, st, ids, rows, tables: task.RaggedStep(
        th, ids[None], st, tables, rows)[0]

  twin_logits = jax.jit(_Step())(*args)
  with pytest.MonkeyPatch.context() as mp:
    # 'auto' on this backend is the twin: take the kernel's side of every
    # call, as a TPU does (interpret mode follows the backend, not this)
    mp.setattr(rba, "Lowering", lambda lowering: (
        "pallas" if lowering == "auto" else lowering))
    stats = engine_lib.ServingLoop(
        task, theta, page_size=PAGE, num_pages=48, max_batch=4,
        max_seq_len=128, prefill_token_budget=8).Stats()
    census = _Census(jax.make_jaxpr(_Step())(*args).jaxpr)
    logits = jax.jit(_Step())(*args)
  return family, twin_eng.Stats(), stats, census, (twin_logits, logits)


def test_the_step_builds_each_plan_once_and_none_inside_a_scan(programs):
  family, _, _, census, _ = programs
  _, plans, in_bodies = DECLARED[family]
  # `_BuildQueryBlocks` is the program's one `cummax`, and with the page
  # write's pairs its one `cumsum`
  assert census.get(("cummax", False), 0) == plans, census
  assert not [k for k in census if k[0] in ("cumsum", "cummax") and k[1]]
  # the kernels they are built for are inside the scans' bodies, all of them
  assert ("pallas_call", False) not in census
  assert census["pallas_call", True] == in_bodies, census


def test_stats_count_what_the_stack_declares(programs):
  family, twin_stats, stats, _, _ = programs
  calls, plans, _ = DECLARED[family]
  assert (stats["attend_calls"], stats["attend_plans"]) == (calls, plans)
  # where the twins run no kernel is called and no descriptor built
  assert (twin_stats["attend_calls"], twin_stats["attend_plans"]) == (0, 0)


@pytest.mark.parametrize("family,depth", [("dense", 2), ("smallthinker", 4)])
def test_stats_count_the_pairs_the_steps_plans_hold(family, depth):
  """`attend_programs` is the sum of `AttendPlan.pairs`, the grids' lengths,
  over the steps an engine dispatched and the plans of each (the host counts
  from its own rows what the device lists from the same), `attend_live_pairs`
  the pages those programs attend (the same where no key walks spans),
  `attend_grid_pairs` the room of those lists; all stay 0 where the twins
  run."""
  task, theta = _Task(family, depth)
  with pytest.MonkeyPatch.context() as mp:
    mp.setattr(rba, "Lowering", lambda lowering: (
        "pallas" if lowering == "auto" else lowering))
    eng, seen = _StepArgs(task, theta)
    keys = sorted(set(task.stack.RaggedPlanKeys(eng._states)))
  stats = eng.Stats()
  assert len(keys) == stats["attend_plans"] == DECLARED[family][1]
  programs = live = grid = 0
  for _, _, _, rows, tables in seen:
    plan = attention_lib.BuildRaggedPlan(keys, rows, *tables.shape[-2:])
    # the grid's length is the plan's `pairs`: `attend_programs`. The live
    # pages are the same key's list at a page an entry (`span` 1)
    programs += sum(int(blocks.pairs) for blocks in plan.blocks.values())
    flat = attention_lib.BuildRaggedPlan(
        [k._replace(span=1) for k in keys], rows, *tables.shape[-2:])
    live += sum(int(blocks.pairs) for blocks in flat.blocks.values())
    grid += sum(blocks.blk.shape[0] for blocks in plan.blocks.values())
  assert stats["steps"] == len(seen) == 2
  assert (stats["attend_programs"], stats["attend_live_pairs"],
          stats["attend_grid_pairs"]) == (programs, live, grid)
  assert 0 < programs <= live < grid
  # a key that is not grouped runs a program a page
  grouped = any(k.span > 1 for k in keys)
  assert grouped == (family == "smallthinker")
  assert grouped or programs == live
  twin_stats = _StepArgs(task, theta)[0].Stats()
  assert (twin_stats["attend_live_pairs"], twin_stats["attend_grid_pairs"],
          twin_stats["attend_programs"]) == (0, 0, 0)


def test_the_kernels_program_is_the_twins_within_rounding(programs):
  _, _, _, _, (twin, kernels) = programs
  assert twin.shape == kernels.shape
  np.testing.assert_allclose(np.asarray(kernels), np.asarray(twin),
                             atol=2e-4, rtol=0)


# -- the whole-page write's counters, and the programs it must leave alone ----


def test_stats_count_the_page_writes_the_steps_plan_holds():
  """`kv_page_writes` is the sum of `WritePlan.live` over the steps an engine
  dispatched (the host counts from its own rows the pairs the kernel's grid
  runs, a program each in every owning layer), `kv_page_write_bound` the
  room of the plan's list a step; both stay 0 where the twins run and in a
  stack that writes by runs."""
  task, theta = _Task("phi4flash", 8)
  with pytest.MonkeyPatch.context() as mp:
    mp.setattr(rba, "Lowering", lambda lowering: (
        "pallas" if lowering == "auto" else lowering))
    eng, seen = _StepArgs(task, theta)
    runs_task, runs_theta = _Task("smallthinker", 4)
    by_runs = _StepArgs(runs_task, runs_theta)[0].Stats()
  stats = eng.Stats()
  live = bound = 0
  for _, _, _, rows, tables in seen:
    plan = diff_attend.BuildWritePlan(rows, *tables.shape[-2:], PAGE)
    assert int(plan.pairs) == int(plan.live.sum())
    live += int(plan.live.sum())
    bound += plan.live.shape[0]
  assert stats["steps"] == len(seen) == 2
  assert (stats["kv_page_writes"], stats["kv_page_write_bound"]) == (
      live, bound)
  assert 0 < live < bound == 2 * diff_attend.PageWrites(4, eng._ragged_t, PAGE)
  assert (stats["kv_write_runs"], stats["kv_write_tokens"]) == (0, 0)
  assert by_runs["kv_write_runs"] > 0
  twin = _StepArgs(task, theta)[0].Stats()
  for other in (by_runs, twin):
    assert (other["kv_page_writes"], other["kv_page_write_bound"]) == (0, 0)


# The step program of each family whose stack has no whole-page writer, as
# the PARENT of PR 56 (`9336827`) lowers it under JAX 0.9.0 on the CPU:
# tests/test_head_cols' engines at their mixed step (a decode row, a finishing
# prompt, a chunk, an empty slot; f32), lines of `lower().as_text()` and the
# first 16 hex digits of its sha256. The test below is the recipe: run it on
# a parent's tree to take a number again. `nemotron_h`'s is PR 58's own tree:
# PR 57 changed its step on purpose (the expert layers at the decode width
# under a conditional, the scan states read and written in the block's
# stack; the parent's was 5302 lines, "0e84d3f4da43a21f"), and so did PR 58
# (the Mamba-2 row pass is handed each row's last TOKEN and a fourth flag,
# the XLA twin divides; PR 57's was 6106 lines, "95362a350e6f1e71"). The
# other four are what they were: no step without a Mamba-2 layer moved.
# The three families with an expert layer are PR 60's own tree: it changed the
# layer's combine on purpose (core/moe.py: one gather of the matmuls' rows, k
# major, then mask, weights and sum); before it `smallthinker` was 3634 lines,
# "4ab22d507292c1e1", `nemotron_h` 6136, "220727d9511a9186", `mistral4` 1374,
# "df1550569c9ec9f4". No step without an expert layer moved. `nemotron_h`'s
# is PR 66's own tree: it changed the Mamba-2 layers' packed convolution on
# purpose (core/ssm._PackedConv: one fused pass and the tails' share by a
# one-hot product; `_PackedConvTail`: a select between the tail's shifts);
# before it 6106 lines, "1b1bed999b069ddd". No step without such a layer moved.
_PARENT_STEP = {
    "dense": (1290, "1876dbf11e99e5cf"),
    "smallthinker": (3612, "5a744b3068ca2ff2"),
    "nemotron_h": (5877, "3d62a2b3985911f7"),
    "brumby": (1608, "137c387cefa12e2f"),
    "mistral4": (1373, "6dd2bffb46b2b356"),
}


@pytest.mark.parametrize("family", list(_PARENT_STEP))
def test_a_stack_with_no_whole_page_writer_lowers_the_parents_step(family):
  """The whole-page write is one family's (differential attention's): the
  plan's new fields, the stack's `WritesWholePages` and the engine's two
  counters leave every other family's step program the parent's text."""
  import hashlib
  from tests import test_head_cols
  task, theta = {**test_head_cols._FAMILIES,
                 **test_head_cols._NEWER_FAMILIES}[family](jnp.float32)
  eng, calls, _ = test_head_cols._MixedStepEngine(task, theta)
  text = eng._ragged_fn.lower(*calls.calls[-1][0]).as_text()
  lines, digest = _PARENT_STEP[family]
  stats = eng.Stats()
  assert (stats["kv_page_writes"], stats["kv_page_write_bound"]) == (0, 0)
  assert len(text.splitlines()) == lines
  if jax.__version__ == "0.9.0":      # the text is that version's
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# -- the grouped kernel's clear pages: who else's step, and the counter --------

# The step program of each family whose attend kernel is NOT the grouped one
# (dense: head-batched; Phi-4-flash: differential attention's own, its key
# through `AttendPlanKey`; Mistral-Small-4: the latent kernel, its key
# `clear` since PR 55), with the kernels' lowering forced, as the PARENT of
# PR 62 (`abe1377`) lowers it under JAX 0.9.0 on the CPU: lines of
# `lower().as_text()` and the first 16 hex digits of its sha256. The test
# below is the recipe: run it on a parent's tree to take a number again.
# `phi4flash`'s is PR 66's own tree: its Mamba-1 layers share the packed
# convolution that PR changed on purpose (core/ssm._PackedConv,
# `_PackedConvTail`; before it 9184 lines, "a0b827fa28090b9b"); its attend
# kernel's key, plan and body are what they were.
_PARENT_KERNEL_STEP = {
    "dense": (2234, "e39ffac52564fa07"),
    "phi4flash": (9012, "42a973303f39c898"),
    "mistral4": (2627, "709f2b404996366f"),
}


@pytest.mark.parametrize("family", list(_PARENT_KERNEL_STEP))
def test_a_step_without_the_grouped_kernel_lowers_the_parents_program(family):
  """The plan's `clear_lo`, the grouped key's `clear` and the kernel's second
  body leave the programs of the other three attend kernels the parent's
  text, byte for byte: their keys, plans and bodies are what they were."""
  import hashlib
  from lingvo_tpu.core import mla as mla_lib
  from tests import test_head_cols
  if family == "mistral4":
    task, theta = test_head_cols._NEWER_FAMILIES[family](jnp.float32)
    _, calls, _ = test_head_cols._MixedStepEngine(task, theta)
    args = calls.calls[-1][0][:5]
  else:
    task, theta = _Task(family, 4 if family == "dense" else 8)
    args = _StepArgs(task, theta)[1][-1]
  with pytest.MonkeyPatch.context() as mp:
    mp.setattr(rba, "Lowering", lambda lowering: (
        "pallas" if lowering == "auto" else lowering))
    mp.setattr(mla_lib.MultiHeadLatentAttention, "_Lowering",
               lambda self, page_size: "pallas")
    keys = task.stack.RaggedPlanKeys(args[1])
    text = jax.jit(lambda th, st, ids, rows, tables: task.RaggedStep(
        th, ids[None], st, tables, rows)[0]).lower(*args).as_text()
  assert keys and all(k.kernel for k in keys)
  assert [k.clear for k in set(keys)] == [family == "mistral4"] * len(set(keys))
  lines, digest = _PARENT_KERNEL_STEP[family]
  got = (len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()[:16])
  assert got[0] == lines, got
  if jax.__version__ == "0.9.0":      # the text is that version's
    assert got[1] == digest, got


def test_the_engine_counts_a_grouped_models_clear_pairs():
  """`attend_clear_pairs` of a model whose layers run the grouped kernel (a
  full layer and three window layers a period: two keys): the host's own
  count over the steps dispatched, positive once a chunk's block holds a
  page under every query's horizon, and unmoved by a decode-only step."""
  task, theta = _Task("smallthinker", 4)
  twin = engine_lib.ServingLoop(
      task, theta, page_size=PAGE, num_pages=48, max_batch=4, max_seq_len=128,
      prefill_token_budget=8).Stats()
  assert twin["attend_plans"] == 0 and not twin.get("attend_clear_pairs")
  with pytest.MonkeyPatch.context() as mp:
    mp.setattr(rba, "Lowering", lambda lowering: (
        "pallas" if lowering == "auto" else lowering))
    eng = engine_lib.ServingLoop(
        task, theta, page_size=PAGE, num_pages=48, max_batch=4,
        max_seq_len=128, prefill_token_budget=8)
    keys = {k for k in task.stack.RaggedPlanKeys(eng._states)
            if k.kernel and k.clear}
    assert len(keys) == 2 == eng.Stats()["attend_plans"]
    assert sorted(k.window > 0 for k in keys) == [False, True]
    seen, note = [], eng._NoteDispatch

    def _Note(batch):
      seen.append((np.array(batch.rows_desc.row_q_pos),
                   np.array(batch.rows_desc.row_len)))
      return note(batch)

    eng._NoteDispatch = _Note
    long = eng.Submit(list(range(1, 41)), 4, eos_id=None, seed=13)
    while not long.done:
      eng.StepOnce()
  pages = eng.sched.table_pages
  by_step = [sum(rba.ClearPairs(k, q_pos, n, pages) for k in keys)
             for q_pos, n in seen]
  assert sum(by_step) == eng.Stats()["attend_clear_pairs"]
  chunk = [int(n.max()) > 1 for _, n in seen]
  # the first chunk sits in its row's first page; a later one has a page
  # under its narrowest horizon; a decode-only step runs the one masked body
  assert by_step[0] == 0 and max(by_step) > 0
  assert not all(chunk) and not any(
      c for c, is_chunk in zip(by_step, chunk) if not is_chunk)
  st = eng.Stats()
  assert 0 < st["attend_clear_pairs"] < st["attend_live_pairs"] == sum(
      rba.LivePairs(k, q_pos, n, pages) for k in keys for q_pos, n in seen)
