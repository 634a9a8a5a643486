"""The page write by runs (ops/run_write.py) against the scatter it replaces.

`MultiHeadedAttention.RaggedStep` wrote a step's K and V with
`pool.at[phys, off].set(new)`, one row a packed token, padding to the trash
page. It now moves the step's RUNS. Here: the op against that scatter on the
live pages of the pool, bitwise, kernel (interpret mode) and twin; the layer
against itself with the scatter put back; what the plan's runs cover; and
what refused the first build of this (ledger, PR 48): a step program traced
or compiled more than once, or a write whose lowering grows with the pack.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.ops import ragged_block_attend as rba
from lingvo_tpu.ops import run_write

P, B, T_PAGES, T, WMAX = 8, 5, 6, 48, 24
NP = B * T_PAGES + 1            # the last page is the trash page

# pack -> (row lengths, first positions, tree rows' parents, padding moved in
# before the first row and between rows)
PACKS = {
    "decode_only": ([1, 1, 0, 1, 1], [5, 8, 1, 47, 16], None, False),
    # slots 5 .. 18: the rest of page 0, page 1 whole, three slots of page 2
    "chunk_from_mid_page_over_two_boundaries": (
        [0, 14, 0, 0, 0], [1, 5, 1, 1, 1], None, False),
    "padding_in_the_middle_and_at_the_end": (
        [1, 11, 0, 20, 3], [5, 6, 1, 13, 7], None, True),
    "tree_rows": ([1, 6, 0, 9, 1], [9, 4, 1, 14, 7],
                  {1: [-1, 0, 0, 2, -1], 3: [-1, 0, 1, 1, 3, -1, 5, 5]},
                  False),
    "a_full_pack": ([8, 16, 1, 22, 1], [0, 8, 3, 5, 40], None, False),
    "no_row_at_all": ([0, 0, 0, 0, 0], [6, 10, 8, 1, 1], None, False),
}


def _Rows(pack, t=T):
  lens, q_pos, parents, spread = PACKS[pack]
  rows = ragged_lib.BuildRaggedRows(np.array(lens), np.array(q_pos),
                                    t - 6 if spread else t, WMAX,
                                    row_parents=parents)
  if spread:
    # the scheduler packs rows back to back; the ops take padding anywhere
    gaps = np.cumsum([2] + [1 if n else 0 for n in lens])[:-1]   # per slot
    shift = gaps[rows.row_of] * rows.valid
    live = np.flatnonzero(rows.valid)
    t_axis = {}
    for name in ("row_of", "col_of", "pos", "valid", "pos_ids", "anc_lo",
                 "anc_hi"):
      src = getattr(rows, name)
      out = np.full((t,), -1 if name.startswith("anc") else 0, src.dtype)
      out[live + shift[live]] = src[live]
      t_axis[name] = out
    cols = np.where(np.arange(WMAX)[None] < np.asarray(lens)[:, None],
                    rows.row_cols + gaps[:, None], 0)
    rows = rows._replace(row_cols=cols.astype(np.int32), **t_axis)
  return ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))


def _Tables(seed=0):
  """Disjoint pages a row, none the trash page."""
  return jnp.asarray(np.random.RandomState(seed).permutation(NP - 1).reshape(
      B, T_PAGES).astype(np.int32))


def _Scatter(pool, new, tables, rows, base=0, np_total=NP):
  """The write this PR replaced, as RaggedStep had it: a row a token through
  its row's table, padding to the trash page (page `np_total - 1` past
  `base`), a table entry clipped to the layer's pages before the base."""
  tokens = ragged_lib.BuildTokenView(rows, *tables.shape, pool.shape[1])
  tables = jnp.clip(tables.astype(jnp.int32), 0, np_total - 1)
  phys = jnp.where(rows.valid, tables[tokens.row, tokens.logical],
                   np_total - 1) + base
  return pool.at[phys, tokens.off].set(new)


def _Normal(rng, shape, dtype):
  if dtype == jnp.int8:
    return jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
  return jnp.asarray(rng.randn(*shape), dtype)


@pytest.mark.parametrize("lowering", ["pallas", "xla"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("pack", sorted(PACKS))
def test_runs_land_where_the_scatter_put_them(pack, dtype, lowering):
  """Every page but the trash page comes out bitwise as the scatter leaves
  it; the trash page, which only the scatter's padding wrote, is untouched."""
  rng = np.random.RandomState(3)
  rows, tables = _Rows(pack), _Tables()
  pools = [_Normal(rng, (NP, P, 2, 128), dtype) for _ in range(2)]
  news = [_Normal(rng, (T, 2, 128), dtype) for _ in range(2)]
  runs = run_write.BuildWriteRuns(rows, B, T_PAGES, P)
  got = jax.jit(lambda kp, vp, kn, vn: run_write.WriteRuns(
      kp, vp, kn, vn, tables[runs.row, runs.logical], runs,
      lowering=lowering))(*pools, *news)
  for pool, new, out in zip(pools, news, got):
    want = _Scatter(pool, new, tables, rows)
    np.testing.assert_array_equal(np.asarray(out[:-1], np.float32),
                                  np.asarray(want[:-1], np.float32))
    np.testing.assert_array_equal(np.asarray(out[-1], np.float32),
                                  np.asarray(pool[-1], np.float32))


# -- the layer: its own write against the scatter put back --------------------


def _Layer(n_kv, dim_per_head=128):
  p = attention_lib.MultiHeadedAttention.Params().Set(
      name="mha", input_dim=32, hidden_dim=4 * dim_per_head, num_heads=4,
      num_kv_heads=n_kv, dim_per_head=dim_per_head,
      use_rotary_position_emb=True)
  layer = p.Instantiate()
  return layer, layer.InstantiateVariables(jax.random.PRNGKey(5))


def _FilledStates(layer, theta, kv_cache_dtype, layers, seed=9):
  """Every element set, trash pages included; stacked over `layers` repeats
  where asked."""
  states = layer.InitPagedStates(theta, NP, P, kv_cache_dtype=kv_cache_dtype)
  rng = np.random.RandomState(seed)
  lead = (layers,) if layers else ()
  return states.Transform(lambda x: (
      jnp.asarray(rng.randint(-127, 128, lead + x.shape), jnp.int8)
      if x.dtype == jnp.int8
      else jnp.asarray(rng.uniform(0.1, 1.0, lead + x.shape), x.dtype)))


# case -> (KV heads, pool dtype, layers the pool is stacked over, the layer)
LAYER_CASES = {
    "its_own_pool_of_2_kv_heads": (2, None, 0, None),
    "its_own_pool_of_4_heads": (4, None, 0, None),
    "a_stacked_pool_addressed_by_layer": (4, None, 3, 1),
    "the_last_layer_of_a_stacked_pool": (2, None, 3, 2),
    "an_int8_pool_with_its_scales": (4, "int8", 0, None),
    "a_stacked_int8_pool": (4, "int8", 2, 0),
}


@pytest.mark.parametrize("lowering", ["pallas", "xla"])
@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_the_layers_write_is_the_scatters_on_its_live_pages(case, lowering,
                                                            monkeypatch):
  """RaggedStep with the write by runs against RaggedStep with the scatter in
  its place, on tables with entries out of the layer's range (clipped before
  the base is added): the step's output and every page but the layer's trash
  page bitwise, so no write reached another layer's pages; and K's and V's
  trash page as it was."""
  n_kv, kv_dtype, layers, at = LAYER_CASES[case]
  layer, theta = _Layer(n_kv)
  states = _FilledStates(layer, theta, kv_dtype, layers)
  rows = _Rows("padding_in_the_middle_and_at_the_end")
  tables = np.array(_Tables())
  tables[2] = [NP + 3, -4, 99, 2, NP - 1, 7]     # an empty slot's: stale
  tables[0, 3:] = [NP + 1, -1, 10**6]            # past row 0's live pages
  tables = jnp.asarray(tables)
  x = jnp.asarray(np.random.RandomState(1).randn(1, T, 32), jnp.float32)
  kw = {} if at is None else {"layer": at}

  def _Step():
    # a function object a program: JAX keeps traces by function
    return jax.jit(lambda th, st: layer.RaggedStep(th, x, st, tables, rows,
                                                   **kw))

  monkeypatch.setattr(rba, "Lowering", lambda asked: (
      lowering if asked == "auto" else asked))
  out, new = _Step()(theta, states)

  def _ScatterInPlace(k_pool, v_pool, k_new, v_new, pages, runs, **_):
    del pages, runs
    base = 0 if at is None else at * NP
    return tuple(_Scatter(pool, fresh, tables, rows, base)
                 for pool, fresh in ((k_pool, k_new), (v_pool, v_new)))

  monkeypatch.setattr(run_write, "WriteRuns", _ScatterInPlace)
  want_out, want = _Step()(theta, states)
  if lowering == "xla":     # the attend kernel's own sums run in its order
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
  else:
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               atol=2e-5, rtol=0)
  for name in sorted(states.keys()):
    got_p, want_p, old_p = (np.asarray(t[name]).reshape(
        (-1,) + t[name].shape[-3:]) for t in (new, want, states))
    trash = (0 if at is None else at * NP) + NP - 1
    keep = np.arange(got_p.shape[0]) != trash
    np.testing.assert_array_equal(got_p[keep], want_p[keep], err_msg=name)
    if "scale" not in name:
      np.testing.assert_array_equal(got_p[trash], old_p[trash], err_msg=name)
    # and the scatter did write the pages the step's rows own
    assert not np.array_equal(want_p[keep], old_p[keep]), name


# -- what the plan's runs cover ----------------------------------------------


def _RandomRows(seed):
  """Rows as a scheduler would pack them, of random length and position;
  (lens, q_pos, t, b, page, t_pages)."""
  rng = np.random.RandomState(seed)
  b = int(rng.randint(1, 9))
  page = int(rng.choice([2, 4, 8, 16]))
  t = int(rng.randint(b, 80))
  lens = np.zeros(b, np.int64)
  left = t - int(rng.randint(0, t // 3 + 1))
  for i in rng.permutation(b):
    kind = rng.randint(3)
    lens[i] = 0 if kind == 0 else min(left, 1 if kind == 1 else int(
        rng.randint(1, 40)))
    left -= lens[i]
  q_pos = rng.randint(0, 5 * page, b)
  t_pages = int(-(-(q_pos + lens).max() // page)) + 1
  return lens, q_pos, t, b, page, t_pages


@pytest.mark.parametrize("seed", range(24))
def test_pieces_cover_every_valid_token_and_no_padding(seed):
  """The live pieces of every width together hold every valid token (a run's
  last piece may lie over the one before it: the same tokens to the same
  slots) and no other; a piece is one row's, inside one page, and its
  tokens' slots are the page's in order; the dead entries stay in bounds."""
  lens, q_pos, t, b, page, t_pages = _RandomRows(seed)
  rows = ragged_lib.BuildRaggedRows(lens, q_pos, t, max(int(lens.max()), 1))
  runs = jax.tree_util.tree_map(np.asarray, run_write.BuildWriteRuns(
      ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows)), b, t_pages,
      page))
  widths = run_write.Widths(page, t)
  room = tuple(r for _, _, r in run_write._Pieces(b, t, page))
  assert runs.row.shape == (sum(room),) and len(widths) == len(room)
  np.testing.assert_array_equal(runs.first, np.cumsum((0,) + room[:-1]))
  assert (runs.counts <= room).all()
  assert (int(runs.runs), int(lens.sum())) == run_write.RunCounts(
      q_pos, lens, page)
  assert int(runs.runs) <= run_write.MaxRuns(b, t, page)
  written = np.zeros(t, np.int64)
  pieces = 0
  for c, w in enumerate(widths):
    every = slice(runs.first[c], runs.first[c] + room[c])
    assert (runs.tok[every] >= 0).all() and (runs.tok[every] + w <= t).all()
    assert (runs.off[every] >= 0).all() and (runs.off[every] + w <= page).all()
    for j in range(runs.first[c], runs.first[c] + runs.counts[c]):
      span = np.arange(runs.tok[j], runs.tok[j] + w)
      written[span] += 1
      pieces += 1
      assert (rows.row_of[span] == runs.row[j]).all() and rows.valid[
          span].all()
      np.testing.assert_array_equal(
          rows.pos[span], runs.logical[j] * page + runs.off[j] + np.arange(w))
  np.testing.assert_array_equal(written > 0, rows.valid)
  # a token is written once, or twice where a last piece is laid back
  assert written.max(initial=0) <= 2
  assert int(runs.runs) <= pieces <= 15 * int(runs.runs)


# -- what refused PR 48: more than one program, or one that grows -------------


def _OpCount(lowered) -> int:
  return sum(" = " in line for line in lowered.as_text().splitlines())


def _Equations(jaxpr) -> int:
  """The equations of a jaxpr and of every jaxpr its equations hold (a
  kernel's body, a loop's, a branch's)."""
  count = 0
  for eqn in jaxpr.eqns:
    count += 1
    for v in eqn.params.values():
      for sub in (v if isinstance(v, (tuple, list)) else (v,)):
        sub = getattr(sub, "jaxpr", sub)
        if hasattr(sub, "eqns"):
          count += _Equations(sub)
  return count


def _WriteArgs(t, b, page=128, pages=33, n=4, h=128):
  sds = jax.ShapeDtypeStruct
  pool, new = sds((pages, page, n, h), jnp.bfloat16), sds((t, n, h),
                                                          jnp.bfloat16)
  rows = ragged_lib.BuildRaggedRows(np.zeros(b, np.int32),
                                    np.ones(b, np.int32), t, t)
  runs = jax.eval_shape(
      lambda r: run_write.BuildWriteRuns(r, b, 16, page),
      ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows)))
  return pool, pool, new, new, sds(runs.row.shape, jnp.int32), runs


@pytest.mark.parametrize("lowering", ["pallas", "xla"])
def test_the_lowered_write_does_not_grow_with_the_pack(lowering):
  """The same number of operations at two packed widths and two slot counts,
  in the program (lowered for a TPU, where the kernel is a custom call) and
  in the kernel's own body: neither T, nor the room of the list, nor a count
  of runs or of pieces is unrolled into either."""
  def _Write(kp, vp, kn, vn, pages, runs):
    return run_write.WriteRuns(kp, vp, kn, vn, pages, runs,
                               lowering=lowering, interpret=False)

  counts, bodies = set(), set()
  for t, b in ((544, 32), (1088, 64), (136, 8)):
    traced = jax.jit(_Write).trace(*_WriteArgs(t, b))
    counts.add(_OpCount(traced.lower(lowering_platforms=("tpu",))))
    bodies.add(_Equations(traced.jaxpr.jaxpr))
  assert len(counts) == 1 and len(bodies) == 1, (counts, bodies)


def test_an_engine_compiles_its_step_once_whatever_the_steps_hold():
  """A ServingLoop driven through decode-only steps, a step with one chunk,
  steps with several and a full pack: one step program, compiled at the
  first step and called at every later one, and nothing traced, lowered or
  compiled after it (the listener benchmarks/harness/device.CompileClock
  sets)."""
  from jax._src import monitoring
  from lingvo_tpu import model_registry
  import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)
  from lingvo_tpu.serving import engine as engine_lib
  mp = model_registry.GetParams("lm.smallthinker.SmallThinkerTiny", "Train")
  tp = mp.task
  tp.input = mp.input
  tp.num_layers = 4
  tp.fprop_dtype = jnp.float32
  tp.atten_tpl.dim_per_head = 128       # heads that tile lanes: the kernel
  task = tp.Instantiate()
  task.FinalizePaths()
  theta = task.InstantiateVariables(jax.random.PRNGKey(7))
  events = []

  def _OnDuration(event, secs, **_):
    if event.startswith("/jax/core/compile/"):
      events.append(event)

  with pytest.MonkeyPatch.context() as mp_:
    mp_.setattr(rba, "Lowering", lambda lowering: (
        "pallas" if lowering == "auto" else lowering))
    eng = engine_lib.ServingLoop(
        task, theta, page_size=16, num_pages=48, max_batch=4,
        max_seq_len=128, prefill_token_budget=8)
    mixes = []
    inner = eng._compile_log.Call

    def _Call(name, fn, *args):
      if name == "ragged":
        mixes.append(tuple(np.asarray(args[3].row_len).tolist()))
      return inner(name, fn, *args)

    eng._compile_log.Call = _Call
    eng.Submit([5, 9, 2], 12, eos_id=None, seed=11)
    eng.StepOnce()                      # one chunk; compiles
    eng.StepOnce()
    monitoring.register_event_duration_secs_listener(_OnDuration)
    try:
      eng.StepOnce()                    # decode only
      eng.Submit([7, 1, 4], 8, eos_id=None, seed=12)
      eng.Submit(list(range(1, 31)), 8, eos_id=None, seed=13)
      eng.Submit(list(range(3, 40)), 8, eos_id=None, seed=14)
      for _ in range(8):                # several chunks, full packs, decode
        eng.StepOnce()
    finally:
      monitoring.unregister_event_duration_listener(_OnDuration)
    stats = eng.Stats()
  assert len(set(mixes)) >= 6, mixes
  budget = 4 + 8
  assert any(sum(m) == budget for m in mixes), mixes             # a full pack
  assert any(sum(n > 1 for n in m) >= 2 for m in mixes), mixes   # chunks
  assert any(set(m) <= {0, 1} for m in mixes), mixes             # decode only
  record = eng._compile_log.Records()["ragged"]
  assert "fallback" not in record, record
  assert record["calls"] == stats["steps"] == len(mixes)
  assert events == []
  # the host counts from its own rows what the device lists from the same
  assert stats["kv_write_tokens"] == sum(sum(m) for m in mixes)
  assert 0 < stats["kv_write_runs"] <= stats["kv_write_tokens"]


def test_counters_are_zero_where_no_layer_writes_by_runs():
  """A stack of differential-attention owners writes whole pages by its own
  plan (ops/diff_attend.WritePages): `kv_write_runs` and `kv_write_tokens`
  stay 0 there."""
  from lingvo_tpu import model_registry
  import lingvo_tpu.models.all_params  # noqa: F401
  from lingvo_tpu.models.lm.params import phi4flash
  from lingvo_tpu.serving import engine as engine_lib
  mp = model_registry.GetParams("lm.phi4flash.Phi4MiniFlashTiny", "Train")
  tp = mp.task
  tp.input = mp.input
  tp.num_layers = 8
  tp.layer_kinds = phi4flash.LayerKinds(8)
  tp.fprop_dtype = jnp.float32
  task = tp.Instantiate()
  task.FinalizePaths()
  theta = task.InstantiateVariables(jax.random.PRNGKey(7))
  eng = engine_lib.ServingLoop(
      task, theta, page_size=16, num_pages=48, max_batch=4, max_seq_len=128,
      prefill_token_budget=8)
  eng.Submit([5, 9, 2], 4, eos_id=None, seed=11)
  eng.StepOnce()
  eng.StepOnce()
  stats = eng.Stats()
  assert stats["steps"] == 2
  assert (stats["kv_write_runs"], stats["kv_write_tokens"]) == (0, 0)
