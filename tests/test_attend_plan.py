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
  lens, q_pos, parents = PACKS[pack]
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


@pytest.mark.parametrize("pack", list(PACKS))
def test_page_writes_with_the_steps_plan_are_bitwise_the_call_without(pack):
  nk, h = 4, 8
  rows = _Rows(pack)
  kp, vp, tables = _Pools(nk, h)
  rng = np.random.RandomState(2)
  kn, vn = (jnp.asarray(rng.randn(T, nk, h), jnp.float32) for _ in range(2))
  key = diff_attend.DiffPlanKey(8, nk, h, PAGE, kn.dtype, kp.dtype,
                                lowering="pallas")
  plan = attention_lib.BuildRaggedPlan([key], rows, *tables.shape,
                                       page_writes=True)
  assert plan.writes.tok.shape == (
      diff_attend.PageWrites(tables.shape[0], T, PAGE), PAGE)
  call = lambda **kw: diff_attend.WritePages(
      kp, vp, kn, vn, tables, rows, lowering="pallas", interpret=True, **kw)
  got, own = call(plan=plan.writes), call()
  scatter = diff_attend.WritePages(kp, vp, kn, vn, tables, rows,
                                   lowering="xla")
  for a, b, c in zip(got, own, scatter):
    _Same(a, b)
    _Same(a[:-1], c[:-1])     # all but the trash page (the scatter's padding)


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
              src=src, cols=cols, col0=tuple(cols[:, 0].T))


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


def _Task(family):
  name, depth = {
      "dense": ("lm.synthetic_packed_input.DenseLmTiny", 24),
      "smallthinker": ("lm.smallthinker.SmallThinkerTiny", 8),
      "phi4flash": ("lm.phi4flash.Phi4MiniFlashTiny", 32),
  }[family]
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
# calls in its scans' bodies: dense one attend; SmallThinker a period of four;
# Phi-4-flash a write and an attend in the window block and in the full
# layer's, an attend in the cross block)
DECLARED = {"dense": (24, 1, 1), "smallthinker": (8, 2, 4),
            "phi4flash": (16, 2, 5)}


def _StepArgs(task, theta):
  """An engine driven to a step that holds a decode row, a finishing prompt,
  a mid-prompt chunk and an empty slot: (engine, that step's theta, states,
  tok_ids, rows, tables)."""
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
  return eng, seen[-1]


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
  twin_eng, args = _StepArgs(task, theta)

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


def test_the_kernels_program_is_the_twins_within_rounding(programs):
  _, _, _, _, (twin, kernels) = programs
  assert twin.shape == kernels.shape
  np.testing.assert_allclose(np.asarray(kernels), np.asarray(twin),
                             atol=2e-4, rtol=0)
