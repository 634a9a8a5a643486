"""SmallThinker's mechanisms at a size the CPU holds, against the plain
reference (benchmarks/references/smallthinker.py) and against numpy: grouped
KV heads and the window through RaggedAttend, the dropless expert layer's
step, the cache by kind of layer, and the tiny registered sibling served by
ServingLoop in chunks and decode steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import smallthinker as ref
from lingvo_tpu import model_registry
from lingvo_tpu.core import moe
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.ops import ragged_block_attend as rba
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import spec_decode

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

_WINDOW, _TOP_K = 24, 2      # SmallThinkerTiny's
# the served f32 model against the f32 reference; the same weights rounded
# to bf16 read 1e-2 and more (test_bf16_weights_fail_the_tolerance)
_LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def tiny():
  mp = model_registry.GetParams("lm.smallthinker.SmallThinkerTiny", "Train")
  tp = mp.task
  tp.input = mp.input
  task = tp.Instantiate()
  task.FinalizePaths()
  theta = task.InstantiateVariables(jax.random.PRNGKey(7))
  return task, ref.SeededWeights(theta, attention_out_scale=4.0,
                                 window=_WINDOW, experts_per_token=_TOP_K)


# -- RaggedAttend: the group as rows of M, the window's lower bound -----------


def _NumpyAttend(q, kp, vp, tables, row_of, q_end, page, window):
  t, n, h = q.shape
  group = n // kp.shape[2]
  out = np.zeros((t, n, h), np.float32)
  for i in range(t):
    e = int(q_end[i])
    if e == 0:
      continue
    lo = max(0, e - window) if window else 0
    slots = range(lo, e)
    ks = np.stack([kp[tables[row_of[i], s // page], s % page] for s in slots])
    vs = np.stack([vp[tables[row_of[i], s // page], s % page] for s in slots])
    for head in range(n):
      sc = ks[:, head // group] @ q[i, head]
      p = np.exp(sc - sc.max())
      out[i, head] = (p / p.sum()) @ vs[:, head // group]
  return out


# (tokens of a row, its first token's q_end) and the padding tokens after it
_THREE_ROWS = (((1, 51), 0), ((20, 31), 0), ((5, 1), 6))
# every rung of the grouped kernel in one call (Bq 512, a token lays 8
# queries): a decode row (8 rows), a 2-token row (16 queries: the next rung),
# a row of exactly Bq / 8 = 64 tokens, and a chunk of 85 that spans two
# blocks with a ragged last one of 21 tokens; padding between the rows
_EVERY_RUNG = (((1, 51), 1), ((2, 30), 2), ((64, 17), 0), ((85, 11), 5))


def _RungCases():
  for heads in ((7, 1), (14, 2), (28, 4)):
    for window in (0, 20):
      for pages in ("f32", "bf16"):
        yield pytest.param(
            _EVERY_RUNG, *heads, window, pages, "pallas",
            id=f"every_rung-{heads[0]}_over_{heads[1]}-w{window}-{pages}")
  for window in (0, 20):
    yield pytest.param(_EVERY_RUNG, 14, 2, window, "f32", "xla",
                       id=f"every_rung-14_over_2-w{window}-f32-xla")
    yield pytest.param(_EVERY_RUNG, 2, 2, window, "f32", "pallas",
                       id=f"every_rung-mha-w{window}-f32")


@pytest.mark.parametrize(
    "pack,heads,kv_heads,window,pages,lowering",
    [pytest.param(_THREE_ROWS, *heads, window, "f32", lowering,
                  id=f"{name}-{window}-{lowering}")
     for lowering in ("xla", "pallas") for window in (0, 20)
     for name, heads in (("group_of_7", (7, 1)), ("two_groups_of_7", (14, 2)),
                         ("mha", (2, 2)))] + list(_RungCases()))
def test_ragged_attend_groups_and_window(pack, heads, kv_heads, window, pages,
                                         lowering):
  """Rows of several lengths and padding in one pack, against numpy and,
  for the kernel, against the XLA twin. Pages wholly behind a row's window
  hold NaN, and so do the queries of the padding tokens, which lie past a
  narrow block's own rows: a lowering that read either into what it keeps,
  even masked, would return NaN. A padding token's output is an exact 0."""
  rng = np.random.RandomState(heads + window)
  page, h, rows = 8, 128, len(pack)
  t_pages = -(-max(n + e for (n, e), _ in pack) // page)
  pool = rows * t_pages + 1
  kp = rng.randn(pool, page, kv_heads, h).astype(np.float32)
  vp = rng.randn(pool, page, kv_heads, h).astype(np.float32)
  tol = 3e-5
  if pages == "bf16":
    # the pool as bf16 holds it; the kernel rounds q and p to bf16 as well
    kp, vp = (np.array(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
              for a in (kp, vp))
    tol = 3e-2
  tables = rng.permutation(pool - 1)[:rows * t_pages].reshape(
      rows, t_pages).astype(np.int32)
  row_of, q_end = [], []
  for row, ((n, first_end), pad) in enumerate(pack):
    row_of += [row] * n + [0] * pad
    q_end += list(range(first_end, first_end + n)) + [0] * pad
  row_of, q_end = np.array(row_of, np.int32), np.array(q_end, np.int32)
  q = rng.randn(len(row_of), heads, h).astype(np.float32) / np.sqrt(h)
  want = _NumpyAttend(q, kp, vp, tables, row_of, q_end, page, window)
  q[q_end == 0] = np.nan
  if window:
    for row, ((_, narrowest), _) in enumerate(pack):
      for lp in range(max(0, narrowest - window) // page):
        kp[tables[row, lp]] = vp[tables[row, lp]] = np.nan
  dtype = jnp.bfloat16 if pages == "bf16" else jnp.float32

  def _Run(lowering):
    return np.asarray(rba.RaggedAttend(
        jnp.asarray(q), jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
        jnp.asarray(tables), row_of, q_end, page_size=page, window=window,
        lowering=lowering))

  got = _Run(lowering)
  np.testing.assert_allclose(got, want, atol=tol)
  assert np.all(got[q_end == 0] == 0.0)
  if lowering == "pallas":
    np.testing.assert_allclose(got, _Run("xla"), atol=tol)


def test_block_rungs_hold_a_blocks_queries():
  """The ladder is a function of Bq and a token's laid queries; a block
  runs the first rung that holds its valid queries, none where it has
  none."""
  bq = rba.QueryBlock(4, 128, 128, jnp.bfloat16, jnp.bfloat16, grouped=True)
  rungs = rba.BlockRungs(bq, rba.GroupLanes(7))
  assert rungs == (8, 512) and bq == 512
  queries = np.array([0, 1, 8, 9, 16, 64, 128, 129, 511, 512])
  rows = rba.BlockRows(queries, rungs)
  assert rows[0] == 0 and rows[2] == 8 and rows[-1] == 512
  for n, r in zip(queries[1:], rows[1:]):
    assert r in rungs and r >= n
    assert not any(n <= lower < r for lower in rungs)
  assert int(rba.BlockRows(8, rungs)) == 8
  # the head-batched kernel: one query alone, or Bq
  assert rba.BlockRungs(128) == (1, 128)
  assert rba.BlockRows(np.array([1, 2, 128]), (1, 128)).tolist() == [1, 128,
                                                                     128]


def test_window_pages_of_a_block():
  assert rba.WindowPages(0, 128, 128, 128) == 128
  # 4096 + 511 slots touch at most 37 pages of 128; never more than the row
  assert rba.WindowPages(4096, 512, 128, 128) == 37
  assert rba.WindowPages(4096, 512, 128, 16) == 16


# -- the expert layer's step -------------------------------------------------


def _ExpertLayer(k):
  p = moe.DroplessMoELayer.Params().Set(
      name="moe", input_dim=16, hidden_dim=8, num_experts=4,
      num_experts_per_token=k)
  layer = p.Instantiate()
  layer.FinalizePaths()
  return layer, layer.InstantiateVariables(jax.random.PRNGKey(3))


@pytest.mark.parametrize("case,k", [("uneven", 2), ("an_expert_with_no_token", 2),
                                    ("every_token_to_one_expert", 1)])
def test_expert_step_is_the_references_gather(case, k, monkeypatch):
  """The sort and the grouped matmuls against the reference's per-expert
  gather, on router logits made to route as the case says; the step's
  padding tokens are routed nowhere and counted nowhere."""
  layer, theta = _ExpertLayer(k)
  rng = np.random.RandomState(11)
  t = 13
  x = jnp.asarray(rng.randn(t, 16), jnp.float32)
  logits = rng.randn(t, 4).astype(np.float32)
  if case == "an_expert_with_no_token":
    logits[:, 3] = -1e9
  elif case == "every_token_to_one_expert":
    logits[:, 2] = 50.0
  valid = np.ones(t, bool)
  valid[[4, 12]] = False
  rows = ragged_lib.RaggedRows(*(jnp.asarray(a) for a in
                                 ragged_lib.BuildRaggedRows([t], [0], t, t)))
  rows = rows._replace(valid=jnp.asarray(valid))
  out, states = jax.jit(
      lambda th, x, r: layer.RaggedStep(
          th, x[None], layer.InitPagedStates(th), rows,
          router_logits=r[None]))(theta, x, jnp.asarray(logits))
  monkeypatch.setattr(ref, "_PIECE", 2)   # several pieces an expert
  monkeypatch.setitem(ref._ARCH, "experts_per_token", k)
  monkeypatch.setitem(ref._ARCH, "eps", 1e-6)
  ff = jax.tree_util.tree_map(lambda a: a[None], dict(theta))
  g = ref._RmsNorm(x, theta.ln.scale)
  want = x + ref._Experts(ff, 0, g, jnp.asarray(logits))
  np.testing.assert_allclose(np.asarray(out[0])[valid],
                             np.asarray(want)[valid], atol=1e-5)
  top = np.argsort(-logits, -1)[:, :k][valid]
  np.testing.assert_array_equal(np.asarray(states.routed),
                                np.bincount(top.reshape(-1), minlength=4))
  if case == "every_token_to_one_expert":
    assert np.asarray(states.routed).tolist() == [0, 0, int(valid.sum()), 0]


# -- pages by kind of layer --------------------------------------------------


def _Kinds(num_pages=64, windows=(0, 24), step=16, table_pages=32, page=8):
  return kv_cache.KindPages(kv_cache.PageAllocator(num_pages, page), windows,
                            step, 2, table_pages)


@pytest.mark.parametrize("total,cursors", [
    (200, list(range(16, 200, 16)) + [199]),  # chunks of 16, past the window
    (20, [16, 19]),                           # shorter than one window
    (250, list(range(1, 250))),               # single steps to the end
], ids=["long_row", "short_row", "single_steps"])
def test_window_pages_follow_the_cursor(total, cursors):
  """After every advance the window layer's row holds a page for each
  logical page that a query at or after the cursor can read or the next
  step can write, and none behind the window, while the full layer's row
  holds them all; what the window lets go of comes back to the ONE pool
  once the row stops growing."""
  kp = _Kinds()
  page, window, step = 8, 24, 16
  cap = (window + step - 2) // page + 2
  assert kp.caps == (32, cap)
  n = -(-total // page)
  kp.Admit("a", 1, total)
  assert kp.alloc.num_in_use == n + min(n, cap) == kp.Footprint(total)
  full = kp.Held("a", 0)
  for pos in cursors:
    kp.Advance("a", pos)
    first, pages = kp.Held("a", 1)
    assert first == max(0, pos - window + 1) // page
    last_needed = min(total - 1, pos + step - 1) // page
    assert first + len(pages) > last_needed
    assert len(set(pages)) == len(pages) <= cap
    assert not set(pages) & set(full[1]) and kp.Held("a", 0) == full
    np.testing.assert_array_equal(
        kp.tables[1, 1, first:first + len(pages)], pages)
    assert kp.alloc.num_in_use == n + len(pages)
    assert kp.in_use == {"full": n, "window": len(pages)}
  assert kp.pages_allocated - kp.pages_released == len(kp.Held("a", 1)[1])
  assert not kp.tables[:, 0].any()
  kp.Free("a")
  assert kp.alloc.num_in_use == 0 and kp.in_use == {"full": 0, "window": 0}
  assert kp.peak_in_use["full"] == n


def test_both_kinds_draw_from_one_pool():
  """Admission takes a request's pages of both kinds or none, and a page
  one kind lets go of is the other's to take: no share is fixed."""
  kp = _Kinds(num_pages=40, windows=(0, 24, 24))
  assert kp.caps == (32, 6, 6) and kp.Footprint(500) == 32 + 12
  assert not kp.CanAdmit(500) and kp.CanAdmit(100)
  kp.Admit("a", 0, 100)                       # 13 + 6 + 6
  assert kp.alloc.num_free == 15 and not kp.CanAdmit(48) and kp.CanAdmit(40)
  assert kp.in_use == {"full": 13, "window": 12}
  for pos in list(range(16, 100, 16)) + [99]:
    kp.Advance("a", pos)                      # the windows' tails are freed
  assert kp.in_use == {"full": 13, "window": 2 * 4}
  assert kp.CanAdmit(48)
  kp.Admit("b", 1, 48)                        # 6 full pages among them
  assert kp.alloc.num_free == 1
  st = kp.Stats()
  assert st["num_pages"] == 40 and st["peak_in_use"] == 39
  assert st["kinds"]["window"]["peak_in_use"] == 8 + 12
  assert st["window_cap_pages"] == 6 and st["window_pages_released"] == 2 * 9


# -- the tiny sibling through ServingLoop ------------------------------------


class _Probe:
  """Every step through the task's ragged step with its logits kept:
  {(slot, position): logits [V]} of every valid token."""

  def __init__(self, engine, task):
    self.engine, self.seen = engine, {}
    self._fn = jax.jit(lambda th, st, ids, rows, tables: task.RaggedStep(
        th, ids[None], st, tables, rows))
    self._inner = engine._compile_log.Call
    engine._compile_log.Call = self._Call

  def _Call(self, name, fn, *args):
    if name != "ragged":
      return self._inner(name, fn, *args)
    theta, states, tok_ids, rows, tables = args[:5]
    logits, new_states = self._fn(theta, states, tok_ids, rows, tables)
    logits = np.asarray(logits[0])
    for col in np.flatnonzero(np.asarray(rows.valid)):
      key = int(np.asarray(rows.row_of)[col]), int(np.asarray(rows.pos)[col])
      self.seen[key] = logits[col]
    return jnp.asarray(logits.argmax(-1), jnp.int32), new_states


def _PoisonDeadWindowPages(eng):
  """Into the pool, what no query may read. NaN in every page no row holds
  (never handed out, or let go of for good). A huge number in every page a
  row holds but has nothing live in: a page a window has left behind that
  now backs its tail, a page reserved and not yet written. (Not NaN there:
  such a page goes live slot by slot, and a masked slot weighs 0, which NaN
  does not survive and real stale K and V do.)"""
  kp, page = eng._kind_pages, eng.page_size
  held, live = set(), set()
  for seq in eng.sched.slots:
    if seq is not None:
      for layer in range(len(kp.windows)):
        first, pages = kp.Held(seq.id, layer)
        held.update(pages)
        if seq.pos > 0:
          live.update(pages[:(seq.pos - 1) // page - first + 1])
  free = jnp.asarray([p for p in range(kp.alloc.num_pages) if p not in held],
                     jnp.int32)
  stale = jnp.asarray(sorted(held - live), jnp.int32)
  pool = eng._states.body.kv_pool
  for name in ("key", "value"):
    pool[name] = pool[name].at[:, free].set(jnp.nan).at[:, stale].set(3e4)


def _Serve(task, theta, prompts, new_tokens, poison=False):
  eng = engine_lib.ServingLoop(task, theta, page_size=8, num_pages=48,
                               max_batch=len(prompts), max_seq_len=128,
                               prefill_token_budget=16)
  probe = _Probe(eng, task)
  handles = [eng.Submit(p, new_tokens) for p in prompts]
  for _ in range(400):
    if all(h.done for h in handles):
      break
    eng.StepOnce()
    if poison:
      _PoisonDeadWindowPages(eng)
  assert all(h.done for h in handles)
  return eng, probe.seen, [h.Result() for h in handles]


def _ReferenceLogits(theta, seq, at):
  ids = np.zeros((1, 128), np.int32)
  ids[0, :len(seq)] = seq
  return np.asarray(jax.jit(lambda th, i, a: ref.LogitsAt(th, i, a, 0.0))(
      theta, jnp.asarray(ids), jnp.asarray([at], jnp.int32)))[0]


_PROMPTS = {"longer_than_three_windows": [90], "shorter_than_one": [10],
            "both_in_one_step": [90, 10, 50]}


@pytest.mark.parametrize("case", list(_PROMPTS))
def test_chunked_prefill_and_decode_match_the_reference(tiny, case):
  """Prefill in chunks of 16 and 8 decode steps through the two-kind paged
  cache: the step's logits at the end of the prompt and at the last token
  fed back equal the reference's full forward there."""
  task, theta = tiny
  rng = np.random.RandomState(5)
  prompts = [rng.randint(1, 128, n).astype(np.int32) for n in _PROMPTS[case]]
  eng, seen, outs = _Serve(task, theta, prompts, 8)
  for slot, (prompt, out) in enumerate(zip(prompts, outs)):
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])
    for at in (len(prompt) - 1, len(seq) - 2):
      want = _ReferenceLogits(theta, seq, at)
      np.testing.assert_allclose(seen[slot, at], want, atol=_LOGIT_TOL,
                                 err_msg=f"row {slot} position {at}")
      assert int(want.argmax()) == seq[at + 1]
  kv = eng.Stats()["kv_pages"]
  if max(_PROMPTS[case]) > _WINDOW + 16:
    assert kv["window_pages_released"] > 0
  else:
    assert kv["window_pages_released"] == 0
  assert kv["kinds"]["window"]["in_use"] == kv["kinds"]["full"]["in_use"] == 0


def test_bf16_weights_fail_the_tolerance(tiny):
  """The tolerance separates f32 from the nearest precision below it: the
  engine serving the weights rounded to bf16 reads a hundred times over."""
  task, theta = tiny
  rounded = jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16).astype(x.dtype), theta)
  prompt = np.random.RandomState(5).randint(1, 128, 40).astype(np.int32)
  _, seen, outs = _Serve(task, rounded, [prompt], 2)
  seq = np.concatenate([prompt, np.asarray(outs[0], np.int32)])
  diff = np.abs(seen[0, 39] - _ReferenceLogits(theta, seq, 39)).max()
  assert diff > 10 * _LOGIT_TOL, diff


@pytest.mark.parametrize("case", ["longer_than_three_windows",
                                  "both_in_one_step"])
def test_released_window_pages_are_never_read(tiny, case):
  """With NaN in every page of the window kind that no live row may read,
  after every step, the engine streams the same tokens and its logits stay
  finite: a released page is behind every query that follows."""
  task, theta = tiny
  rng = np.random.RandomState(5)
  prompts = [rng.randint(1, 128, n).astype(np.int32) for n in _PROMPTS[case]]
  _, _, clean = _Serve(task, theta, prompts, 8)
  _, seen, outs = _Serve(task, theta, prompts, 8, poison=True)
  assert outs == clean
  assert all(np.isfinite(v).all() for v in seen.values())


def _BlockFill(task, eng):
  """The block-fill entry of the first attention mixer's counting method
  (ragged.StepCount), at the engine's geometry."""
  mixer = task.stack.MixerLayers()[0][0]
  (fill, _) = mixer.StepCounts(ragged_lib.StepGeometry(
      eng.page_size, None, eng.max_batch, eng._ragged_t,
      eng.sched.table_pages), 1)
  assert fill.names == ("attend_query_blocks", "attend_block_queries",
                        "attend_block_rows")
  return fill


def test_one_pool_of_uniform_pages_for_both_kinds(tiny):
  task, theta = tiny
  eng = engine_lib.ServingLoop(task, theta, page_size=8, num_pages=48,
                               max_batch=2, max_seq_len=128,
                               prefill_token_budget=16)
  kp = eng._kind_pages
  assert kp.windows == (0, _WINDOW, _WINDOW, _WINDOW) and kp.alloc is eng.alloc
  # the bytes of 48 pages at all four layers: 192 pages of one layer each
  assert eng.alloc.num_pages == 48 * 4
  pools = [tuple(x.shape) for x in jax.tree_util.tree_leaves(eng._states)
           if x.ndim == 5]
  assert pools == [(1, 48 * 4 + 1, 8, 2, 16)] * 2         # K and V, once
  kv = eng.Stats()["kv_pages"]
  assert kv["num_pages"] == 192 and kv["window_cap_pages"] == 6
  assert set(kv["kinds"]) == {"full", "window"}
  # a KV head's group of three query heads rides the packed axis: a
  # one-token row is three of its block's queries
  assert _BlockFill(task, eng).count(np.zeros(1, np.int64),
                                     np.ones(1, np.int64))[:2] == (1, 3)


@pytest.mark.parametrize("kw,names", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"scheduler_mode": "priority"}, "priority"),
    ({"spec": spec_decode.SelfDraft(k=2, num_layers=4)}, "draft source"),
])
def test_paths_of_one_block_table_refuse_two_kinds(tiny, kw, names):
  task, theta = tiny
  with pytest.raises(ValueError, match="two kinds") as e:
    engine_lib.ServingLoop(task, theta, page_size=8, num_pages=48,
                           max_batch=2, max_seq_len=128, **kw)
  assert names in str(e.value) and "[0, 24, 24, 24]" in str(e.value)


def test_engine_counts_expert_load(tiny):
  task, theta = tiny
  eng = engine_lib.ServingLoop(task, theta, page_size=8, num_pages=48,
                               max_batch=2, max_seq_len=128,
                               prefill_token_budget=16)
  handle = eng.Submit(np.arange(1, 31, dtype=np.int32), 4)
  while not handle.done:
    eng.StepOnce()
  st = eng.Stats()
  tokens = st["prompt_tokens"] + st["tokens_emitted"] - 1
  layers = 4
  assert st["moe_tokens_routed"] == tokens * _TOP_K * layers
  assert st["moe_expert_load_mean"] == pytest.approx(
      st["moe_tokens_routed"] / 8)
  assert st["moe_expert_load_max"] * 8 >= st["moe_tokens_routed"]
  assert 0 < st["moe_experts_active"] <= st["steps"] * layers * 8
  records = [r for r in eng.trace.Steps() if r.counters]
  assert records and records[-1].counters["window_pages_allocated"] > 0
  assert "moe_tokens_routed" in records[-1].counters


def test_engine_counts_the_rows_a_block_runs(tiny):
  """`attend_block_rows` beside the block-fill counters: a request alone is
  one block a step, a chunk of 16 or 14 tokens (128 or 112 laid queries)
  runs the block's bound and a decode token its group's 8 rows, by the
  ladder the kernel itself reads (`BlockRungs`)."""
  task, theta = tiny
  eng = engine_lib.ServingLoop(task, theta, page_size=8, num_pages=48,
                               max_batch=2, max_seq_len=128,
                               prefill_token_budget=16)
  handle = eng.Submit(np.arange(1, 31, dtype=np.int32), 4)
  while not handle.done:
    eng.StepOnce()
  st = eng.Stats()
  # a one-token row: a block, the token's own queries, the rows it runs
  _, own, laid = _BlockFill(task, eng).count(np.zeros(1, np.int64),
                                             np.ones(1, np.int64))
  bq = eng._attend_bq
  assert (laid, bq) == (8, 512)
  assert rba.BlockRungs(bq, laid) == (laid, bq)
  assert st["attend_query_blocks"] == st["steps"] == 5
  assert st["attend_block_queries"] == (30 + 3) * own
  assert st["attend_block_rows"] == 2 * bq + 3 * laid
  assert (st["attend_block_queries"] * laid // own
          <= st["attend_block_rows"] < st["attend_query_blocks"] * bq)


def test_layer_pattern_is_data():
  """The period is read off the two layouts; a stack that is all one kind
  is the plain body, and the dense decode paths refuse what only the ragged
  step serves."""
  mp = model_registry.GetParams("lm.smallthinker.SmallThinkerTiny", "Train")
  tp = mp.task
  tp.input = mp.input
  task = tp.Instantiate()
  body = task.stack.body.x_layers
  assert [(l.self_atten.atten.p.window,
           l.self_atten.atten.p.use_rotary_position_emb) for l in body] == [
               (0, False), (24, True), (24, True), (24, True)]
  assert task.stack.p.num_layers == 1 and "head" in task.children
  plain = tp.Copy().Set(sliding_window_layout=[0], rope_layout=[1],
                        num_layers=4)
  assert not hasattr(plain.Instantiate().stack.body, "x_layers")
  with pytest.raises(NotImplementedError, match="num_kv_heads=2"):
    body[1].self_atten.atten.InitStates(NestedMap(), 1, 8)


# -- what `correct` stands on: routing two precisions decide alike -----------


def test_routers_that_read_unwritten_dimensions_route_by_the_token(tiny):
  """SeededWeights(router_reads_share=...): no layer writes the dimensions
  the routers read, so a token's router logits are its embedding's in every
  layer, to the last bit of an f32 dot, whatever came before it and whether
  the rest of the weights are f32 or rounded to bf16: a near-tie between its
  k-th and (k + 1)-th expert cannot be decided differently by two
  precisions (PERF.md section 6, PR 35)."""
  task, theta = tiny
  routed = ref.SeededWeights(theta, router_scale=50.0, router_reads_share=0.1,
                             window=_WINDOW, experts_per_token=_TOP_K)
  d = routed.emb.emb.shape[1]
  reads = int(d * 0.1)
  body = routed.stack.body.x_layers
  for layer in body:
    assert not np.asarray(layer.self_atten.atten.w_post)[:, :reads].any()
    assert not np.asarray(layer.fflayer.w_down)[..., :reads].any()
    assert not np.asarray(layer.fflayer.w_router)[:, reads:].any()
    assert np.asarray(layer.fflayer.w_router)[:, :reads].any()
  ids = np.zeros((2, 128), np.int32)
  rng = np.random.RandomState(3)
  ids[0, :60], ids[1, :60] = rng.randint(1, 128, 60), rng.randint(1, 128, 60)
  ids[1, 59] = ids[0, 59]                     # other contexts, the same token
  at = jnp.asarray([59, 59], jnp.int32)
  routes = jax.jit(ref.RouterLogitsAt)(routed, jnp.asarray(ids), at)
  np.testing.assert_array_equal(routes[0], routes[1])
  emb = np.asarray(routed.emb.emb)[ids[0, 59], :reads]
  want = np.stack([emb @ np.asarray(l.fflayer.w_router)[0, :reads]
                   for l in body])
  np.testing.assert_allclose(routes[0], want, rtol=1e-5, atol=1e-7)
  rounded = jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16).astype(x.dtype), routed)
  again = jax.jit(ref.RouterLogitsAt)(rounded, jnp.asarray(ids), at)
  np.testing.assert_array_equal(again[0], again[1])
  # and the logits stay what the model's logits are: finite, not the same
  assert np.isfinite(np.asarray(jax.jit(
      lambda th, i, a: ref.LogitsAt(th, i, a))(routed, jnp.asarray(ids), at))
                     ).all()


@pytest.mark.parametrize("control,correct", [("none", True),
                                              ("best_expert", False)])
def test_controls_of_the_expert_cell_rehearsed(control, correct, tmp_path,
                                               capsys):
  """benchmarks/tools/moe_controls.py on the CPU at the rehearsal's sizes: a
  sound run is correct with the program's top-k sets the reference's in
  every layer, and a token sent past its best expert is not. (The other
  controls need the chip's sizes to show: PERF.md section 6, PR 35.)"""
  import json
  from benchmarks.tools import moe_controls
  rc = moe_controls.main([
      "--workload", "smallthinker21b_serve_mixed", "--seed", "3500000777",
      "--control", control, "--seconds", "3", "--rehearse",
      "--out", str(tmp_path)])
  line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert rc == 0 and line["correct"] is correct and line["control"] == control
  rows = line["routing"]["rows"]
  assert rows and len(line["routing"]["layers"]) == 4
  if control != "best_expert":
    assert all(not r["layers_flipped"] for r in rows)
  else:
    assert all(len(r["layers_flipped"]) == 4 for r in rows)


def test_int8_pages_under_kv_groups_are_refused(tiny):
  task, theta = tiny
  with pytest.raises(NotImplementedError, match="int8 KV pages under "
                     "num_kv_heads=2 of 6"):
    engine_lib.ServingLoop(task, theta, page_size=8, num_pages=48,
                           max_batch=2, max_seq_len=128,
                           kv_cache_dtype="int8")
  with pytest.raises(NotImplementedError, match="KV heads"):
    rba.RaggedAttend(
        jnp.zeros((4, 6, 128)), jnp.zeros((3, 8, 2, 128), jnp.int8),
        jnp.zeros((3, 8, 2, 128), jnp.int8), jnp.zeros((1, 2), jnp.int32),
        jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.int32), page_size=8,
        k_scale=jnp.ones((3, 2, 8)), v_scale=jnp.ones((3, 2, 8)),
        lowering="pallas")


# -- a layer that holds a share of the experts its router scores ---------------


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_the_tiny_presets_experts_add_up_to_the_reference(
    tiny, shares, monkeypatch):
  """One of `shares` chips that share a layer holds a contiguous run of its
  experts and the router's whole width (SmallThinkerTiny's expert layer: 8
  ReGLU experts top-2, logits handed in): what each share adds to the stream,
  summed over the shares, is the uncut layer's routed sum as the reference
  computes it, and the weights of a token's two stay normalised over both
  wherever the two live. Padding tokens count nowhere; a share's `routed` and
  `elsewhere` are the numpy counts."""
  task, _ = tiny
  tpl = task.stack.body.x_layers[0].fflayer.p.Copy()
  e, k, d = tpl.num_experts, tpl.num_experts_per_token, tpl.input_dim
  whole = tpl.Copy().Set(name="moe").Instantiate()
  whole.FinalizePaths()
  theta = whole.InstantiateVariables(jax.random.PRNGKey(5))
  rng = np.random.RandomState(shares)
  t = 21
  x = jnp.asarray(rng.randn(t, d), jnp.float32)
  logits = jnp.asarray(rng.randn(t, e), jnp.float32)
  valid = np.ones(t, bool)
  valid[[3, 20]] = False
  rows = ragged_lib.RaggedRows(*(jnp.asarray(a) for a in
                                 ragged_lib.BuildRaggedRows([t], [0], t, t)))
  rows = rows._replace(valid=jnp.asarray(valid))
  monkeypatch.setattr(ref, "_PIECE", 4)
  monkeypatch.setitem(ref._ARCH, "experts_per_token", k)
  monkeypatch.setitem(ref._ARCH, "eps", float(tpl.norm_tpl.epsilon))
  ff = jax.tree_util.tree_map(lambda a: a[None], dict(theta))
  want = ref._Experts(ff, 0, ref._RmsNorm(x, theta.ln.scale), logits)
  top = np.argsort(-np.asarray(logits), -1)[:, :k][valid]
  held = e // shares
  total = jnp.zeros_like(x)
  for s in range(shares):
    layer = tpl.Copy().Set(name="moe", first_expert=s * held,
                           num_experts_held=held).Instantiate()
    layer.FinalizePaths()
    mine = theta.Copy()
    for name in layer.StackAddressed():
      mine[name] = theta[name][s * held:(s + 1) * held]
    assert jax.tree_util.tree_map(lambda a: a.shape, dict(mine)) == (
        jax.tree_util.tree_map(lambda a: a.shape, dict(
            layer.InstantiateVariables(jax.random.PRNGKey(0)))))
    out, states = jax.jit(lambda th, x, r, layer=layer: layer.RaggedStep(
        th, x[None], layer.InitPagedStates(th), rows,
        router_logits=r[None]))(mine, x, logits)
    total = total + (out[0] - x)
    here = (top >= s * held) & (top < (s + 1) * held)
    np.testing.assert_array_equal(
        np.asarray(states.routed),
        np.bincount(top[here] - s * held, minlength=held))
    assert int(states.elsewhere) == int((~here).sum())
  np.testing.assert_allclose(np.asarray(total)[valid], np.asarray(want)[valid],
                             atol=1e-5)
  # the layer that holds all of them keeps the state it had
  assert "elsewhere" not in whole.InitPagedStates(theta)
