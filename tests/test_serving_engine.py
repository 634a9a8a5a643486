"""Continuous-batching serving engine: scheduler + KV block tables over
ragged paged decode.

Covers docs/serving_engine.md:
- the block-table paged decode kernel's XLA twin matches a dense softmax
  reference across ragged lengths (0, mid-page, capacity boundary) and is
  bit-identical to the Pallas kernel in interpret mode — including after
  pages are freed and reallocated to a different sequence,
- `BlockPrefill` matches the dense reference at arbitrary (q_pos, in_len)
  and returns exactly 0 for invalid queries,
- `PagedStep` chunked-prefill + decode reproduces the dense
  Prefill/ExtendStep logits on a left-aligned row,
- the page allocator packs low (min-heap), is all-or-nothing, idempotent
  on Free, and tracks peak occupancy,
- the scheduler's admit/prefill/decode/retire lifecycle (driven with
  fabricated sample arrays, no device), cancellation at both lifecycle
  stages, and graceful queueing on pool exhaustion,
- `ServingLoop.RunBatch` is token-identical to per-row dense greedy decode
  AND to batch-synchronous `GShardDecode.DecodeOnce`, with pages fully
  reclaimed after the batch drains,
- the async Submit/stream/Cancel front door, ineligible-config dense
  fallback visibility (`paged_path`, `dense_fallback_steps`), GShardDecode
  per-call telemetry, and a deterministic mixed-length soak (slow).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu.core import sampling
from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.ops import block_decode
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import scheduler as scheduler_lib


# -- shared tiny LM (session-scoped `tiny_lm` fixture: conftest.py) ----------

from tests.conftest import TinyLmParams as _TinyLmParams  # noqa: E402


# one jitted ExtendStep per task and one memoized rollout per prompt: the
# whole file shares a single compiled reference program (fixed 32-slot
# cache; unwritten tail slots are position-masked, so length is free)
_REF_TOKENS = {}
_REF_EXT = {}
_REF_CACHE_LEN = 32


def _Rollout(task, theta, prompt, max_new, pick):
  """Per-row dense rollout (per-token ExtendStep): shares no scheduler,
  allocator, page pool or packed step with the engine it judges.
  pick(logits [1, V], t) chooses output token t."""
  ext = _REF_EXT.get(id(task))
  if ext is None:
    ext = jax.jit(
        lambda th, ids_t, st: task.ExtendStep(th, ids_t, st))
    _REF_EXT[id(task)] = ext
  assert len(prompt) + max_new <= _REF_CACHE_LEN
  states = task.InitDecodeState(theta, 1, _REF_CACHE_LEN)
  logits = None
  for t in prompt:
    logits, states = ext(theta, jnp.asarray([[t]], jnp.int32), states)
  out = []
  for t in range(max_new):
    nxt = pick(logits, t)
    out.append(nxt)
    logits, states = ext(theta, jnp.asarray([[nxt]], jnp.int32), states)
  return out


def _GreedyRef(task, theta, prompt, max_new):
  """The rollout's argmax stream: the batch-free reference every greedy
  engine output must match token-for-token."""
  key = (id(task), id(theta), tuple(int(t) for t in prompt), max_new)
  if key not in _REF_TOKENS:
    _REF_TOKENS[key] = _Rollout(
        task, theta, prompt, max_new,
        lambda logits, t: int(np.argmax(np.asarray(logits[0]))))
  return _REF_TOKENS[key]


def _SampledRef(task, theta, prompt, max_new, *, seed, sample_seed,
                temperature, top_k=0):
  """The rollout at temperature > 0: output token t of a request with seed
  s is a pure function of (engine sample_seed, s, t) — the engine's
  sampling contract, whichever slot, step or neighbors it decoded with."""
  key = jax.random.PRNGKey(sample_seed)
  seeds = jnp.asarray([seed], jnp.int32)

  def _Draw(logits, t):
    return int(sampling.SampleFromLogits(
        logits, key, temperature=temperature, top_k=top_k, row_seeds=seeds,
        positions=jnp.asarray([t], jnp.int32))[0])

  return _Rollout(task, theta, prompt, max_new, _Draw)


# -- kernel twins ------------------------------------------------------------


class TestBlockDecodeKernel:

  def _Inputs(self, b=4, t_pages=4, page=4, n=2, h=8, seed=0,
              extra_pages=1):
    rng = np.random.RandomState(seed)
    np_total = b * t_pages + extra_pages
    q = rng.randn(b, 1, n, h).astype(np.float32)
    k_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    v_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    # arbitrary disjoint physical pages per row — NOT identity, so a kernel
    # that ignores the table cannot pass
    tables = rng.permutation(np_total - extra_pages).reshape(
        b, t_pages).astype(np.int32)
    return q, k_pool, v_pool, tables

  @staticmethod
  def _DenseRef(q, k_pool, v_pool, tables, lens):
    """numpy masked softmax over the gathered dense view."""
    b, _, n, h = q.shape
    page = k_pool.shape[1]
    out = np.zeros_like(q)
    for i in range(b):
      ln = int(lens[i])
      if ln == 0:
        continue
      k = k_pool[tables[i]].reshape(-1, n, h)[:ln]        # [ln, N, H]
      v = v_pool[tables[i]].reshape(-1, n, h)[:ln]
      s = np.einsum("nh,snh->ns", q[i, 0], k)             # [N, ln]
      s = s - s.max(axis=-1, keepdims=True)
      p = np.exp(s)
      p /= p.sum(axis=-1, keepdims=True)
      out[i, 0] = np.einsum("ns,snh->nh", p, v)
    return out

  def test_xla_twin_matches_dense_reference(self):
    q, k_pool, v_pool, tables = self._Inputs()
    # 0 = inactive row, 3 = inside page 0, 9 = mid page 2, 16 = capacity
    lens = np.array([0, 3, 9, 16], np.int32)
    out = block_decode.BlockDecode(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), page_size=4, lowering="xla")
    ref = self._DenseRef(q, k_pool, v_pool, tables, lens)
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-6)
    # the len-0 row is exactly zero, not NaN
    np.testing.assert_array_equal(np.asarray(out[0]), np.zeros_like(q[0]))

  def test_stale_table_entries_never_leak(self):
    """Entries past a row's live pages may point anywhere (freed/foreign
    pages); they must not change the output."""
    q, k_pool, v_pool, tables = self._Inputs()
    lens = np.array([3, 4, 5, 8], np.int32)   # nobody uses pages 2..3
    out1 = block_decode.BlockDecode(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), page_size=4, lowering="xla")
    hostile = tables.copy()
    hostile[:, 2:] = np.arange(8).reshape(4, 2)   # alias other rows' pages
    out2 = block_decode.BlockDecode(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(hostile), jnp.asarray(lens), page_size=4, lowering="xla")
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

  def test_twins_bitwise_equal_incl_page_reuse(self):
    """XLA == Pallas(interpret) bitwise, before AND after the allocator
    frees one sequence's pages and hands them to another (the pool bytes
    are overwritten in place — exactly what eviction + admission does)."""
    q, k_pool, v_pool, tables = self._Inputs(b=2, t_pages=2, page=8, n=1,
                                             h=8)
    k_pool = jnp.asarray(k_pool)
    v_pool = jnp.asarray(v_pool)
    lens = np.array([5, 16], np.int32)

    def _Both(kp, vp, tb, ln):
      out_x = block_decode.BlockDecode(
          jnp.asarray(q), kp, vp, jnp.asarray(tb), jnp.asarray(ln),
          page_size=8, lowering="xla")
      out_p = block_decode.BlockDecode(
          jnp.asarray(q), kp, vp, jnp.asarray(tb), jnp.asarray(ln),
          page_size=8, lowering="pallas", interpret=True)
      np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_p))
      return np.asarray(out_x)

    _Both(k_pool, v_pool, tables, lens)

    # retire row 0 through a real allocator; its pages go to a new sequence
    alloc = kv_cache.PageAllocator(num_pages=4, page_size=8)
    alloc.Allocate("a", 2)
    alloc.Allocate("b", 2)
    assert sorted(alloc.PagesOf("a") + alloc.PagesOf("b")) == [0, 1, 2, 3]
    alloc.Free("a")
    reused = alloc.Allocate("c", 2)
    assert reused == [0, 1]   # min-heap: the freed low pages come back first
    rng = np.random.RandomState(7)
    for pg in reused:   # the new sequence overwrites the reused pages
      k_pool = k_pool.at[pg].set(rng.randn(8, 1, 8).astype(np.float32))
      v_pool = v_pool.at[pg].set(rng.randn(8, 1, 8).astype(np.float32))
    tables2 = np.array([reused, list(alloc.PagesOf("b"))], np.int32)
    out = _Both(k_pool, v_pool, tables2, np.array([12, 16], np.int32))
    ref = self._DenseRef(np.asarray(q), np.asarray(k_pool),
                         np.asarray(v_pool), tables2,
                         np.array([12, 16], np.int32))
    np.testing.assert_allclose(out, ref, atol=5e-6)

  @pytest.mark.slow
  def test_pallas_interpret_bitwise_sweep(self):
    """Twin equality across the length grid incl. 0 and capacity."""
    q, k_pool, v_pool, tables = self._Inputs(b=4, t_pages=2, page=8, n=1,
                                             h=8)
    for lens in ([0, 1, 8, 16], [16, 16, 16, 16], [0, 0, 0, 0],
                 [7, 9, 15, 3]):
      ln = np.asarray(lens, np.int32)
      out_x = block_decode.BlockDecode(
          jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
          jnp.asarray(tables), jnp.asarray(ln), page_size=8, lowering="xla")
      out_p = block_decode.BlockDecode(
          jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
          jnp.asarray(tables), jnp.asarray(ln), page_size=8,
          lowering="pallas", interpret=True)
      np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_p))

  def test_block_prefill_matches_dense_reference(self):
    b, c, n, h, page, t_pages = 3, 4, 2, 8, 4, 4
    rng = np.random.RandomState(3)
    np_total = b * t_pages + 1
    q = rng.randn(b, c, n, h).astype(np.float32)
    k_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    v_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    tables = rng.permutation(np_total - 1).reshape(b, t_pages).astype(
        np.int32)
    q_pos = np.array([0, 5, 9], np.int32)
    in_len = np.array([4, 3, 0], np.int32)   # row 2 is a dead row
    out = block_decode.BlockPrefill(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(q_pos), jnp.asarray(in_len),
        page_size=page)
    out = np.asarray(out)
    for i in range(b):
      k = k_pool[tables[i]].reshape(-1, n, h)
      v = v_pool[tables[i]].reshape(-1, n, h)
      for ci in range(c):
        if ci >= in_len[i]:   # invalid query: exactly zero
          np.testing.assert_array_equal(out[i, ci], np.zeros((n, h),
                                                             np.float32))
          continue
        end = int(q_pos[i]) + ci + 1     # attends slots <= q_pos + ci
        s = np.einsum("nh,snh->ns", q[i, ci], k[:end])
        s = s - s.max(axis=-1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(axis=-1, keepdims=True)
        ref = np.einsum("ns,snh->nh", p, v[:end])
        np.testing.assert_allclose(out[i, ci], ref, atol=5e-6)


# -- PagedStep vs the dense decode path --------------------------------------


class TestPagedStepParity:

  def test_chunked_prefill_plus_decode_matches_dense(self, tiny_lm):
    """One left-aligned row through PagedStep (prefill chunks 4+2, then 3
    decode steps) reproduces dense Prefill/ExtendStep logits."""
    task, theta = tiny_lm
    prompt = [5, 9, 2, 33, 17, 4]
    page = 4
    paged_fn = jax.jit(task.PagedStep)
    dense_ext = jax.jit(lambda th, ids_t, st: task.ExtendStep(th, ids_t, st))
    tables = jnp.asarray([[0, 1, 2]], jnp.int32)      # capacity 12 slots
    states = task.InitPagedDecodeState(theta, 4, page)  # 3 pages + trash
    logits_paged = []
    pos = 0
    for chunk in ([5, 9, 2, 33], [17, 4]):
      ids = jnp.asarray([chunk + [0] * (4 - len(chunk))], jnp.int32)
      lg, states = paged_fn(theta, ids, states, tables,
                            jnp.asarray([pos], jnp.int32),
                            jnp.asarray([len(chunk)], jnp.int32))
      logits_paged.append(np.asarray(lg[0, :len(chunk)]))
      pos += len(chunk)
    paged_prompt_logits = np.concatenate(logits_paged, 0)   # [6, V]

    dense_states = task.InitDecodeState(theta, 1, len(prompt) + 3)
    dense_logits, dense_states = jax.jit(task.Prefill)(
        theta, jnp.asarray([prompt], jnp.int32), dense_states)
    np.testing.assert_allclose(paged_prompt_logits,
                               np.asarray(dense_logits[0]), atol=2e-5)

    nxt = int(np.argmax(paged_prompt_logits[-1]))
    for _ in range(3):
      lg, states = paged_fn(
          theta, jnp.asarray([[nxt]], jnp.int32), states, tables,
          jnp.asarray([pos], jnp.int32), jnp.asarray([1], jnp.int32))
      dl, dense_states = dense_ext(
          theta, jnp.asarray([[nxt]], jnp.int32), dense_states)
      np.testing.assert_allclose(np.asarray(lg[0, 0]), np.asarray(dl[0]),
                                 atol=2e-5)
      pos += 1
      nxt = int(np.argmax(np.asarray(lg[0, 0])))


# -- page allocator ----------------------------------------------------------


class TestPageAllocator:

  def test_packs_low_and_reuses_freed_pages_first(self):
    a = kv_cache.PageAllocator(num_pages=8, page_size=4)
    assert a.Allocate("x", 3) == [0, 1, 2]
    assert a.Allocate("y", 2) == [3, 4]
    a.Free("x")
    # freed low pages sink to the front of the heap: defrag by construction
    assert a.Allocate("z", 4) == [0, 1, 2, 5]
    assert a.num_free == 2 and a.num_in_use == 6

  def test_all_or_nothing_exhaustion(self):
    a = kv_cache.PageAllocator(num_pages=4, page_size=4)
    a.Allocate("x", 3)
    assert not a.CanAllocate(2)
    with pytest.raises(kv_cache.OutOfPages):
      a.Allocate("y", 2)
    # the failed call had no side effects
    assert a.num_free == 1 and "y" not in a._owned
    assert a.Allocate("y", 1) == [3]

  def test_free_is_idempotent_and_peak_tracks(self):
    a = kv_cache.PageAllocator(num_pages=4, page_size=4)
    a.Allocate("x", 4)
    assert a.peak_in_use == 4
    assert a.Free("x") == 4
    assert a.Free("x") == 0        # second free: no-op
    assert a.Free("never-seen") == 0
    assert a.num_free == 4
    assert a.peak_in_use == 4      # peak survives the drain
    assert a.Stats()["utilization"] == 0.0

  def test_pages_for_rounds_up(self):
    a = kv_cache.PageAllocator(num_pages=4, page_size=4)
    assert [a.PagesFor(n) for n in (1, 4, 5, 8, 9)] == [1, 1, 2, 2, 3]


# -- scheduler lifecycle (device-free) ---------------------------------------


def _MakeSched(slots=2, pages=8, page=4, table_pages=4):
  alloc = kv_cache.PageAllocator(pages, page)
  return scheduler_lib.Scheduler(slots, alloc, table_pages), alloc


# the packed axis of the device-free steps: 2 decode tokens + a prefill
# budget of 4, the widest row 4
_T, _WMAX = 6, 4


def _Drive(sched, sampled_tok=7):
  """One admit → build → fabricated-sample → commit iteration."""
  sched.EvictCancelled()
  sched.Admit()
  batch = sched.BuildRaggedStep(_T, _WMAX)
  if batch is None:
    return None, []
  sampled = np.full((_T,), sampled_tok, np.int32)
  return batch, sched.CommitRaggedStep(batch, sampled)


class TestScheduler:

  def test_prefill_to_decode_to_length_finish(self):
    sched, alloc = _MakeSched()
    sched.Submit(scheduler_lib.Request("a", [1, 2, 3, 4, 5], 2))
    # step 1: mixed step consumes the first chunk (4 of 5 prompt tokens)
    batch, events = _Drive(sched)
    assert batch.mixed and list(batch.rows_desc.row_len) == [4, 0]
    assert batch.prompt_tokens == 4 and events == []
    # step 2: last prompt token -> first sampled token
    batch, events = _Drive(sched)
    assert batch.rows_desc.row_len[0] == 1 and events == [("a", 7, False)]
    assert sched._by_id["a"].state is scheduler_lib.SeqState.DECODE
    # step 3: pure decode step (one token a row) hits max_new -> retire
    batch, events = _Drive(sched)
    assert not batch.mixed and list(batch.rows_desc.row_len) == [1, 0]
    # feeds back the last sampled token
    assert batch.tok_ids[batch.rows_desc.row_cols[0, 0]] == 7
    assert events == [("a", 7, True)]
    assert sched._by_id["a"].finish_reason == "length"
    assert alloc.num_free == alloc.num_pages
    assert sched.slots == [None, None]

  def test_eos_finishes_early(self):
    sched, alloc = _MakeSched()
    sched.Submit(scheduler_lib.Request("a", [1, 2], 10, eos_id=7))
    _, events = _Drive(sched, sampled_tok=7)
    assert events == [("a", 7, True)]
    assert sched._by_id["a"].finish_reason == "eos"
    assert alloc.num_free == alloc.num_pages

  def test_pool_exhaustion_queues_gracefully(self):
    # each request needs 2 pages; the 8-page pool holds 4 but only 2 slots
    sched, alloc = _MakeSched(slots=2, pages=3)
    for rid in ("a", "b", "c"):
      sched.Submit(scheduler_lib.Request(rid, [1, 2, 3, 4], 4))
    sched.Admit()
    # only "a" fits (2 pages); "b" head-of-line blocks on the last page
    assert [s and s.id for s in sched.slots] == ["a", None]
    assert [s.id for s in sched.waiting] == ["b", "c"]
    assert sched.Stats()["queue_depth"] == 2
    while sched._by_id["a"].state is not scheduler_lib.SeqState.FINISHED:
      _Drive(sched)
    # "a" freed its pages; "b" admitted on the very next boundary
    sched.Admit()
    assert any(s and s.id == "b" for s in sched.slots)

  def test_overlong_request_rejected(self):
    sched, _ = _MakeSched(table_pages=2)   # capacity 8 slots
    with pytest.raises(ValueError):
      sched.Submit(scheduler_lib.Request("a", [1] * 6, 4))
    assert sched.rejected_overlong == 1

  def test_cancel_queued_and_cancel_midflight(self):
    sched, alloc = _MakeSched()
    sched.Submit(scheduler_lib.Request("a", [1, 2], 8))
    sched.Submit(scheduler_lib.Request("b", [3, 4], 8))
    # queued cancel: retires immediately, never occupies a slot
    assert sched.Cancel("b")
    assert sched._by_id["b"].state is scheduler_lib.SeqState.CANCELLED
    assert not sched.Cancel("b")   # double-cancel: no
    _Drive(sched)                  # "a" now mid-flight (decoding)
    assert sched.Cancel("a")
    assert alloc.num_in_use > 0    # pages return at the boundary, not now
    evicted = sched.EvictCancelled()
    assert [s.id for s in evicted] == ["a"]
    assert alloc.num_free == alloc.num_pages
    assert sched.Stats()["cancelled"] == 2
    assert not sched.HasWork()

  def test_block_tables_rewritten_only_on_admit(self):
    sched, alloc = _MakeSched(slots=2, pages=8)
    sched.Submit(scheduler_lib.Request("a", [1, 2, 3, 4], 4))
    sched.Admit()
    row0 = sched.block_tables[0].copy()
    assert list(row0[:2]) == alloc.PagesOf("a")
    _Drive(sched)
    np.testing.assert_array_equal(sched.block_tables[0], row0)


# -- serving engine ----------------------------------------------------------


def _MakeEngine(task, theta, **kw):
  kw.setdefault("page_size", 4)
  kw.setdefault("num_pages", 16)
  kw.setdefault("max_batch", 4)
  kw.setdefault("max_seq_len", 32)
  kw.setdefault("prefill_chunk", 4)
  kw.setdefault("default_max_new", 6)
  return engine_lib.ServingLoop(task, theta, **kw)


class TestServingEngine:

  def test_runbatch_token_identical_to_dense_greedy(self, tiny_lm):
    task, theta = tiny_lm
    eng = _MakeEngine(task, theta)
    prompts = np.zeros((4, 11), np.int32)
    rows = [[5, 9, 2, 33, 17, 4, 8, 1, 60, 3, 12], [7, 7, 7],
            [1, 2, 3, 4, 5, 6, 7], [44, 21, 9, 9, 2]]
    lens = np.array([len(r) for r in rows], np.int32)
    for i, r in enumerate(rows):
      prompts[i, :len(r)] = r
    out = eng.RunBatch(prompts, lens, 6)
    for i, r in enumerate(rows):
      assert list(out[i]) == _GreedyRef(task, theta, r, 6), f"row {i}"
    # the batch drained: every page is back, counters moved
    stats = eng.Stats()
    assert stats["kv_pages"]["free"] == eng.num_pages
    assert stats["kv_pages"]["peak_in_use"] > 0
    assert stats["scheduler"]["finished"] == 4
    assert stats["mixed_steps"] > 0 and stats["decode_steps"] > 0
    assert stats["tokens_emitted"] == 24
    assert stats["prompt_tokens"] == int(lens.sum())
    assert stats["paged_path"] == (
        "pallas" if jax.default_backend() == "tpu" else "xla")
    assert stats["dense_fallback_steps"] == 0

  @pytest.mark.parametrize("family", ["dense", "nemotron_h", "brumby"])
  def test_start_builds_the_step_program_in_its_callers_thread(self, family):
    """`Start()` builds the step program and the feed before the loop's
    thread exists (the loop's own thread built them at its first dispatch,
    beside the thread that waits for it: PERF.md section 6, PR 51), from an
    idle step's arguments; the steps that follow call that executable: no
    second build, no fall-back to the jitted function."""
    import threading
    import time
    from tests.test_head_cols import _FAMILIES
    task, theta = _FAMILIES[family](jnp.float32)
    eng = engine_lib.ServingLoop(
        task, theta, page_size=8, num_pages=48, max_batch=4, max_seq_len=128,
        prefill_token_budget=8)
    built = []
    inner = eng._compile_log._Compile
    eng._compile_log._Compile = lambda name, fn, args: (
        built.append((name, threading.current_thread().name)),
        inner(name, fn, args))[1]
    assert eng.Stats()["compile"]["step_programs"] == 0
    eng.Start()
    try:
      me = threading.current_thread().name
      assert built == [("ragged", me), ("feed", me)]
      assert eng.Stats()["compile"]["step_programs"] == 1
      handle = eng.Submit(list(range(1, 12)), 6, eos_id=None, seed=3)
      deadline = time.monotonic() + 120
      while not handle.done and time.monotonic() < deadline:
        time.sleep(0.005)
      assert handle.done
    finally:
      eng.Stop()
    records = eng.Stats()["compile"]
    assert built == [("ragged", me), ("feed", me)]
    assert records["step_programs"] == 1
    for name in ("ragged", "feed"):
      assert "fallback" not in records[name], records[name]
      assert records[name]["calls"] > 0

  def test_narrow_steps_counts_the_steps_whose_tokens_fit(self, tiny_lm):
    """`narrow_steps` (docs/observability.md) is the count of dispatched steps
    with sum(row_len) <= W = max_batch, whatever kind of step: here
    decode-only steps, a prompt of 2 beside a decode row (a chunk that fits)
    and a prompt of 30 in chunks of 8 (which do not). One step program, and
    what it costs to trace stays under a stated ceiling."""
    from jax._src import monitoring
    task, theta = tiny_lm
    events = []

    def _OnDuration(event, secs, **_):
      del secs
      if event.startswith("/jax/core/compile/"):
        events.append(event)

    eng = engine_lib.ServingLoop(
        task, theta, page_size=8, num_pages=48, max_batch=4, max_seq_len=128,
        prefill_token_budget=8)
    mixes = []
    inner = eng._compile_log.Call

    def _Call(name, fn, *args):
      if name == "ragged":
        mixes.append(tuple(np.asarray(args[3].row_len).tolist()))
      return inner(name, fn, *args)

    eng._compile_log.Call = _Call
    monitoring.register_event_duration_secs_listener(_OnDuration)
    try:
      eng.Submit([5, 9, 2], 12, eos_id=None, seed=11)
      eng.StepOnce()                    # the step program is built here
    finally:
      monitoring.unregister_event_duration_listener(_OnDuration)
    eng.StepOnce()
    eng.Submit([7, 1], 8, eos_id=None, seed=12)
    eng.StepOnce()
    eng.Submit(list(range(1, 31)), 4, eos_id=None, seed=13)
    while eng.sched.HasWork():
      eng.StepOnce()
    stats = observe_schema.ValidateEngineStats(eng.Stats())
    w = 4
    assert (1, 2, 0, 0) in mixes                       # a chunk that fits
    assert any(max(m) == 8 for m in mixes)             # one that does not
    assert any(set(m) <= {0, 1} and sum(m) for m in mixes)  # decode only
    narrow = sum(sum(m) <= w for m in mixes)
    assert 0 < narrow < len(mixes) == stats["steps"]
    assert stats["narrow_steps"] == narrow
    assert eng.metrics.Snapshot()["serving/narrow_steps"] == narrow
    # `decode_steps` asks another question: the chunk that fits is mixed
    assert stats["narrow_steps"] > stats["decode_steps"]
    assert stats["compile"]["step_programs"] == 1
    # every trace, lowering and compile of the first step (a nested `jnp`
    # call at new shapes is an event): the conditionals trace the row-wise
    # blocks at two widths, which this count sees. 299 at PR 50 (246 before
    # it; 475 while a stack's slice was taken through `jnp` operators): a PR
    # that passes the ceiling has made every serve cell's set-up longer.
    assert 0 < len(events) <= 320, len(events)

  def test_page_reuse_across_batches_stays_identical(self, tiny_lm):
    """A second RunBatch on the same engine decodes into recycled pages;
    outputs must not change."""
    task, theta = tiny_lm
    eng = _MakeEngine(task, theta)
    prompts = np.array([[5, 9, 2, 33], [44, 21, 9, 9]], np.int32)
    lens = np.array([4, 4], np.int32)
    out1 = eng.RunBatch(prompts, lens, 6)
    out2 = eng.RunBatch(prompts, lens, 6)
    np.testing.assert_array_equal(out1, out2)

  def test_matches_batch_synchronous_gshard_decode(self, tmp_path):
    """The acceptance bar: continuous batching changes WHEN rows decode,
    never WHAT they decode — greedy tokens identical to GShardDecode."""
    from lingvo_tpu.core import checkpointer as checkpointer_lib
    from lingvo_tpu.runners import gshard_decode
    from lingvo_tpu import model_registry
    import lingvo_tpu.models.all_params  # noqa: F401

    mp = model_registry.GetParams("lm.synthetic_packed_input.DenseLmTiny",
                                  "Train")
    mp.task.input = mp.input
    task = mp.task.Instantiate()
    task.FinalizePaths()
    train_dir = str(tmp_path / "train")
    ckpt = checkpointer_lib.Checkpointer(train_dir)
    state = task.CreateTrainState(jax.random.PRNGKey(3))
    ckpt.Save(1, state, force=True)
    ckpt.Close()
    prompts = np.array([[5, 6, 7, 8], [9, 10, 0, 0], [11, 0, 0, 0]],
                       np.int32)
    lens = np.array([4, 2, 1], np.int32)

    driver = gshard_decode.GShardDecode(
        task, train_dir, str(tmp_path / "out.jsonl"), max_decode_steps=4)
    recs = driver.DecodeOnce(1, prompts, lens)
    telem = driver._last_telemetry
    assert telem is not None
    # the telemetry key set is single-sourced in observe/schema.py — the
    # exact-match assertion catches keys landing on only one surface
    assert set(telem) == set(observe_schema.GSHARD_TELEMETRY_KEYS)
    assert {"spec_branches", "spec_width_clamps",
            "accepted_depth_hist"} <= set(telem)
    # compiled-step-program census: one (p_len, t_max) bucket was used,
    # and this driver compiles a (prefill, sample) program pair per bucket
    assert telem["step_programs"] == 2
    # the telemetry dict is generated from observe.schema, so any key added
    # to one surface without the other fails here, not in a bench comparison
    assert list(telem) == list(observe_schema.GSHARD_TELEMETRY_KEYS)
    # both surfaces share the mirrored keys by construction
    assert observe_schema.SHARED_SERVING_KEYS <= set(telem)
    # batch-synchronous decode never speculates: the spec keys exist (the
    # engine-Stats mirror contract) but stay at their zero values
    assert telem["draft_tokens"] == 0
    assert telem["accepted_tokens"] == 0
    assert telem["accepted_len_hist"] == []
    # ...and never serves cached prefixes: same mirror contract
    assert telem["prefix_hit_tokens"] == 0
    assert telem["prefix_cache"]["enabled"] is False
    assert set(telem["prefix_cache"]) == (
        observe_schema.PREFIX_CACHE_STATS_KEYS)
    assert telem["prompt_tokens"] == 7 and telem["decode_tokens"] == 12
    assert telem["decode_state_bytes_per_seq"] > 0
    assert telem["tokens_per_sec"] > 0
    assert telem["kv_cache_dtype"] == "float32"
    assert telem["kv_bytes_per_token"] > 0
    assert telem["serve_int8_weights"] is False
    assert all(r["telemetry"] == telem for r in recs)

    eng = engine_lib.ServingLoop(
        task, state.theta, page_size=4, num_pages=8, max_batch=3,
        max_seq_len=8, prefill_chunk=4, default_max_new=4)
    out = eng.RunBatch(prompts, lens, 4)
    for i, rec in enumerate(recs):
      assert list(out[i]) == rec["output_ids"], f"row {i}"

  def test_async_submit_stream_and_stats(self, tiny_lm):
    task, theta = tiny_lm
    eng = _MakeEngine(task, theta).Start()
    try:
      h1 = eng.Submit([5, 9, 2, 33, 17], 6)
      h2 = eng.Submit([7, 7, 7], 6)
      streamed = list(h1.Tokens(timeout=30))
      assert streamed == h1.Result(timeout=30)
      assert h1.Result(timeout=30) == _GreedyRef(task, theta,
                                                 [5, 9, 2, 33, 17], 6)
      assert h2.Result(timeout=30) == _GreedyRef(task, theta, [7, 7, 7], 6)
      assert h1.finish_reason == "length" and h1.done
      assert h1.first_token_time is not None
      assert h1.finish_time >= h1.first_token_time >= h1.submit_time
    finally:
      eng.Stop()
    assert eng.Stats()["kv_pages"]["free"] == eng.num_pages

  def test_exhaustion_queues_and_all_finish(self, tiny_lm):
    """More requests than slots AND pages: later requests queue (never
    crash) and run when pages free up."""
    task, theta = tiny_lm
    eng = _MakeEngine(task, theta, num_pages=6, max_batch=2, max_seq_len=16)
    prompts = np.tile(np.array([[3, 1, 4]], np.int32), (5, 1))
    prompts += np.arange(5, dtype=np.int32)[:, None]   # distinct rows
    lens = np.full((5,), 3, np.int32)
    out = eng.RunBatch(prompts, lens, 5)
    for i in range(5):
      assert list(out[i]) == _GreedyRef(task, theta, list(prompts[i]), 5)
    stats = eng.Stats()
    assert stats["scheduler"]["finished"] == 5
    assert stats["kv_pages"]["free"] == 6

  def test_cancel_midstream_reclaims_pages(self, tiny_lm):
    task, theta = tiny_lm
    eng = _MakeEngine(task, theta, num_pages=8, max_batch=2).Start()
    try:
      h = eng.Submit([5, 9, 2], 24)
      it = h.Tokens(timeout=30)
      got = [next(it) for _ in range(3)]
      assert h.Cancel()
      rest = list(it)   # stream terminates promptly after the cancel
      assert h.finish_reason == "cancelled" and h.done
      assert len(got) + len(rest) < 24
      # a request submitted after the cancel still runs to completion
      h2 = eng.Submit([7, 7, 7], 4)
      assert h2.Result(timeout=30) == _GreedyRef(task, theta, [7, 7, 7], 4)
    finally:
      eng.Stop()
    assert eng.Stats()["kv_pages"]["free"] == eng.num_pages

  def test_overcapacity_submit_rejected(self, tiny_lm):
    task, theta = tiny_lm
    eng = _MakeEngine(task, theta, num_pages=4, max_seq_len=32)
    with pytest.raises(ValueError, match="could never be admitted"):
      eng.Submit([1, 2, 3], 30)   # needs 9 pages; the pool has 4

  def test_ineligible_config_falls_back_dense_and_visibly(self):
    """atten_logit_cap > 0 fails BlockDecodeEligible: the engine must
    still decode correctly (gather-dense fallback) AND say so."""
    from lingvo_tpu.core import attention as attention_lib
    p = _TinyLmParams()
    p.atten_tpl = attention_lib.MultiHeadedAttention.Params().Set(
        atten_logit_cap=50.0)
    task = p.Instantiate()
    task.FinalizePaths()
    theta = task.InstantiateVariables(jax.random.PRNGKey(0))
    eng = _MakeEngine(task, theta)
    assert eng.paged_path == "dense"
    prompts = np.array([[5, 9, 2, 33], [7, 7, 7, 0]], np.int32)
    lens = np.array([4, 3], np.int32)
    out = eng.RunBatch(prompts, lens, 4)
    assert list(out[0]) == _GreedyRef(task, theta, [5, 9, 2, 33], 4)
    assert list(out[1]) == _GreedyRef(task, theta, [7, 7, 7], 4)
    stats = eng.Stats()
    assert stats["paged_path"] == "dense"
    assert stats["dense_fallback_steps"] == stats["steps"] > 0


# -- deterministic mixed-length soak -----------------------------------------


@pytest.mark.slow
class TestSoak:

  def test_mixed_length_soak_token_identical(self, tiny_lm):
    """20 seeded ragged requests through 3 slots and a deliberately tight
    pool, submitted from a separate thread while the loop runs: every
    request must finish and match its per-row dense reference."""
    task, theta = tiny_lm
    rng = np.random.RandomState(0)
    reqs = []
    for _ in range(20):
      p_len = int(rng.randint(1, 12))
      max_new = int(rng.randint(1, 8))
      prompt = [int(t) for t in rng.randint(1, 64, size=p_len)]
      reqs.append((prompt, max_new))
    eng = engine_lib.ServingLoop(
        task, theta, page_size=4, num_pages=10, max_batch=3,
        max_seq_len=20, prefill_chunk=4, default_max_new=8).Start()
    handles = [None] * len(reqs)

    def _Submit():
      for i, (prompt, max_new) in enumerate(reqs):
        handles[i] = eng.Submit(prompt, max_new)

    t = threading.Thread(target=_Submit)
    t.start()
    t.join(timeout=60)
    try:
      for i, (prompt, max_new) in enumerate(reqs):
        got = handles[i].Result(timeout=120)
        assert got == _GreedyRef(task, theta, prompt, max_new), f"req {i}"
        assert handles[i].finish_reason == "length"
    finally:
      eng.Stop()
    stats = eng.Stats()
    assert stats["scheduler"]["finished"] == 20
    assert stats["kv_pages"]["free"] == 10
    assert stats["kv_pages"]["peak_in_use"] <= 10
