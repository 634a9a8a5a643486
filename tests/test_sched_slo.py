"""SLO-aware multi-tenant scheduling: preemption by KV page spill.

Covers docs/multi_tenant_scheduling.md (ISSUE 20):
- `kv_cache.HostPageStore` bookkeeping and the allocator's spill surface
  (PrivatePages / SpillPrivate / HoleCount / FillHoles, hole-aware Free),
- `TokenBucket` per-tenant quotas with an injectable clock, and
  QuotaExceeded raised at Submit on both the engine and fleet surfaces,
- the device-free priority scheduler lifecycle: class-ordered admission,
  weighted-fair tenants, victim selection, preemption, re-admission from
  the spilled cursor, PREEMPTED cancellation,
- spill→restore is BITWISE per paged leaf (including int8 scale
  sidecars) via the engine's jitted gather/scatter,
- greedy streams are byte-identical preempted-vs-unpreempted on plain
  attention, hybrid-SSM (state rows ride along), repeat-stack, int8-KV,
  and mid-spec-cycle engines, and under scheduler_mode='fifo' vs legacy
  default,
- preempting a request that borrows shared prefix pages spills only its
  PRIVATE pages — the cache's nodes stay valid and keep hitting,
- fleet failover resubmits a PREEMPTED request like any other,
- the stats surfaces: SCHEDULER_STATS_KEYS exact match, per-class
  queue-wait histograms, router class-aware load routing.
"""

import threading
import time

import numpy as np
import pytest

from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import fleet as fleet_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import router as router_lib
from lingvo_tpu.serving import scheduler as scheduler_lib
from lingvo_tpu.serving import spec_decode

from tests.conftest import TinyLmParams, InstantiateLm  # noqa: E402
from tests.test_serving_engine import _GreedyRef  # noqa: E402


# -- host tier + allocator spill surface (device-free) ------------------------


class TestHostPageStore:

  def test_put_pop_roundtrip_and_counters(self):
    store = kv_cache.HostPageStore()
    blocks = [np.arange(8, dtype=np.float32), np.ones(4, np.int8)]
    row = [np.full(3, 7.0, np.float32)]
    store.Put("a", [0, 2], blocks, row)
    assert "a" in store and len(store) == 1
    st = store.Stats()
    assert st["spilled_pages"] == 2 and st["entries"] == 1
    assert st["host_bytes"] == 8 * 4 + 4 + 3 * 4
    assert st["peak_host_bytes"] == st["host_bytes"]
    entry = store.Pop("a")
    assert entry.logical_idxs == [0, 2]
    np.testing.assert_array_equal(entry.blocks[0], blocks[0])
    np.testing.assert_array_equal(entry.state_row[0], row[0])
    st = store.Stats()
    assert st["restored_pages"] == 2 and st["host_bytes"] == 0
    assert st["entries"] == 0 and "a" not in store

  def test_drop_is_not_a_restore(self):
    store = kv_cache.HostPageStore()
    store.Put("a", [1], [np.zeros(4, np.float32)])
    store.Drop("a")
    st = store.Stats()
    assert st["restored_pages"] == 0 and st["host_bytes"] == 0

  def test_double_spill_asserts(self):
    store = kv_cache.HostPageStore()
    store.Put("a", [0], None)
    with pytest.raises(AssertionError):
      store.Put("a", [1], None)


class TestAllocatorSpill:

  def test_spill_private_leaves_shared_and_fills_holes_fresh(self):
    alloc = kv_cache.PageAllocator(num_pages=8, page_size=4)
    alloc.Allocate("donor", 2)
    donor_pages = alloc.PagesOf("donor")
    alloc.Share("s", donor_pages)          # borrowed: refcount 2
    alloc.Allocate("s", 2)                 # private tail
    pages = alloc.PagesOf("s")
    # 2 shared + 2 private; only data pages within 12 tokens (3 pages)
    priv = alloc.PrivatePages("s", 12)
    assert [li for li, _ in priv] == [2]
    assert alloc.SpillPrivate("s") == 2    # both private pages freed
    assert alloc.HoleCount("s") == 2
    assert alloc.PagesOf("s")[:2] == pages[:2]   # shared pages untouched
    filled = alloc.FillHoles("s")
    assert [li for li, _ in filled] == [2, 3]
    assert alloc.HoleCount("s") == 0
    for _, pg in filled:
      assert alloc.RefCount(pg) == 1

  def test_fill_holes_all_or_nothing_under_exhaustion(self):
    alloc = kv_cache.PageAllocator(num_pages=4, page_size=4)
    alloc.Allocate("a", 3)
    alloc.SpillPrivate("a")                # 3 holes, 4 free
    alloc.Allocate("b", 2)                 # squeeze: 2 free < 3 holes
    free_before = alloc.num_free
    with pytest.raises(kv_cache.OutOfPages):
      alloc.FillHoles("a")
    assert alloc.num_free == free_before   # no partial fill
    assert alloc.HoleCount("a") == 3

  def test_free_skips_holes(self):
    alloc = kv_cache.PageAllocator(num_pages=4, page_size=4)
    alloc.Allocate("a", 3)
    alloc.SpillPrivate("a")
    assert alloc.Free("a") == 0            # all holes: nothing device-side
    assert alloc.num_free == 4
    assert "a" not in alloc._owned


class TestTokenBucket:

  def test_refill_is_rate_times_elapsed(self):
    now = [0.0]
    b = scheduler_lib.TokenBucket(rate=10.0, burst=20.0,
                                  clock=lambda: now[0])
    assert b.TryTake(20) and not b.TryTake(1)
    now[0] = 1.0                           # +10 tokens
    assert b.TryTake(10) and not b.TryTake(1)
    now[0] = 100.0                         # clamped at burst
    assert b.level == pytest.approx(20.0)


# -- device-free priority scheduler lifecycle ---------------------------------


def _MkSched(**kw):
  kw.setdefault("scheduler_mode", "priority")
  alloc = kw.pop("alloc", None) or kv_cache.PageAllocator(8, 4)
  return scheduler_lib.Scheduler(kw.pop("slots", 2), alloc,
                                 table_pages=4, **kw), alloc


class TestPrioritySchedulerLifecycle:

  def test_preempt_park_readmit_resumes_cursor(self):
    sched, alloc = _MkSched()
    for i in range(2):
      sched.Submit(scheduler_lib.Request(i, [1, 2, 3, 4], 8, priority=0))
    low = sched.Admit()
    assert [s.id for s in low] == [0, 1]
    for s in low:                          # simulate decode progress
      s.pos, s.state, s.out = 4, scheduler_lib.SeqState.DECODE, [5, 6]
    sched.Submit(scheduler_lib.Request(9, [1] * 8, 8, priority=5))
    adm = sched.Admit()
    assert [s.id for s in adm] == [9]
    assert sched.preemptions == 1
    victim = sched.preempted[0]
    assert victim.state is scheduler_lib.SeqState.PREEMPTED
    assert victim.slot is None and victim.id in sched.host_store
    assert victim.draft_pos == 0           # draft replays on restore
    # retire the high-pri request -> victim restores at its old cursor
    hp = sched._by_id[9]
    sched.slots[hp.slot] = None
    alloc.Free(hp.id)
    hp.state, hp.slot = scheduler_lib.SeqState.FINISHED, None
    back = sched.Admit()
    assert [s.id for s in back] == [victim.id]
    assert victim.state is scheduler_lib.SeqState.DECODE
    assert victim.pos == 4 and victim.out == [5, 6]
    assert sched.restores == 1 and not sched.preempted

  def test_victim_is_lowest_class_least_progress(self):
    sched, _ = _MkSched(slots=3, alloc=kv_cache.PageAllocator(16, 4))
    for i, (pr, ntok) in enumerate([(1, 1), (0, 3), (0, 1)]):
      sched.Submit(scheduler_lib.Request(i, [1, 2, 3, 4], 8, priority=pr))
    live = sched.Admit()
    for s, n in zip(live, [1, 3, 1]):
      s.pos, s.state = 4, scheduler_lib.SeqState.DECODE
      s.out = list(range(n))
    sched.Submit(scheduler_lib.Request(9, [1] * 8, 8, priority=5))
    sched.Admit()
    # class 0 outranks class 1 as victim; fewest tokens wins in-class
    assert [s.id for s in sched.preempted] == [2]

  def test_same_class_never_preempts(self):
    sched, _ = _MkSched()
    for i in range(2):
      sched.Submit(scheduler_lib.Request(i, [1, 2, 3, 4], 8, priority=3))
    for s in sched.Admit():
      s.pos, s.state = 4, scheduler_lib.SeqState.DECODE
    sched.Submit(scheduler_lib.Request(9, [1, 2], 4, priority=3))
    assert sched.Admit() == []             # equal class: waits, no thrash
    assert sched.preemptions == 0

  def test_weighted_fair_tenants_within_class(self):
    sched, _ = _MkSched(slots=1, alloc=kv_cache.PageAllocator(32, 4),
                        tenant_weights={"heavy": 4.0})
    # all same class; 'heavy' has 4x weight -> 4x the admitted service
    ids = []
    for i, tn in enumerate(["light", "heavy", "heavy", "light", "heavy"]):
      sched.Submit(scheduler_lib.Request(i, [1, 2], 2, tenant=tn))
      ids.append((i, tn))
    order = []
    while sched.HasWork():
      adm = sched.Admit()
      if not adm:
        break
      seq = adm[0]
      order.append(seq.id)
      sched.slots[seq.slot] = None         # instant-retire to free the slot
      sched.alloc.Free(seq.id)
      seq.state, seq.slot = scheduler_lib.SeqState.FINISHED, None
    # first admit is arrival-tied (0 service each); after 'light' serves
    # once, 'heavy' (weight 4) wins repeatedly until its service/weight
    # catches up
    assert order[0] == 0 and order[1:4] == [1, 2, 4]

  def test_cancel_preempted_drops_host_entry(self):
    sched, alloc = _MkSched()
    for i in range(2):
      sched.Submit(scheduler_lib.Request(i, [1, 2, 3, 4], 8))
    for s in sched.Admit():
      s.pos, s.state = 4, scheduler_lib.SeqState.DECODE
    sched.Submit(scheduler_lib.Request(9, [1] * 8, 8, priority=5))
    sched.Admit()
    victim_id = sched.preempted[0].id
    assert sched.Cancel(victim_id)
    assert victim_id not in sched.host_store
    assert not sched.preempted
    # refs on any pages are gone: cancel again is a no-op
    assert not sched.Cancel(victim_id)

  def test_quota_rejects_at_submit(self):
    now = [0.0]
    sched, _ = _MkSched(tenant_quotas={"t": (1.0, 10.0)}, clock=lambda: now[0])
    sched.Submit(scheduler_lib.Request(0, [1, 2], 6, tenant="t"))
    with pytest.raises(scheduler_lib.QuotaExceeded):
      sched.Submit(scheduler_lib.Request(1, [1, 2], 6, tenant="t"))
    assert sched.quota_rejections == 1
    now[0] = 8.0                           # rate 1/s refills the bucket
    sched.Submit(scheduler_lib.Request(2, [1, 2], 6, tenant="t"))
    # untracked tenants are never charged
    sched.Submit(scheduler_lib.Request(3, [1, 2], 6, tenant="other"))

  def test_stats_key_set_matches_schema(self):
    sched, _ = _MkSched()
    st = sched.Stats()
    assert set(st) == observe_schema.SCHEDULER_STATS_KEYS
    assert st["scheduler_mode"] == "priority"
    fifo = scheduler_lib.Scheduler(2, kv_cache.PageAllocator(8, 4), 4)
    st = fifo.Stats()
    assert set(st) == observe_schema.SCHEDULER_STATS_KEYS
    assert st["scheduler_mode"] == "fifo" and st["preemptions"] == 0


# -- engine: bitwise spill/restore + byte-identical streams -------------------


def _MkEngine(task, theta, **kw):
  kw.setdefault("page_size", 4)
  kw.setdefault("num_pages", 10)
  kw.setdefault("max_batch", 2)
  kw.setdefault("max_seq_len", 32)
  kw.setdefault("trace", False)
  return engine_lib.ServingLoop(task, theta, **kw)


def _PlayWithProbe(task, theta, mode, probe, bulk_new=12, pre_steps=4, **kw):
  """Two saturating low-pri requests; optionally a high-pri probe after
  pre_steps steps (driven inline — deterministic preemption point)."""
  eng = _MkEngine(task, theta, scheduler_mode=mode, **kw)
  h1 = eng.Submit([1, 2, 3, 4], bulk_new, eos_id=None)
  h2 = eng.Submit([5, 6, 7, 8], bulk_new, eos_id=None)
  for _ in range(pre_steps):
    eng.StepOnce()
  hp = (eng.Submit([9, 10, 11, 12], 6, eos_id=None, priority=5)
        if probe else None)
  while eng.sched.HasWork():
    eng.StepOnce()
  out = [h1.Result(0), h2.Result(0)]
  sched_stats = eng.Stats()["scheduler"]
  probe_out = hp.Result(0) if hp else None
  return out, probe_out, sched_stats, eng


class TestPreemptionByteIdentity:

  def test_attention_stack(self, tiny_lm):
    task, theta = tiny_lm
    base, _, st0, _ = _PlayWithProbe(task, theta, "fifo", False)
    assert st0["preemptions"] == 0
    pre, probe_out, st, _ = _PlayWithProbe(task, theta, "priority", True)
    assert st["preemptions"] >= 1 and st["restores"] >= 1
    assert st["spilled_pages"] >= 1 and st["restored_pages"] >= 1
    assert base == pre                     # preemption never shifts a token
    assert probe_out == _GreedyRef(task, theta, [9, 10, 11, 12], 6)
    # fifo mode == the engine's legacy default mode, byte for byte
    legacy, _, _, _ = _PlayWithProbe(task, theta, "fifo", False)
    assert legacy == base

  def test_hybrid_ssm_state_rows_ride_along(self, hybrid_lm):
    task, theta = hybrid_lm
    base, _, _, _ = _PlayWithProbe(task, theta, "fifo", False)
    pre, _, st, _ = _PlayWithProbe(task, theta, "priority", True)
    assert st["preemptions"] >= 1
    assert base == pre

  @pytest.mark.slow
  def test_repeat_stack_leaves(self):
    task, theta = InstantiateLm(TinyLmParams(every_n=2, use_repeat=True))
    base, _, _, _ = _PlayWithProbe(task, theta, "fifo", False)
    pre, _, st, _ = _PlayWithProbe(task, theta, "priority", True)
    assert st["preemptions"] >= 1
    assert base == pre

  @pytest.mark.slow
  def test_int8_kv_scale_sidecars(self, tiny_lm):
    task, theta = tiny_lm
    base, _, _, _ = _PlayWithProbe(task, theta, "fifo", False,
                                   kv_cache_dtype="int8")
    pre, _, st, _ = _PlayWithProbe(task, theta, "priority", True,
                                   kv_cache_dtype="int8")
    assert st["preemptions"] >= 1
    assert base == pre

  def test_preempt_mid_spec_cycle(self, tiny_lm):
    task, theta = tiny_lm
    spec = lambda: spec_decode.SelfDraft(k=3, num_layers=1)  # noqa: E731
    kw = dict(bulk_new=20, pre_steps=2, num_pages=16)
    base, _, _, _ = _PlayWithProbe(task, theta, "fifo", False, spec=spec(),
                                   **kw)
    pre, _, st, _ = _PlayWithProbe(task, theta, "priority", True,
                                   spec=spec(), **kw)
    assert st["preemptions"] >= 1
    assert base == pre                     # rollback cursors survive spill

  def test_spill_restore_bitwise_per_leaf(self, tiny_lm):
    task, theta = tiny_lm
    eng = _MkEngine(task, theta, scheduler_mode="priority")
    eng.Submit([1, 2, 3, 4, 5, 6], 4, eos_id=None)
    for _ in range(3):
      eng.StepOnce()
    pages = eng.alloc.PagesOf(1)
    blocks = eng._SpillPages(pages)
    assert blocks and all(isinstance(b, np.ndarray) for b in blocks)
    eng._RestorePages(pages, blocks)       # scatter back in place
    again = eng._SpillPages(pages)
    for a, b in zip(blocks, again):
      np.testing.assert_array_equal(a, b)  # bitwise round trip

  def test_state_row_bitwise_roundtrip(self, hybrid_lm):
    task, theta = hybrid_lm
    eng = _MkEngine(task, theta, scheduler_mode="priority")
    eng.Submit([1, 2, 3, 4], 4, eos_id=None)
    for _ in range(3):
      eng.StepOnce()
    rows = eng._SpillStateRow(0)
    assert rows                            # hybrid stack has state leaves
    eng._RestoreStateRow(1, rows)          # land in a DIFFERENT slot
    moved = eng._SpillStateRow(1)
    for a, b in zip(rows, moved):
      np.testing.assert_array_equal(a, b)


class TestSharedPrefixPreemption:

  def test_only_private_pages_spill_cache_stays_valid(self, tiny_lm):
    task, theta = tiny_lm
    sys_prompt = [3, 1, 4, 1, 5, 9, 2, 6]   # two full pages
    eng = _MkEngine(task, theta, scheduler_mode="priority",
                    prefix_cache=True, num_pages=12)
    # warm the cache with the shared prefix
    h0 = eng.Submit(list(sys_prompt), 4, eos_id=None)
    while eng.sched.HasWork():
      eng.StepOnce()
    h0.Result(0)
    cached_before = eng.prefix_cache.Stats()["cached_pages"]
    assert cached_before >= 2
    # two borrowers fill both slots
    h1 = eng.Submit(list(sys_prompt) + [7], 8, eos_id=None)
    h2 = eng.Submit(list(sys_prompt) + [8], 8, eos_id=None)
    for _ in range(4):
      eng.StepOnce()
    assert eng.Stats()["prefix_hit_tokens"] >= 2 * len(sys_prompt)
    hp = eng.Submit([9, 10, 11], 4, eos_id=None, priority=5)
    while eng.sched.HasWork():
      eng.StepOnce()
    st = eng.Stats()["scheduler"]
    assert st["preemptions"] >= 1
    # shared pages never spilled: the victim kept its refs, so every
    # cached page stayed device-resident and the cache node count held
    assert eng.prefix_cache.Stats()["cached_pages"] == cached_before
    # streams match the dense reference (restored KV bitwise)
    assert h1.Result(0) == _GreedyRef(task, theta, sys_prompt + [7], 8)
    assert h2.Result(0) == _GreedyRef(task, theta, sys_prompt + [8], 8)
    hp.Result(0)


class TestEngineQuotaAndHistograms:

  def test_engine_submit_quota_raises_before_handle(self, tiny_lm):
    task, theta = tiny_lm
    eng = _MkEngine(task, theta, scheduler_mode="priority",
                    tenant_quotas={"t": (0.0, 20.0)})
    eng.Submit([1, 2], 8, tenant="t")
    with pytest.raises(scheduler_lib.QuotaExceeded):
      eng.Submit([1, 2], 16, tenant="t")
    assert len(eng._handles) == 1          # no orphan handle created
    assert eng.Stats()["scheduler"]["quota_rejections"] == 1

  def test_per_class_queue_wait_histograms(self, tiny_lm):
    task, theta = tiny_lm
    _, _, _, eng = _PlayWithProbe(task, theta, "priority", True)
    snap = eng.metrics.Snapshot()
    assert any(k.startswith("serving/queue_wait_s_c0") for k in snap), (
        sorted(k for k in snap if "queue_wait" in k))
    assert any(k.startswith("serving/queue_wait_s_c5") for k in snap)
    # the router's class-aware load key flattens out of the scheduler
    # section for every engine (fifo ones just always read 0)
    assert "scheduler/queue_depth_high" in snap


# -- router + fleet threading -------------------------------------------------


class TestRouterPriorityLoad:

  def test_priority_routes_on_class_aware_load(self):
    r = router_lib.PrefixRouter(4, ["a", "b"], pin_sessions=False)
    snaps = {
        "a": {"scheduler/queue_depth": 0, "scheduler/queue_depth_high": 3},
        "b": {"scheduler/queue_depth": 5, "scheduler/queue_depth_high": 0},
    }
    # default class reads raw queue depth: a (0) beats b (5)
    assert r.Route([1, 2], snaps) == "a"
    # priority class reads parked-above-default work: b (0) beats a (3)
    assert r.Route([1, 2], snaps, priority=5) == "b"
    st = r.Stats()
    assert set(st) == observe_schema.ROUTER_STATS_KEYS
    assert st["priority_routed"] == 1

  def test_missing_key_falls_back_to_load_keys(self):
    r = router_lib.PrefixRouter(4, ["a", "b"], pin_sessions=False)
    snaps = {"a": {"scheduler/queue_depth": 5},
             "b": {"scheduler/queue_depth": 0}}
    assert r.Route([1, 2], snaps, priority=5) == "b"


class TestFleetPreemption:

  def test_failover_resubmits_preempted_request(self, tiny_lm):
    task, theta = tiny_lm
    mk = lambda: _MkEngine(task, theta, max_batch=1,  # noqa: E731
                           scheduler_mode="priority")
    fl = fleet_lib.ServingFleet({"r0": mk(), "r1": mk()},
                                policy="round_robin").Start()

    def _Until(cond, what):
      deadline = time.monotonic() + 60
      while not cond():
        if time.monotonic() > deadline:
          raise TimeoutError(what)
        time.sleep(0.005)

    try:
      # r0 steps on permits until the high-priority request is in, so that
      # it arrives while hb0 holds r0's one slot however loaded the host is
      # (a free-running r0 can finish hb0's 12 tokens before this thread
      # gets to submit hp, and then nothing is ever preempted)
      r0 = fl.Engine("r0")
      permits = threading.Semaphore(0)
      free_step = r0.StepOnce

      def _StepOnPermit():
        permits.acquire()
        return free_step()

      r0.StepOnce = _StepOnPermit
      hb0 = fl.Submit([1, 2, 3, 4], 12)                    # -> r0
      hb1 = fl.Submit([5, 6, 7, 8], 12)                    # -> r1
      for _ in range(3):
        permits.release()
      _Until(lambda: r0.Stats()["steps"] >= 3, "hb0 never started")
      hp = fl.Submit([9, 10, 11, 12], 12, priority=5)      # -> r0: preempts
      r0.StepOnce = free_step
      permits.release()      # the loop may be waiting for one
      _Until(lambda: r0.Stats()["scheduler"]["preemptions"] >= 1,
             "r0 never preempted")
      fl.KillReplica("r0")   # hb0 (or hp) may be PREEMPTED right now
      assert hb0.Result(timeout=120) == _GreedyRef(task, theta,
                                                   [1, 2, 3, 4], 12)
      assert hb1.Result(timeout=120) == _GreedyRef(task, theta,
                                                   [5, 6, 7, 8], 12)
      assert hp.Result(timeout=120) == _GreedyRef(task, theta,
                                                  [9, 10, 11, 12], 12)
      st = fl.Stats()
      assert set(st) == observe_schema.FLEET_STATS_KEYS
      assert st["failovers"] == 1 and st["resubmitted_requests"] >= 1
      assert st["priority_requests"] == 1
    finally:
      fl.Stop()

  def test_fleet_quota_counts_and_propagates(self, tiny_lm):
    task, theta = tiny_lm
    fl = fleet_lib.ServingFleet(
        {"r0": _MkEngine(task, theta, scheduler_mode="priority",
                         tenant_quotas={"t": (0.0, 20.0)})}).Start()
    try:
      h = fl.Submit([1, 2], 8, tenant="t")
      with pytest.raises(scheduler_lib.QuotaExceeded):
        fl.Submit([1, 2], 16, tenant="t")
      assert fl.Stats()["quota_rejections"] == 1
      h.Result(timeout=120)
    finally:
      fl.Stop()


# -- multi-tenant soak (slow) -------------------------------------------------


@pytest.mark.slow
class TestMultiTenantSoak:

  def test_saturated_mixed_stream_byte_identical(self, tiny_lm):
    task, theta = tiny_lm
    rng = np.random.RandomState(0)
    reqs = []
    for i in range(14):
      prompt = [int(t) for t in rng.randint(1, 60, rng.randint(2, 8))]
      pr = 5 if i % 5 == 4 else 0
      # vip probes arrive mid-flight (after `at` engine steps) so the
      # priority arms must preempt running bulk work, not just reorder
      at = 3 + 2 * (i // 5) if pr else 0
      reqs.append((at, prompt, int(rng.randint(4, 12)), pr,
                   "vip" if pr else "bulk"))

    def _Play(mode):
      eng = _MkEngine(task, theta, scheduler_mode=mode, max_batch=2)
      hs, step, pending = [None] * len(reqs), 0, sorted(
          range(len(reqs)), key=lambda i: reqs[i][0])
      while pending or eng.sched.HasWork():
        while pending and reqs[pending[0]][0] <= step:
          i = pending.pop(0)
          _at, p, n, pr, tn = reqs[i]
          hs[i] = eng.Submit(list(p), n, eos_id=None, priority=pr, tenant=tn)
        if eng.sched.HasWork():
          eng.StepOnce()
        step += 1
      out = [h.Result(0) for h in hs]
      return out, eng.Stats()["scheduler"]

    fifo, _ = _Play("fifo")
    prio, st = _Play("priority")
    assert fifo == prio
    assert st["preemptions"] >= 1          # the mix actually preempted
    for (_at, p, n, _pr, _tn), toks in zip(reqs, fifo):
      assert toks == _GreedyRef(task, theta, p, n)
