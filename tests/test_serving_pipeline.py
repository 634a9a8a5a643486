"""Two steps in flight: ServingLoop dispatches step n+1 before it fetches
step n's tokens (docs/serving_engine.md, "The loop is a pipeline").

Every stream here is held, byte for byte, to the dense per-row references of
tests/test_serving_engine.py (`_GreedyRef`, `_SampledRef`), which share no
scheduler, pool or packed step with the engine: a draw is a pure function
of (engine seed, row seed, output position), never of the schedule, so the
pipeline may not move a single token. Covered:
- eos reached while the next step, which the row already rides, is in
  flight: the extra row is dropped, the stream ends at the eos;
- Cancel and a priority preemption between a step's dispatch and its
  commit: the cancelled row's draw is dropped, the preempted row's draw
  still reaches its stream (its cursor and its spilled pages hold it);
- a slot and its pages given to the next request while the last step of
  the one that ended by length is still in flight, prefix cache on;
- `Stop(drain=False)` from another thread mid-iteration: every step that
  was dispatched is retired before anything is cancelled;
- `RunBatch` and `while HasWork(): StepOnce()` end with nothing in flight;
- an engine with a draft source keeps depth one through the same loop;
- the step records and `steps_overlapped` say how often the pipeline
  engaged.
"""

import threading
import time

import numpy as np
import pytest

from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import scheduler as scheduler_lib
from lingvo_tpu.serving import spec_decode
from tests.test_serving_engine import _GreedyRef
from tests.test_serving_engine import _SampledRef

_SAMPLING = {"greedy": {},
             "sampled": dict(temperature=0.8, top_k=8, sample_seed=3)}
MODES = sorted(_SAMPLING)


def _Engine(lm, mode="greedy", **kw):
  task, theta = lm
  kw.setdefault("page_size", 4)
  kw.setdefault("num_pages", 16)
  kw.setdefault("max_batch", 3)
  kw.setdefault("max_seq_len", 32)
  kw.setdefault("prefill_chunk", 4)
  return engine_lib.ServingLoop(task, theta, **_SAMPLING[mode], **kw)


def _Ref(lm, mode, prompt, max_new, seed):
  task, theta = lm
  if mode == "greedy":
    return _GreedyRef(task, theta, prompt, max_new)
  kw = _SAMPLING[mode]
  return _SampledRef(task, theta, prompt, max_new, seed=seed,
                     sample_seed=kw["sample_seed"],
                     temperature=kw["temperature"], top_k=kw["top_k"])


def _RunDry(eng):
  while eng.sched.HasWork():
    eng.StepOnce()
  assert not eng._in_flight and eng.sched.steps_in_flight == 0


PROMPTS = ([5, 9, 2, 33, 17, 4], [7, 7, 7], [1, 2, 3, 4, 5, 6, 7, 8, 9])


class TestEndsWhileInFlight:

  @pytest.mark.parametrize("mode", MODES)
  def test_eos_reached_while_the_next_step_is_in_flight(self, tiny_lm, mode):
    new = 8
    refs = [_Ref(tiny_lm, mode, p, new, seed=11 + i)
            for i, p in enumerate(PROMPTS)]
    # row 0 stops at its fourth token (or where that token first shows)
    eos = refs[0][3]
    cut = refs[0].index(eos) + 1
    assert cut < new
    eng = _Engine(tiny_lm, mode)
    handles = [eng.Submit(p, new, eos_id=eos if i == 0 else None, seed=11 + i)
               for i, p in enumerate(PROMPTS)]
    _RunDry(eng)
    assert handles[0].Result(0) == refs[0][:cut]
    assert handles[0].finish_reason == "eos"
    for h, ref in zip(handles[1:], refs[1:]):
      assert h.Result(0) == ref and h.finish_reason == "length"
    stats = eng.Stats()
    # the step after the one that drew the eos was already dispatched with
    # the row in it: computed, and dropped
    assert stats["inflight_rows_dropped"] == 1
    assert stats["tokens_emitted"] == cut + 2 * new
    assert stats["scheduler"]["finished"] == 3
    assert stats["kv_pages"]["free"] == eng.num_pages

  @pytest.mark.parametrize("mode", MODES)
  def test_cancel_between_dispatch_and_commit(self, tiny_lm, mode):
    new = 10
    refs = [_Ref(tiny_lm, mode, p, new, seed=21 + i)
            for i, p in enumerate(PROMPTS[:2])]
    eng = _Engine(tiny_lm, mode)
    h0, h1 = (eng.Submit(p, new, eos_id=None, seed=21 + i)
              for i, p in enumerate(PROMPTS[:2]))
    for _ in range(5):
      eng.StepOnce()
    # between two calls the newest step is dispatched and not committed
    seq = eng.sched._by_id[h1.id]
    assert len(eng._in_flight) == 1 and seq.pending == 1
    got = list(h1._tokens)
    assert h1.Cancel() and h1.finish_reason == "cancelled"
    _RunDry(eng)
    # the draw that was in flight when the cancel came is not streamed
    assert h1.Result(0) == got == refs[1][:len(got)]
    assert 0 < len(got) < new
    assert h0.Result(0) == refs[0]
    stats = eng.Stats()
    assert stats["inflight_rows_dropped"] == 1
    assert stats["scheduler"]["cancelled"] == 1
    assert stats["kv_pages"]["free"] == eng.num_pages

  @pytest.mark.parametrize("lm_name", ["tiny_lm", "hybrid_lm"])
  def test_preempted_between_dispatch_and_commit(self, lm_name, request):
    lm = request.getfixturevalue(lm_name)
    new = 12
    bulk = ([1, 2, 3, 4], [5, 6, 7, 8])
    refs = [_Ref(lm, "greedy", p, new, seed=0) for p in bulk]
    eng = _Engine(lm, scheduler_mode="priority", num_pages=10, max_batch=2)
    h = [eng.Submit(p, new, eos_id=None) for p in bulk]
    for _ in range(4):
      eng.StepOnce()
    seqs = [eng.sched._by_id[x.id] for x in h]
    assert all(s.pending == 1 for s in seqs)
    before = [(s.pos, len(s.out)) for s in seqs]
    hp = eng.Submit([9, 10, 11, 12], 6, eos_id=None, priority=5)
    eng.StepOnce()     # admits the probe: a victim leaves with a draw in flight
    victims = [k for k, s in enumerate(seqs)
               if s.state is scheduler_lib.SeqState.PREEMPTED]
    assert len(victims) == 1
    k = victims[0]
    # never an advanced cursor with a lost token: the draw arrived
    assert (seqs[k].pos, len(seqs[k].out)) == (before[k][0], before[k][1] + 1)
    assert seqs[k].pending == 0
    _RunDry(eng)
    st = eng.Stats()
    assert st["scheduler"]["preemptions"] >= 1
    assert st["scheduler"]["restores"] >= 1
    assert st["inflight_rows_dropped"] == 0
    assert [x.Result(0) for x in h] == refs
    assert hp.Result(0) == _Ref(lm, "greedy", [9, 10, 11, 12], 6, seed=0)

  def test_eos_of_a_row_preempted_with_its_draw_in_flight(self, tiny_lm):
    new = 12
    bulk = ([1, 2, 3, 4], [5, 6, 7, 8])
    refs = [_Ref(tiny_lm, "greedy", p, new, seed=0) for p in bulk]

    def _Play(eos_at):
      eng = _Engine(tiny_lm, scheduler_mode="priority", num_pages=10,
                    max_batch=2)
      h = [eng.Submit(p, new, eos_id=None) for p in bulk]
      for _ in range(4):
        eng.StepOnce()
      seqs = [eng.sched._by_id[x.id] for x in h]
      if eos_at is not None:
        # the draw in flight is the victim's eos
        k, n = eos_at
        seqs[k].req.eos_id = refs[k][n]
      eng.Submit([9, 10, 11, 12], 6, eos_id=None, priority=5)
      eng.StepOnce()
      return eng, h, seqs

    eng, h, seqs = _Play(None)
    k = next(i for i, s in enumerate(seqs)
             if s.state is scheduler_lib.SeqState.PREEMPTED)
    n = len(seqs[k].out) - 1          # the position that was in flight
    eng, h, seqs = _Play((k, n))
    assert seqs[k].state is scheduler_lib.SeqState.FINISHED
    assert h[k].Result(0) == refs[k][:n + 1] and h[k].finish_reason == "eos"
    assert not eng.sched.preempted and h[k].id not in eng.sched.host_store
    _RunDry(eng)
    assert h[1 - k].Result(0) == refs[1 - k]
    assert eng.Stats()["kv_pages"]["free"] == eng.num_pages


class TestSlotReuseUnderTheLastStep:

  @pytest.mark.parametrize("mode", MODES)
  def test_slot_and_pages_readmitted_the_step_after_a_finish_by_length(
      self, tiny_lm, mode):
    shared = [3, 1, 4, 1, 5, 9, 2, 6]            # two full pages
    prompts = [shared + [7], shared + [8, 9], shared + [7]]
    new = 5
    refs = [_Ref(tiny_lm, mode, p, new, seed=31 + i)
            for i, p in enumerate(prompts)]
    # one slot, and pages for one request at a time (beside the cached ones)
    eng = _Engine(tiny_lm, mode, max_batch=1, num_pages=6, prefix_cache=True)
    handles = [eng.Submit(p, new, eos_id=None, seed=31 + i)
               for i, p in enumerate(prompts)]
    took_over = 0
    owner = None
    while eng.sched.HasWork():
      eng.StepOnce()
      live = eng.sched.slots[0]
      if live is not None and owner is not None and live.id != owner:
        # the slot changed hands in this iteration: the one before had ended
        # by length at its last dispatch, and its last token was still in
        # flight when the next request was admitted into its slot and pages
        took_over += 1
      if live is not None:
        owner = live.id
    assert took_over == 2
    for h, ref in zip(handles, refs):
      assert h.Result(0) == ref and h.finish_reason == "length"
    stats = eng.Stats()
    # back to back: no iteration between two requests launched nothing
    assert stats["steps_overlapped"] == stats["steps"] - 1
    assert stats["inflight_rows_dropped"] == 0
    assert stats["prefix_hit_tokens"] >= 2 * len(shared)
    assert stats["scheduler"]["finished"] == 3

  def test_the_last_token_is_undelivered_when_the_slot_is_reused(
      self, tiny_lm):
    eng = _Engine(tiny_lm, max_batch=1, num_pages=4, prefix_cache=True)
    a = eng.Submit([3, 1, 4, 1, 5], 3, eos_id=None)
    b = eng.Submit([3, 1, 4, 1, 6], 3, eos_id=None)
    seen = False
    while eng.sched.HasWork():
      eng.StepOnce()
      live = eng.sched.slots[0]
      if live is not None and live.id == b.id and not seen:
        seen = True
        sa = eng.sched._by_id[a.id]
        assert sa.state is scheduler_lib.SeqState.FINISHED
        assert not a.done or len(a._tokens) == 3
    assert seen
    task, theta = tiny_lm
    assert a.Result(0) == _GreedyRef(task, theta, [3, 1, 4, 1, 5], 3)
    assert b.Result(0) == _GreedyRef(task, theta, [3, 1, 4, 1, 6], 3)


class TestStopAndDrain:

  @pytest.mark.parametrize("mode", MODES)
  def test_stop_without_drain_retires_what_was_dispatched(self, tiny_lm,
                                                         mode):
    new = 12
    refs = [_Ref(tiny_lm, mode, p, new, seed=41 + i)
            for i, p in enumerate(PROMPTS)]
    eng = _Engine(tiny_lm, mode)
    inner = eng._compile_log.Call
    dispatched = {}
    at_step = threading.Event()
    calls = [0]

    def _Call(name, fn, *args):
      out = inner(name, fn, *args)
      if name == "ragged":
        calls[0] += 1
        if calls[0] == 8:
          # mid-iteration: this step is dispatched, the one before is not
          # committed yet. What each row has been fed and has drawn so far:
          for s in eng.sched.slots:
            if s is not None:
              dispatched[s.id] = s.n_out
          at_step.set()
          t_end = time.monotonic() + 30
          while not eng._cancel_open and time.monotonic() < t_end:
            time.sleep(0.001)       # until Stop(drain=False) has asked
      return out

    eng._compile_log.Call = _Call
    handles = [eng.Submit(p, new, eos_id=None, seed=41 + i)
               for i, p in enumerate(PROMPTS)]
    eng.Start()
    try:
      assert at_step.wait(60)
    finally:
      eng.Stop(drain=False)
    assert len(dispatched) == 3 and not eng._in_flight
    for h, ref in zip(handles, refs):
      toks = h.Result(0)
      # every draw of a dispatched step is in the handle, none is invented
      assert len(toks) >= dispatched[h.id] > 0
      assert toks == ref[:len(toks)] and len(toks) < new
      assert h.finish_reason == "cancelled"
    assert eng.Stats()["inflight_rows_dropped"] == 0

  def test_stop_with_drain_delivers_everything(self, tiny_lm):
    eng = _Engine(tiny_lm).Start()
    handles = [eng.Submit(p, 7, eos_id=None) for p in PROMPTS]
    eng.Stop(drain=True)
    assert not eng._in_flight and not eng.sched.HasWork()
    for h, p in zip(handles, PROMPTS):
      assert h.Result(0) == _Ref(tiny_lm, "greedy", p, 7, seed=0)

  @pytest.mark.parametrize("mode", MODES)
  def test_runbatch_and_the_inline_loop_deliver_every_token(self, tiny_lm,
                                                           mode):
    eng = _Engine(tiny_lm, mode)
    prompts = np.zeros((3, 9), np.int32)
    for i, p in enumerate(PROMPTS):
      prompts[i, :len(p)] = p
    lens = np.array([len(p) for p in PROMPTS])
    out = eng.RunBatch(prompts, lens, 6)
    assert not eng._in_flight and not eng.sched.HasWork()
    # RunBatch's requests take ids 1.. and the id is the default seed
    for i, p in enumerate(PROMPTS):
      assert list(out[i]) == _Ref(tiny_lm, mode, p, 6, seed=i + 1)
    handles = [eng.Submit(p, 6, eos_id=None, seed=i + 1)
               for i, p in enumerate(PROMPTS)]
    _RunDry(eng)
    assert [h.Result(0) for h in handles] == [list(r) for r in out]
    stats = eng.Stats()
    assert stats["tokens_emitted"] == 2 * 3 * 6
    assert stats["scheduler"]["finished"] == 6


class TestDepth:

  @pytest.mark.parametrize("lm_name", ["tiny_lm", "hybrid_lm"])
  def test_a_draft_source_retires_before_it_builds(self, lm_name, request):
    lm = request.getfixturevalue(lm_name)
    eng = _Engine(lm, spec=spec_decode.SelfDraft(num_layers=1, k=2))
    handles = [eng.Submit(p, 8, eos_id=None) for p in PROMPTS]
    while eng.sched.HasWork():
      eng.StepOnce()
      assert not eng._in_flight and eng.sched.steps_in_flight == 0
    stats = eng.Stats()
    assert stats["steps_overlapped"] == 0 and stats["steps"] > 0
    assert "feed" not in stats["compile"]
    # every record of a depth-one loop holds its own device_wait and commit
    assert all(s.Phases()["device_wait"] > 0 and s.Phases()["commit"] > 0
               for s in eng.trace.Steps())
    for h, p in zip(handles, PROMPTS):
      assert h.Result(0) == _Ref(lm, "greedy", p, 8, seed=0)

  def test_without_one_a_step_stays_in_flight_between_calls(self, tiny_lm):
    eng = _Engine(tiny_lm)
    h = eng.Submit(PROMPTS[0], 6, eos_id=None)
    eng.StepOnce()
    assert len(eng._in_flight) == 1 and eng.sched.steps_in_flight == 1
    assert eng.sched.HasWork() and not h._tokens
    for _ in range(3):
      eng.StepOnce()
      assert len(eng._in_flight) == 1
    _RunDry(eng)
    assert h.Result(0) == _Ref(tiny_lm, "greedy", PROMPTS[0], 6, seed=0)

  def test_tokens_are_fed_on_the_device_as_plain_ids(self, tiny_lm):
    """The step program never sees a placeholder: what it is handed at a
    decode row's column is the token the previous step drew."""
    eng = _Engine(tiny_lm)
    inner = eng._compile_log.Call
    fed = []

    def _Call(name, fn, *args):
      if name == "ragged":
        fed.append(np.asarray(args[2]))
      return inner(name, fn, *args)

    eng._compile_log.Call = _Call
    h = eng.Submit(PROMPTS[1], 6, eos_id=None)
    _RunDry(eng)
    toks = h.Result(0)
    assert all((f >= 0).all() for f in fed)
    # one prefill step, then a decode step per token but the last
    assert [int(f[0]) for f in fed[1:]] == toks[:-1]
    assert eng.Stats()["compile"]["feed"]["calls"] == len(fed) - 1


class TestRecordsAndCounters:

  def test_dispatch_of_a_step_ends_before_the_wait_for_the_one_before(
      self, tiny_lm):
    eng = _Engine(tiny_lm, max_batch=2)
    handles = [eng.Submit(p, 9, eos_id=None) for p in PROMPTS]
    _RunDry(eng)
    gap = eng.Submit(PROMPTS[1], 4, eos_id=None)   # after a drain: a fill
    _RunDry(eng)
    steps = eng.trace.Steps()
    stats = eng.Stats()
    assert len(steps) == stats["steps"]
    fills = [s for s in steps if s.Phases()["device_wait"] == 0.0]
    assert len(fills) == 2 and fills[0] is steps[0]
    assert stats["steps_overlapped"] == stats["steps"] - len(fills)
    seg = list(eng.trace.Steps()[0].segments_s)
    i_dispatch, i_wait = 5, 6
    assert len(seg) == 9
    for s in steps:
      if s in fills:
        continue
      # record k: step k's dispatch, then step k-1's device_wait and commit
      dispatch_end = s.start_ts + sum(s.segments_s[:i_dispatch + 1])
      wait_end = s.start_ts + sum(s.segments_s[:i_wait + 1])
      assert s.start_ts < dispatch_end < wait_end <= s.end_ts
      assert s.segments_s[i_dispatch] > 0 and s.segments_s[i_wait] > 0
    for h, p in zip(handles, PROMPTS):
      assert h.Result(0) == _Ref(tiny_lm, "greedy", p, 9, seed=0)
    assert gap.Result(0) == _Ref(tiny_lm, "greedy", PROMPTS[1], 4, seed=0)
    assert stats["inflight_rows_dropped"] == 0

  def test_steps_and_tokens_are_counted_once(self, tiny_lm):
    eng = _Engine(tiny_lm)
    seen = []
    inner = eng.StepOnce

    def _Step():
      before = eng.Stats()["steps"]
      n = inner()
      after = eng.Stats()["steps"]
      seen.append((after - before, n))
      return n

    handles = [eng.Submit(p, 5, eos_id=None) for p in PROMPTS]
    while eng.sched.HasWork():
      _Step()
    # `steps` moves once a call that launched, never in the call that only
    # retires; the events add up to the tokens streamed
    assert [d for d, _ in seen[:-1]] == [1] * (len(seen) - 1)
    assert seen[-1][0] == 0 and seen[-1][1] > 0
    assert sum(n for _, n in seen) == 15 == eng.Stats()["tokens_emitted"]
    assert all(h.done for h in handles)
