"""One ragged step program (serving/scheduler.py + serving/engine.py).

Covers docs/ragged_step.md:
- ragged-vs-legacy BYTE-IDENTITY: seeded mixed-length request streams
  produce token-for-token identical outputs on `step_mode='ragged'` and
  `step_mode='legacy'` engines — greedy across draft sources (none /
  SelfDraft / ModelDraft) and target shapes (dense / hybrid-SSM), with
  the prefix cache on and off; temperature > 0 without a draft source is
  byte-identical too (per-token draws are position-indexed, so packing
  never moves a request's sampling stream),
- the compiled-program census: one serving lifetime with admissions,
  prefill/decode overlap, spec cycles, a cancellation and retirements
  compiles EXACTLY ONE step program (`Stats()["compile"]` census == 1,
  name "ragged", no fallback), where the legacy trio compiles three,
- `BuildRaggedStep` packing: decode rows mandatory-first with per-row
  draft clamps, prefill consuming the leftover budget, zero-length rows
  riding with their true q_pos (the SSM-reset trigger is q_pos == 0),
  and `CommitRaggedStep` rollback accounting (rejected tails and
  eos-truncated accepted prefixes) on the page pool,
- cached-prefix-first admission (the scheduler's `_NextWaiting` window):
  under pool pressure the cached follower admits before the uncached
  FIFO head, lifting prefix-cache hit_tokens over strict FIFO, counted
  by `prefix_ordered_admissions`.
"""

import numpy as np
import pytest

from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import prefix_cache as prefix_cache_lib
from lingvo_tpu.serving import scheduler as scheduler_lib
from lingvo_tpu.serving import spec_decode

from tests.test_spec_decode import (_Instantiate, _LmParams, _Stream,
                                    _RunStream)  # noqa: F401
# tiny_lm / hybrid_lm / ssm_draft_lm fixtures: session-scoped in conftest.py


def _Engine(task, theta, spec=None, *, step_mode="ragged", **kw):
  kw.setdefault("page_size", 4)
  kw.setdefault("num_pages", 24)
  kw.setdefault("max_batch", 3)
  kw.setdefault("max_seq_len", 32)
  kw.setdefault("prefill_chunk", 4)
  kw.setdefault("default_max_new", 8)
  return engine_lib.ServingLoop(task, theta, spec=spec, step_mode=step_mode,
                                **kw)


def _BothModes(task, theta, reqs, spec_fn=None, **kw):
  """Runs one stream through a ragged and a legacy engine; returns both."""
  outs = {}
  for mode in ("ragged", "legacy"):
    spec = spec_fn() if spec_fn is not None else None
    eng = _Engine(task, theta, spec, step_mode=mode, **kw)
    outs[mode] = (_RunStream(eng, reqs), eng)
  return outs


# -- ragged vs legacy byte-identity -------------------------------------------


class TestRaggedLegacyByteIdentity:

  def test_greedy_dense_nospec_prefix_on_and_off(self, tiny_lm):
    """Greedy, no draft source — with a repeated-prompt stream so the
    prefix cache actually shares pages in the cache-on arm."""
    task, theta = tiny_lm
    shared = ([7, 3, 7, 3, 7, 3, 7, 3, 7], 4)  # > 2 full pages of prompt
    reqs = [shared] + _Stream(12, seed=11) + [shared]
    # the first copy retires (and inserts its pages) long before the
    # last admits, so the cache-on arm sees a real hit + CoW split
    for cache in (False, True):
      outs = _BothModes(task, theta, reqs, prefix_cache=cache)
      assert outs["ragged"][0] == outs["legacy"][0], f"prefix_cache={cache}"
      if cache:
        for _, eng in outs.values():
          assert eng.Stats()["prefix_cache"]["hit_tokens"] > 0

  def test_greedy_self_draft_ragged_matches_legacy(self, tiny_lm):
    task, theta = tiny_lm
    reqs = _Stream(10, seed=12)
    outs = _BothModes(
        task, theta, reqs,
        spec_fn=lambda: spec_decode.SelfDraft(k=3, num_layers=1))
    assert outs["ragged"][0] == outs["legacy"][0]
    for _, eng in outs.values():
      assert eng.Stats()["spec_cycles"] > 0
    # the unified step speculates WHILE neighbors prefill; legacy defers
    # spec cycles to pure-decode steps — so ragged never cycles less
    assert (outs["ragged"][1].Stats()["spec_cycles"]
            >= outs["legacy"][1].Stats()["spec_cycles"])

  def test_greedy_model_draft_hybrid_target(self, hybrid_lm, ssm_draft_lm):
    """Hybrid-SSM target (trajectory restore on the real path) driven by
    an independent pageless draft model."""
    task, theta = hybrid_lm
    dtask, dtheta = ssm_draft_lm
    reqs = _Stream(8, seed=13)
    outs = _BothModes(
        task, theta, reqs,
        spec_fn=lambda: spec_decode.ModelDraft(dtask, dtheta, k=2))
    assert outs["ragged"][0] == outs["legacy"][0]
    assert outs["ragged"][1].Stats()["spec_cycles"] > 0

  def test_temp_gt0_dense_nospec_byte_identical(self, tiny_lm):
    """temperature > 0: every draw is keyed by (row seed, output
    position), never by step index or slot — so the ragged packing must
    reproduce the legacy stream bitwise, not just in distribution."""
    task, theta = tiny_lm
    reqs = _Stream(10, seed=14)
    outs = _BothModes(task, theta, reqs, temperature=0.8, top_k=8,
                      sample_seed=7)
    assert outs["ragged"][0] == outs["legacy"][0]

  @pytest.mark.slow
  def test_greedy_hybrid_nospec_and_repeat_stack_draft(self, hybrid_lm):
    """Matrix tail: hybrid-SSM without a draft source (zero-length rows
    must not reset SSM states) and a RepeatedTransformerLayer target
    under early-exit self-speculation."""
    task, theta = hybrid_lm
    reqs = _Stream(10, seed=15)
    outs = _BothModes(task, theta, reqs)
    assert outs["ragged"][0] == outs["legacy"][0]
    rtask, rtheta = _Instantiate(
        _LmParams().Set(use_repeat_layer=True, num_layers=3))
    reqs = _Stream(8, seed=16)
    outs = _BothModes(
        rtask, rtheta, reqs,
        spec_fn=lambda: spec_decode.SelfDraft(k=3, num_layers=1))
    assert outs["ragged"][0] == outs["legacy"][0]

  @pytest.mark.slow
  def test_temp_gt0_spec_replays(self, tiny_lm):
    """temperature > 0 WITH a draft source is distribution-preserving,
    not legacy-byte-identical (the verify coin at a position replaces
    the plain draw there) — the contract is seeded replay determinism."""
    task, theta = tiny_lm
    reqs = _Stream(8, seed=17)
    runs = []
    for _ in range(2):
      eng = _Engine(task, theta, spec_decode.SelfDraft(k=3, num_layers=1),
                    temperature=0.7, top_k=8, sample_seed=21)
      runs.append(_RunStream(eng, reqs))
    assert runs[0] == runs[1]


# -- compiled-step-program census ---------------------------------------------


class TestStepProgramCensus:

  def test_ragged_compiles_exactly_one_step_program(self, tiny_lm):
    """A full lifecycle — staggered admissions, prefill/decode overlap,
    spec cycles, a cancellation, retirements — dispatches through ONE
    compiled program."""
    task, theta = tiny_lm
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=3, num_layers=1),
                  prefix_cache=True)
    h1 = eng.Submit([5, 6, 7, 8, 9, 10, 11], 8, eos_id=None)
    h2 = eng.Submit([3, 1], 6, eos_id=None)
    for _ in range(3):           # overlap: h1 still prefilling, h2 decoding
      eng.StepOnce()
    h3 = eng.Submit([2, 2, 2], 6, eos_id=None)
    victim = eng.Submit([4, 4, 4, 4], 6, eos_id=None)
    eng.StepOnce()
    eng.Cancel(victim.id)
    while eng.sched.HasWork():
      eng.StepOnce()
    for h in (h1, h2, h3):
      assert len(h.Result(timeout=0)) > 0
    stats = eng.Stats()
    comp = stats["compile"]
    assert comp[observe_schema.COMPILE_CENSUS_KEY] == 1
    assert set(comp) & observe_schema.STEP_PROGRAM_NAMES == {"ragged"}
    assert comp["ragged"]["calls"] > 0
    assert "fallback" not in comp["ragged"]
    # the lifecycle really was mixed: prefill rode decode steps and spec
    # cycles ran — all through that one program
    assert stats["mixed_steps"] > 0
    assert stats["spec_cycles"] > 0
    assert stats["scheduler"]["cancelled"] == 1
    assert stats["scheduler"]["finished"] == 3

  def test_block_fill_counters_count_rows_of_every_step(self, tiny_lm):
    """`attend_query_blocks` / `attend_block_queries`: per step the query
    blocks the ragged kernel runs (ceil(row_len / Bq) a row) and the valid
    queries in them, known on the host. Without a draft source or prefix
    hits every prompt token and every decode token is one query, and a
    request's first output rides its last prefill chunk."""
    task, theta = tiny_lm
    eng = _Engine(task, theta)                    # pages of 4: Bq is 8
    bq = eng._AttentionLayers()[0].RaggedQueryBlock(4)
    assert eng._attend_bq == bq == 8
    reqs = [([5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17], 5),
            ([3, 1], 6), ([2, 2, 2], 4)]
    _RunStream(eng, reqs)
    stats = eng.Stats()
    assert observe_schema.ENGINE_STATS_REQUIRED <= set(stats)
    queries = stats["attend_block_queries"]
    blocks = stats["attend_query_blocks"]
    assert queries == (stats["prompt_tokens"] + stats["tokens_emitted"]
                       - len(reqs))
    # chunks are at most prefill_chunk = 4 <= Bq tokens: a block a live row
    # a step, so the fill is queries over blocks x Bq and below one
    assert 0 < blocks <= queries <= blocks * bq
    assert blocks >= stats["steps"]

  def test_legacy_trio_still_compiles_three(self, tiny_lm):
    """The comparison baseline keeps its three shapes — the 3 -> 1
    collapse is observable in the census, not just asserted in docs."""
    task, theta = tiny_lm
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=3, num_layers=1),
                  step_mode="legacy")
    _RunStream(eng, _Stream(4, seed=18))
    _RunStream(eng, [([5, 6], 3)], spec_k=0)   # opt-out -> plain decode
    comp = eng.Stats()["compile"]
    assert (set(comp) & observe_schema.STEP_PROGRAM_NAMES
            == {"decode", "mixed", "spec_verify"})
    assert comp[observe_schema.COMPILE_CENSUS_KEY] == 3


# -- BuildRaggedStep / CommitRaggedStep (device-free) -------------------------


def _MakeSched(slots=3, pages=24, page=4, table_pages=8, chunk=4, **kw):
  alloc = kv_cache.PageAllocator(pages, page)
  return scheduler_lib.Scheduler(slots, alloc, table_pages, chunk, **kw), alloc


def _Prefill(sched):
  """Drives ragged steps with fabricated draws until every live row has
  finished its prompt (or everything retired)."""
  while True:
    sched.Admit()
    batch = sched.BuildRaggedStep(16, 4)
    if batch is None:
      return
    sched.CommitRaggedStep(batch, np.full((16,), 7, np.int32))
    live = [s for s in sched.slots if s is not None]
    if all(s.state is scheduler_lib.SeqState.DECODE for s in live):
      return


class TestBuildRaggedStep:

  def test_decode_first_prefill_takes_leftover(self):
    sched, alloc = _MakeSched()
    sched.Submit(scheduler_lib.Request("a", [1, 2], 8))       # -> decode
    sched.Submit(scheduler_lib.Request("b", list(range(1, 11)), 4))
    sched.Admit()
    b1 = sched.BuildRaggedStep(8, 4, spec_k=2)
    sched.CommitRaggedStep(b1, np.full((8,), 7, np.int32))
    assert sched._by_id["a"].state is scheduler_lib.SeqState.DECODE
    # a decodes (spec_k=2 -> 3 tokens), b prefills with the leftover 5,
    # capped at wmax=4
    b2 = sched.BuildRaggedStep(8, 4, spec_k=2)
    np.testing.assert_array_equal(b2.rows_desc.row_len[:2], [3, 4])
    assert b2.row_k[0] == 2 and b2.any_spec and b2.mixed
    assert b2.prompt_tokens == 4
    # packed-token invariants: pos == row_q_pos[row] + col, trailing pad
    d = b2.rows_desc
    for tkn in range(8):
      if not d.valid[tkn]:
        continue
      r = d.row_of[tkn]
      assert d.pos[tkn] == d.row_q_pos[r] + d.col_of[tkn]
    assert d.valid.sum() == 7
    # the decode row's feedback token rides column 0; draft columns
    # stay zero until the engine fills Draft() proposals in
    assert b2.tok_ids[d.row_cols[0, 0]] == 7
    assert b2.ids[0, 0] == 7 and b2.in_len[0] == 1 and b2.in_len[1] == 0

  def test_zero_length_row_keeps_true_q_pos(self):
    """A live row that fits no budget this step must ride with its real
    q_pos: q_pos == 0 is the SSM state-reset trigger, so an idle row at
    pos > 0 advertising 0 would wipe its recurrent state."""
    sched, _ = _MakeSched(slots=2)
    sched.Submit(scheduler_lib.Request("a", list(range(1, 7)), 4))
    sched.Submit(scheduler_lib.Request("b", list(range(1, 7)), 4))
    sched.Admit()
    batch = sched.BuildRaggedStep(4, 4)   # budget covers only row a
    np.testing.assert_array_equal(batch.rows_desc.row_len, [4, 0])
    assert batch.rows_desc.row_q_pos[1] == 0  # b truly at pos 0 (prefill)
    sched.CommitRaggedStep(batch, np.full((4,), 7, np.int32))
    batch = sched.BuildRaggedStep(4, 4)
    np.testing.assert_array_equal(batch.rows_desc.row_len, [2, 2])
    assert batch.rows_desc.row_q_pos[0] == 4  # a rides at its true pos

  def test_spec_commit_rolls_back_rejected_and_eos_tail(self):
    sched, alloc = _MakeSched(slots=1)
    sched.Submit(scheduler_lib.Request("a", [1, 2, 3], 8, eos_id=9))
    _Prefill(sched)
    batch = sched.BuildRaggedStep(8, 4, spec_k=3)
    assert batch.row_k[0] == 3
    # verify accepted 2 of 3 drafts: cursor rolled back over the tail
    out = np.zeros((1, 4), np.int32)
    out[0, :3] = [5, 6, 7]
    before = alloc.Stats()["rolled_back_tokens"]
    ev = sched.CommitRaggedStep(batch, np.zeros((8,), np.int32),
                                out_tokens=out,
                                accept_len=np.array([2], np.int32))
    assert [t for _, t, _ in ev] == [5, 6, 7]
    assert alloc.Stats()["rolled_back_tokens"] - before == 1
    # eos INSIDE the accepted prefix: retire at eos, roll back the rest
    batch = sched.BuildRaggedStep(8, 4, spec_k=3)
    out[0, :3] = [5, 9, 7]
    before = alloc.Stats()["rolled_back_tokens"]
    ev = sched.CommitRaggedStep(batch, np.zeros((8,), np.int32),
                                out_tokens=out,
                                accept_len=np.array([3], np.int32))
    assert ev[-1] == ("a", 9, True)
    assert alloc.Stats()["rolled_back_tokens"] - before == 2
    assert sched.slots[0] is None


# -- cached-prefix-first admission --------------------------------------------


class TestPrefixOrderedAdmission:

  def _Pressured(self, ordered: bool) -> scheduler_lib.Scheduler:
    """A pool sized so the uncached head and the cached follower don't
    both fit: admission order decides whether the cached pages get
    reused (ordered) or sit behind the head (FIFO)."""
    alloc = kv_cache.PageAllocator(6, 4)
    cache = prefix_cache_lib.PrefixCache(alloc, None)
    sched = scheduler_lib.Scheduler(2, alloc, 4, 4, prefix_cache=cache)
    if not ordered:
      sched._NextWaiting = lambda: 0     # strict FIFO baseline
    # prime: run one request to completion so its prompt's full pages
    # land in the cache (retained there after retirement)
    prime = list(range(1, 9))            # 8 tokens = 2 full pages
    sched.Submit(scheduler_lib.Request("prime", prime, 1))
    _Prefill(sched)                      # max_new=1: retires at prefill end
    assert sched.slots[0] is None and cache.Stats()["cached_pages"] == 2
    # pressure: a big uncached head, then a follower matching the prime
    sched.Submit(scheduler_lib.Request("head", [30 + i for i in range(12)], 4))
    sched.Submit(scheduler_lib.Request("tail", prime, 4))
    sched.Admit()
    return sched

  def test_cached_follower_beats_uncached_head_under_pressure(self):
    ordered = self._Pressured(ordered=True)
    fifo = self._Pressured(ordered=False)
    o_hits = ordered.prefix_cache.Stats()["hit_tokens"]
    f_hits = fifo.prefix_cache.Stats()["hit_tokens"]
    assert o_hits > f_hits            # the whole point of the reorder
    assert o_hits == 7                # prime prompt minus the last token
    assert ordered.prefix_ordered_admissions == 1
    assert fifo.prefix_ordered_admissions == 0
    assert ordered.Stats()["prefix_ordered_admissions"] == 1
    # ordered: the cached tail is live; FIFO burned the pool on the head
    live = [s.id for s in ordered.slots if s is not None]
    assert "tail" in live
    flive = [s.id for s in fifo.slots if s is not None]
    assert flive == ["head"]

  def test_fifo_head_never_starves(self):
    """When the cache-ordered pick does not fit, the true FIFO head
    still gets its legacy try — reorder never starves the head."""
    alloc = kv_cache.PageAllocator(4, 4)
    cache = prefix_cache_lib.PrefixCache(alloc, None)
    sched = scheduler_lib.Scheduler(1, alloc, 4, 4, prefix_cache=cache)
    prime = list(range(1, 9))
    sched.Submit(scheduler_lib.Request("prime", prime, 1))
    _Prefill(sched)
    # head fits only if nothing else does; follower matches the cache
    # but needs MORE pages than remain free
    sched.Submit(scheduler_lib.Request("head", [40, 41], 2))
    sched.Submit(scheduler_lib.Request("tail", prime + [50, 51], 4))
    sched.Admit()
    live = [s.id for s in sched.slots if s is not None]
    assert live == ["head"]
    assert sched.prefix_ordered_admissions == 0
