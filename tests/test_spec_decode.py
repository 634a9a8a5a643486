"""Speculative decoding (serving/spec_decode.py + friends).

Covers docs/speculative_decoding.md:
- `SpecVerifyTokens` greedy acceptance: longest matching prefix, ragged
  `draft_valid` masking, and out_tokens == the target argmax chain (the
  bitwise-identity primitive); at temperature > 0 the all-accepted bonus
  draw is bitwise the legacy `SampleFromLogits` draw at that stream
  position and forced rejections land in the residual support,
- `GatedSSMLayer.PagedStep(collect_col_states=True)` returns per-column
  states matching the chained single-token decode path (snapshot), and
  `_SelectAcceptedCols` restores the chosen column (restore),
- scheduler `BuildRaggedStep(spec_k=...)` raggedness (opt-out rows ride
  one token wide, draft length clamped to the remaining token budget) and
  `CommitRaggedStep` cursor rollback + eos retirement mid-prefix, with
  `rolled_back_tokens` accounted on the page pool,
- the engine bar: greedy spec output streams TOKEN-IDENTICAL to the
  non-speculative engine on a seeded 20-request mixed-length stream, for
  BOTH draft sources (early-exit self-speculation and an independent
  pageless SSM draft model), including hybrid-SSM targets (state
  rollback on the real path) and draft-state catch-up after long
  neighbor prefills,
- acceptance telemetry: `draft_tokens` / `accepted_tokens` /
  `accepted_len_hist` in engine Stats(), zero/empty without a draft source,
- (slow) residual speculative sampling preserves the per-position output
  law at temperature > 0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu.core import sampling, ssm
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import scheduler as scheduler_lib
from lingvo_tpu.serving import spec_decode


# -- shared tiny models: session-scoped fixtures live in conftest.py ----------

from tests.conftest import InstantiateLm as _Instantiate  # noqa: E402
from tests.conftest import TinyLmParams as _LmParams  # noqa: E402


def _Engine(task, theta, spec=None, *, max_batch=3, num_pages=24,
            max_seq_len=32, **kw):
  return engine_lib.ServingLoop(
      task, theta, page_size=4, num_pages=num_pages, max_batch=max_batch,
      max_seq_len=max_seq_len, prefill_chunk=4, default_max_new=8,
      spec=spec, **kw)


def _Stream(n=20, seed=0, max_len=10, max_new=6):
  """Seeded mixed-length request stream (prompt, max_new) pairs."""
  rng = np.random.RandomState(seed)
  reqs = []
  for _ in range(n):
    p_len = int(rng.randint(1, max_len))
    reqs.append(([int(t) for t in rng.randint(1, 64, size=p_len)],
                 int(rng.randint(1, max_new))))
  return reqs


def _RunStream(eng, reqs, **submit_kw):
  """Submits a whole stream, drives the loop inline, returns the outputs."""
  handles = [eng.Submit(p, m, eos_id=None, **submit_kw) for p, m in reqs]
  while eng.sched.HasWork():
    eng.StepOnce()
  return [h.Result(timeout=0) for h in handles]


# -- SpecVerifyTokens ---------------------------------------------------------


class TestSpecVerifyTokens:

  def test_greedy_accepts_longest_matching_prefix(self):
    # target argmax chain per column is token (col + 1); draft matches
    # cols 0,1 then diverges, so accept_len == 2
    b, c, v = 2, 4, 8
    logits = np.full((b, c, v), -5.0, np.float32)
    for j in range(c):
      logits[:, j, j + 1] = 5.0
    draft = np.array([[1, 2, 7], [1, 5, 3]], np.int32)
    out, alen = sampling.SpecVerifyTokens(
        jnp.asarray(logits), jnp.asarray(draft), jnp.zeros((b, 3, v)),
        jax.random.PRNGKey(0))
    # out is the argmax chain itself regardless of the proposals
    np.testing.assert_array_equal(np.asarray(out),
                                  [[1, 2, 3, 4], [1, 2, 3, 4]])
    assert list(np.asarray(alen)) == [2, 1]

  def test_greedy_draft_valid_masks_ragged_tails(self):
    b, c, v = 1, 4, 8
    logits = np.full((b, c, v), -5.0, np.float32)
    logits[:, :, 2] = 5.0                       # argmax chain: 2,2,2,2
    draft = np.array([[2, 2, 2]], np.int32)     # all would match...
    valid = np.array([[True, False, False]])    # ...but the row_k was 1
    _, alen = sampling.SpecVerifyTokens(
        jnp.asarray(logits), jnp.asarray(draft), jnp.zeros((b, 3, v)),
        jax.random.PRNGKey(0), draft_valid=jnp.asarray(valid))
    assert int(alen[0]) == 1

  def test_bonus_draw_bitwise_matches_legacy_stream(self):
    # all proposals accepted (draft == target argmax under a peaked
    # target): the bonus token at the last column must be the EXACT
    # SampleFromLogits draw the non-spec engine makes at that position
    b, k, v = 3, 2, 16
    rng = np.random.RandomState(3)
    tl = rng.randn(b, k + 1, v).astype(np.float32)
    tl[:, :k] += 100.0 * np.eye(v)[rng.randint(v, size=(b, k))]
    draft = np.argmax(tl[:, :k], axis=-1).astype(np.int32)
    key = jax.random.PRNGKey(11)
    seeds = np.array([5, 6, 7], np.int32)
    pos = np.array([0, 3, 9], np.int32)
    out, alen = sampling.SpecVerifyTokens(
        jnp.asarray(tl), jnp.asarray(draft), jnp.asarray(tl[:, :k]),
        key, temperature=0.7, top_k=0, row_seeds=jnp.asarray(seeds),
        row_pos=jnp.asarray(pos))
    assert list(np.asarray(alen)) == [k] * b
    legacy = sampling.SampleFromLogits(
        jnp.asarray(tl[:, k]), key, temperature=0.7,
        row_seeds=jnp.asarray(seeds), positions=jnp.asarray(pos + k))
    np.testing.assert_array_equal(np.asarray(out[:, k]),
                                  np.asarray(legacy))

  def test_forced_rejection_samples_from_residual_support(self):
    # the draft proposes a token the (top-k-masked) target gives zero
    # mass: p(d) == 0 forces rejection, and the replacement must come
    # from the residual support {t : p(t) > q(t)}
    b, v = 4, 8
    tl = np.full((b, 2, v), -1.0, np.float32)
    tl[:, :, 0] = 8.0                     # target mass ~all on token 0
    ql = np.full((b, 1, v), -1.0, np.float32)
    ql[:, :, 5] = 8.0                     # draft mass ~all on token 5
    draft = np.full((b, 1), 5, np.int32)
    out, alen = sampling.SpecVerifyTokens(
        jnp.asarray(tl), jnp.asarray(draft), jnp.asarray(ql),
        jax.random.PRNGKey(2), temperature=1.0, top_k=2,
        row_seeds=jnp.arange(b, dtype=jnp.int32),
        row_pos=jnp.zeros((b,), jnp.int32))
    assert list(np.asarray(alen)) == [0] * b
    assert all(int(t) == 0 for t in np.asarray(out[:, 0]))


# -- SSM per-column state collection + rollback -------------------------------


class TestSsmColStates:

  def _Layer(self):
    p = ssm.GatedSSMLayer.Params().Set(
        name="s", input_dim=16, hidden_dim=16, num_heads=2, state_dim=4,
        chunk_size=4)
    return _Instantiate(p, seed=4)

  def test_col_states_match_single_token_chain(self):
    layer, theta = self._Layer()
    b, c = 3, 5
    x = jax.random.normal(jax.random.PRNGKey(7), (b, c, 16))
    states = layer.InitPagedStates(theta, 2, 4, b)
    tables = jnp.zeros((b, 1), jnp.int32)
    q_pos = jnp.array([4, 4, 4], jnp.int32)   # != 0: no device-side reset
    in_len = jnp.array([c, 3, 0], jnp.int32)
    out_c, ns = layer.PagedStep(theta, x, states, tables, q_pos, in_len,
                                collect_col_states=True)
    assert "col_states" in ns and ns.col_states.shape[1] == c
    # the final state IS the last column's snapshot (same computation)
    np.testing.assert_array_equal(np.asarray(ns.state),
                                  np.asarray(ns.col_states[:, -1]))
    # masked columns must leave the state untouched: row 1 (in_len 3)
    # freezes after col 2, row 2 (in_len 0) never moves
    np.testing.assert_array_equal(np.asarray(ns.col_states[1, 2]),
                                  np.asarray(ns.col_states[1, 4]))
    np.testing.assert_array_equal(np.asarray(ns.col_states[2, 0]),
                                  np.asarray(ns.col_states[2, 4]))
    np.testing.assert_array_equal(np.asarray(ns.col_states[2, 4]),
                                  np.asarray(states.state[2]))
    # reference: C single-token PagedSteps (the per-token decode path). The
    # projections batch over C in collect mode, so cross-path agreement is
    # float-tolerance, not bitwise — same bar the mixed prefill+decode
    # step already meets vs per-token decode
    ref = states
    out_ref = []
    for j in range(c):
      oj, ref = layer.PagedStep(theta, x[:, j:j + 1], ref, tables,
                                q_pos + j,
                                (in_len > j).astype(jnp.int32))
      out_ref.append(oj[:, 0])
      np.testing.assert_allclose(np.asarray(ns.col_states[:, j]),
                                 np.asarray(ref.state),
                                 rtol=1e-5, atol=1e-6, err_msg=f"col {j}")
    np.testing.assert_allclose(np.asarray(out_c),
                               np.asarray(jnp.stack(out_ref, 1)),
                               rtol=1e-5, atol=1e-5)

  def test_select_accepted_cols_restores_snapshot(self):
    b, c, n, h, s = 4, 3, 2, 3, 5
    cols = np.arange(b * c * n * h * s, dtype=np.float32).reshape(
        b, c, n, h, s)
    tree = NestedMap(
        layer=NestedMap(state=jnp.asarray(cols[:, -1]),
                        col_states=jnp.asarray(cols)),
        passthrough=[NestedMap(pool=jnp.ones((2, 2)))])
    alen = jnp.array([0, 2, 1, 0], jnp.int32)
    out = spec_decode._SelectAcceptedCols(tree, alen)
    assert "col_states" not in out.layer       # trajectory stripped
    for i, m in enumerate([0, 2, 1, 0]):
      np.testing.assert_array_equal(np.asarray(out.layer.state[i]),
                                    cols[i, m])
    # unrelated leaves (paged KV pools) pass through untouched
    np.testing.assert_array_equal(np.asarray(out.passthrough[0].pool),
                                  np.ones((2, 2)))


# -- scheduler verify-step lifecycle (device-free) ----------------------------


# the packed axis of an engine with draft depth 4 over 2 slots and a
# prefill budget of 4: 2 * (1 + 4) + 4 tokens, the widest row 1 + 4
_SPEC_K, _T, _WMAX = 4, 14, 5


def _BuildSpec(sched):
  return sched.BuildRaggedStep(_T, _WMAX, spec_k=_SPEC_K)


def _DecodingSched(reqs, slots=2):
  """Admits reqs and fast-forwards every row to DECODE with one token out."""
  alloc = kv_cache.PageAllocator(16, 4)
  sched = scheduler_lib.Scheduler(slots, alloc, 4)
  for r in reqs:
    sched.Submit(r)
  sched.Admit()
  while any(s is not None and s.state is scheduler_lib.SeqState.PREFILL
            for s in sched.slots):
    batch = sched.BuildRaggedStep(_T, _WMAX)
    sched.CommitRaggedStep(batch, np.full((_T,), 7, np.int32))
  return sched, alloc


class TestVerifySchedulerLifecycle:

  def test_build_verify_raggedness_and_optout(self):
    sched, _ = _DecodingSched([
        scheduler_lib.Request("a", [1, 2, 3], 8),            # full k
        scheduler_lib.Request("b", [4, 5], 8, spec_k=0),     # opted out
    ])
    vb = _BuildSpec(sched)
    assert vb.any_spec and not vb.mixed
    assert list(vb.row_k) == [4, 0]
    assert list(vb.rows_desc.row_len) == [5, 1]
    assert list(vb.in_len) == [1, 0]        # only the drafting row drafts
    cols0 = vb.rows_desc.row_cols[:, 0]
    assert list(vb.tok_ids[cols0]) == [7, 7]   # last emitted token
    assert list(vb.ids[:, 0]) == [7, 7]
    assert list(vb.q_pos) == [3, 2]

  def test_build_verify_clamps_to_remaining_budget(self):
    # max_new == 2 and one token already out: only 1 more may ever be
    # written, so row_k must clamp to 1 (KV writes stay inside the pages
    # reserved at admission)
    sched, _ = _DecodingSched([scheduler_lib.Request("a", [1, 2], 2)])
    vb = _BuildSpec(sched)
    assert vb.row_k[0] == 1 and vb.rows_desc.row_len[0] == 2

  def test_nothing_to_verify_during_prefill_or_all_optout(self):
    alloc = kv_cache.PageAllocator(16, 4)
    sched = scheduler_lib.Scheduler(2, alloc, 4)
    sched.Submit(scheduler_lib.Request("a", [1, 2, 3, 4, 5, 6], 4))
    sched.Admit()
    vb = _BuildSpec(sched)                      # still prefilling
    assert vb.mixed and not vb.any_spec and not vb.row_k.any()
    sched2, _ = _DecodingSched(
        [scheduler_lib.Request("b", [1], 8, spec_k=0)])
    vb = _BuildSpec(sched2)                     # nobody speculates
    assert not vb.any_spec and not vb.row_k.any()
    assert list(vb.rows_desc.row_len) == [1, 0] and not vb.in_len.any()

  @pytest.mark.parametrize(
      "req_kw,max_new,out,alen,want,finish,rolled_back", [
          # 2 accepted + 1 correction committed; 2 drafted tokens rolled back
          ({}, 8, [11, 12, 13, 14, 15], 2,
           [("a", 11, False), ("a", 12, False), ("a", 13, False)], None, 2),
          # eos at the 2nd committed token: the stream truncates there, the
          # row retires, its pages free, and the 3 unconsumed accepted
          # tokens are rolled back on top of the 0 rejected ones
          ({"eos_id": 12}, 8, [11, 12, 13, 14, 15], 4,
           [("a", 11, False), ("a", 12, True)], "eos", 3),
          # row_k clamps to 3 - 1 = 2; the correction is never emitted
          ({}, 3, [11, 12, 13, 0, 0], 2,
           [("a", 11, False), ("a", 12, True)], "length", 1),
      ], ids=["rejected_tail", "eos_mid_prefix", "max_new_truncates"])
  def test_commit_spec_row(self, req_kw, max_new, out, alen, want, finish,
                           rolled_back):
    sched, alloc = _DecodingSched(
        [scheduler_lib.Request("a", [1, 2], max_new, **req_kw)])
    seq = sched._by_id["a"]
    pos0 = seq.pos
    vb = _BuildSpec(sched)
    assert vb.row_k[0] == min(_SPEC_K, max_new - 1)
    events = sched.CommitRaggedStep(
        vb, np.zeros((_T,), np.int32), np.array([out], np.int32),
        np.array([alen], np.int32))
    assert events == want
    tokens = [tok for _, tok, _ in want]
    assert seq.pos == pos0 + len(tokens)
    assert seq.out[-len(tokens):] == tokens
    assert seq.finish_reason == finish
    if finish is not None:
      assert alloc.num_free == alloc.num_pages and sched.slots[0] is None
    assert alloc.rolled_back_tokens == rolled_back
    assert alloc.Stats()["rolled_back_tokens"] == rolled_back


# -- the engine bar: token identity + telemetry -------------------------------


class TestSpecEngine:

  def _Baseline(self, task, theta, reqs):
    return _RunStream(_Engine(task, theta), reqs)

  def test_self_draft_20_request_stream_token_identical(self, tiny_lm):
    task, theta = tiny_lm
    reqs = _Stream(20)
    base = self._Baseline(task, theta, reqs)
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=4, num_layers=1))
    assert _RunStream(eng, reqs) == base
    stats = eng.Stats()
    assert stats["spec_cycles"] > 0
    assert stats["draft_tokens"] >= stats["accepted_tokens"] >= 0
    assert sum(m * n for m, n in enumerate(stats["accepted_len_hist"])) \
        == stats["accepted_tokens"]
    assert stats["kv_pages"]["free"] == eng.num_pages

  def test_model_draft_20_request_stream_token_identical(self, tiny_lm,
                                                         ssm_draft_lm):
    task, theta = tiny_lm
    dtask, dtheta = ssm_draft_lm
    reqs = _Stream(20, seed=1)
    base = self._Baseline(task, theta, reqs)
    eng = _Engine(task, theta, spec_decode.ModelDraft(dtask, dtheta, k=4))
    assert _RunStream(eng, reqs) == base
    stats = eng.Stats()
    assert stats["spec_cycles"] > 0 and stats["draft_tokens"] > 0

  def test_hybrid_target_rollback_token_identical(self, hybrid_lm):
    """Hybrid SSM+attention target: rejected verify columns must roll the
    recurrent state back (snapshot-and-restore on the real path)."""
    task, theta = hybrid_lm
    reqs = _Stream(8, seed=2)
    base = self._Baseline(task, theta, reqs)
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=4, num_layers=1))
    assert _RunStream(eng, reqs) == base
    stats = eng.Stats()
    # a 1-layer draft of a 2-layer hybrid WILL mispredict sometimes;
    # identity above proves those rejections restored the SSM state
    assert stats["spec_cycles"] > 0

  def test_repeat_stack_prefix_draft_token_identical(self):
    """RepeatedTransformerLayer target: the early-exit prefix slices the
    scanned theta/states to the leading repeats, suffix states pass
    through untouched."""
    task, theta = _Instantiate(
        _LmParams().Set(use_repeat_layer=True, num_layers=3))
    reqs = _Stream(6, seed=6)
    base = self._Baseline(task, theta, reqs)
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=3, num_layers=1))
    assert _RunStream(eng, reqs) == base
    assert eng.Stats()["spec_cycles"] > 0

  def test_full_depth_self_draft_accepts_everything(self, tiny_lm):
    """num_layers == full depth makes the draft argmax == target argmax,
    so greedy acceptance must be total (up to budget clamps)."""
    task, theta = tiny_lm
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=4, num_layers=2))
    prompts = np.array([[5, 6, 7, 8], [9, 10, 0, 0]], np.int32)
    out = eng.RunBatch(prompts, np.array([4, 2], np.int32), 8)
    base = _Engine(task, theta).RunBatch(
        prompts, np.array([4, 2], np.int32), 8)
    np.testing.assert_array_equal(out, base)
    stats = eng.Stats()
    assert stats["accepted_tokens"] == stats["draft_tokens"] > 0

  def test_model_draft_drains_backlog_after_long_prefill(self, tiny_lm,
                                                         ssm_draft_lm):
    """A decode row riding many mixed steps (neighbor prefilling a long
    prompt) accumulates draft-state backlog > k+1; the drain path must
    catch up without breaking identity."""
    task, theta = tiny_lm
    dtask, dtheta = ssm_draft_lm
    long_prompt = [int(t) for t in
                   np.random.RandomState(5).randint(1, 64, size=24)]
    reqs = [([3, 1, 4], 16), (long_prompt, 4)]
    base = self._Baseline(task, theta, reqs)
    eng = _Engine(task, theta, spec_decode.ModelDraft(dtask, dtheta, k=2),
                  max_batch=2, num_pages=32, max_seq_len=40)
    assert _RunStream(eng, reqs) == base

  def test_eos_mid_verify_on_engine(self, tiny_lm):
    """eos emitted inside an accepted prefix: spec engine must truncate
    exactly where the non-spec engine stops."""
    task, theta = tiny_lm
    base_eng = _Engine(task, theta)
    h = base_eng.Submit([5, 6, 7, 8], 8, eos_id=None)
    while base_eng.sched.HasWork():
      base_eng.StepOnce()
    ref = h.Result(timeout=0)
    eos = ref[2]   # a token the model verifiably emits mid-stream
    truncated = ref[:ref.index(eos) + 1]
    for spec in (spec_decode.SelfDraft(k=4, num_layers=2),
                 spec_decode.SelfDraft(k=4, num_layers=1)):
      eng = _Engine(task, theta, spec)
      h2 = eng.Submit([5, 6, 7, 8], 8, eos_id=eos)
      while eng.sched.HasWork():
        eng.StepOnce()
      assert h2.Result(timeout=0) == truncated
      assert h2.finish_reason == "eos"
      assert eng.Stats()["kv_pages"]["free"] == eng.num_pages

  def test_stats_telemetry_surface(self, tiny_lm):
    from lingvo_tpu.observe import schema as observe_schema
    task, theta = tiny_lm
    plain = _Engine(task, theta)
    stats = plain.Stats()
    observe_schema.ValidateEngineStats(stats)
    # the keys exist on EVERY engine; without a draft source they are zero
    assert stats["spec_cycles"] == 0 and stats["draft_tokens"] == 0
    assert stats["accepted_tokens"] == 0
    assert stats["accepted_len_hist"] == [] and "spec" not in stats
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=3, num_layers=1))
    eng.RunBatch(np.array([[5, 6]], np.int32), np.array([2], np.int32), 6)
    stats = eng.Stats()
    observe_schema.ValidateEngineStats(stats)
    assert stats["spec"] == {"draft": "self", "k": 3, "w": 1,
                             "num_layers": 1}
    assert len(stats["accepted_len_hist"]) == 4   # k + 1 buckets
    assert sum(stats["accepted_len_hist"]) == stats["spec_cycles"]

  def test_rollback_counter_consistent_with_acceptance(self, tiny_lm):
    task, theta = tiny_lm
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=4, num_layers=1))
    reqs = _Stream(6, seed=3)
    _RunStream(eng, reqs)
    stats = eng.Stats()
    rejected = stats["draft_tokens"] - stats["accepted_tokens"]
    # rolled_back >= rejected: every rejected draft rolls back, plus any
    # accepted-but-eos/budget-truncated corrections
    assert stats["kv_pages"]["rolled_back_tokens"] >= rejected

  def test_model_draft_rejects_paged_draft_models(self, tiny_lm):
    task, theta = tiny_lm
    with pytest.raises(AssertionError, match="pageless"):
      _Engine(task, theta, spec_decode.ModelDraft(task, theta, k=2))


# -- residual speculative sampling law (slow) ---------------------------------


@pytest.mark.slow
class TestResidualSamplingLaw:

  def test_emitted_marginal_matches_target_law(self):
    """Accept-or-residual must emit exactly softmax(p) at each position:
    empirical frequencies over many independent rows vs the target law."""
    b, v = 4000, 6
    rng = np.random.RandomState(0)
    tl = np.tile(rng.randn(1, 2, v).astype(np.float32), (b, 1, 1))
    ql = np.tile(rng.randn(1, 1, v).astype(np.float32), (b, 1, 1))
    # draft proposals drawn from q's own law so acceptance is realistic
    qp = np.exp(ql[0, 0]) / np.exp(ql[0, 0]).sum()
    draft = rng.choice(v, size=(b, 1), p=qp).astype(np.int32)
    out, _ = sampling.SpecVerifyTokens(
        jnp.asarray(tl), jnp.asarray(draft), jnp.asarray(ql),
        jax.random.PRNGKey(9), temperature=1.0, top_k=0,
        row_seeds=jnp.arange(b, dtype=jnp.int32),
        row_pos=jnp.zeros((b,), jnp.int32))
    freq = np.bincount(np.asarray(out[:, 0]), minlength=v) / b
    p = np.exp(tl[0, 0]) / np.exp(tl[0, 0]).sum()
    assert np.abs(freq - p).sum() < 0.05   # total-variation tolerance

  def test_spec_engine_temp_gt0_runs_and_replays(self, tiny_lm):
    task, theta = tiny_lm
    reqs = _Stream(6, seed=4)
    outs = []
    for _ in range(2):
      eng = _Engine(task, theta,
                    spec_decode.SelfDraft(k=3, num_layers=1),
                    temperature=0.8, top_k=8, sample_seed=13)
      outs.append(_RunStream(eng, reqs))
    assert outs[0] == outs[1]   # engine-level replayability survives spec
