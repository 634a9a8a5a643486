"""Tree speculation on the unified ragged step.

Covers docs/speculative_decoding.md (tree section):
- `core/ragged.py` tree descriptors: DFS depths, per-column ancestor
  bitmasks (incl. the 64-column hi-word split), `BuildRaggedRows` tree
  rows (pos_ids = q_pos + depth, anc masks, col_parent) next to chain
  rows that keep the bitwise-neutral sentinels,
- `SpecVerifyTree` acceptance: greedy picks the longest LAWFUL
  root-to-leaf argmax chain (leftmost sibling on ties, never a branch
  whose head mismatches), emits the target argmax chain itself; W == 1
  is bitwise `SpecVerifyTokens`; adversarial trees (empty/all-invalid,
  full acceptance with bonus); at temperature > 0 the full-acceptance
  bonus is bitwise the plain positional draw and (slow) the emitted
  marginal over i.i.d.-sampled siblings matches the target law,
- scheduler tree packing: `BuildRaggedStep(spec_w > 1)` rows of
  1 + row_w * row_k tokens with DFS parents, width-before-depth clamping
  under the packed-row cap (`width_clamps` counted on Stats()),
  per-request `spec_w` opt-down, and `CommitRaggedStep` rolling back
  row_w * row_k - m tree nodes,
- the engine bar: greedy tree-spec output streams BYTE-IDENTICAL to the
  non-speculative engine — SelfDraft and ModelDraft drafts, dense /
  hybrid-SSM (in-program KV repair + SSM column restore) / repeat-stack
  targets, int8 KV pools (scale-sidecar repair), prefix cache on, and
  per-request width/depth/opt-out mixing — all through EXACTLY ONE
  compiled step program; w == 1 engines reproduce chain speculation,
- tree telemetry: `spec_branches` / `spec_width_clamps` /
  `accepted_depth_hist` on engine Stats() (GShard mirror keys are
  asserted schema-wide in test_serving_engine.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu.core import ragged, sampling
from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import scheduler as scheduler_lib
from lingvo_tpu.serving import spec_decode

from tests.test_spec_decode import (_Engine, _Instantiate, _LmParams,
                                    _RunStream, _Stream)


# -- tree descriptors (core/ragged.py) ----------------------------------------


class TestTreeDescriptors:

  def test_depths_and_ancestor_masks_w2_k2(self):
    # two branches of depth 2: drafts [b0d0, b0d1, b1d0, b1d1]
    parents = [-1, 0, -1, 2]
    np.testing.assert_array_equal(ragged.TreeDepths(parents), [1, 2, 1, 2])
    lo, hi = ragged.TreeAncestorMasks(parents)
    # col 0 root=bit0; col1=root|self; col2=col1|bit2; col3=root|bit3;
    # col4=col3|bit4
    np.testing.assert_array_equal(lo, [0b1, 0b11, 0b111, 0b1001, 0b11001])
    np.testing.assert_array_equal(hi, [0, 0, 0, 0, 0])

  def test_ancestor_masks_spill_into_hi_word(self):
    # a 35-deep chain-as-tree crosses the 32-bit boundary: columns >= 32
    # carry their ancestor bits in the hi word
    r = 35
    parents = np.arange(-1, r - 1)
    lo, hi = ragged.TreeAncestorMasks(parents)
    assert lo[31] == -1 and hi[31] == 0          # bits 0..31 all set
    assert lo[35] == -1 and hi[35] == 0b1111     # bits 32..35 in hi
    with pytest.raises(AssertionError):
      ragged.TreeAncestorMasks(np.arange(-1, ragged.MAX_TREE_COLS - 1))

  def test_build_ragged_rows_tree_next_to_chain(self):
    # row 0: w=2,k=2 tree at q_pos 10; row 1: plain 3-token chain at 4
    desc = ragged.BuildRaggedRows([5, 3], [10, 4], 8, 5,
                                  row_parents={0: [-1, 0, -1, 2]})
    # KV slots stay DFS-packed (collision-free): pos = q_pos + col
    np.testing.assert_array_equal(desc.pos[:5], [10, 11, 12, 13, 14])
    # logical/rotary positions follow tree DEPTH, branches repeat depths
    np.testing.assert_array_equal(desc.pos_ids[:5], [10, 11, 12, 11, 12])
    np.testing.assert_array_equal(desc.anc_lo[:5],
                                  [0b1, 0b11, 0b111, 0b1001, 0b11001])
    np.testing.assert_array_equal(desc.col_parent[0], [-1, 0, 1, 0, 3])
    # the chain row keeps the bitwise-neutral sentinels of the pre-tree
    # build: pos_ids == pos, anc == -1 (mask reads all-ones), parent c-1
    np.testing.assert_array_equal(desc.pos_ids[5:], desc.pos[5:])
    np.testing.assert_array_equal(desc.anc_lo[5:], [-1, -1, -1])
    np.testing.assert_array_equal(desc.anc_hi[5:], [-1, -1, -1])
    np.testing.assert_array_equal(desc.col_parent[1], [-1, 0, 1, 2, 3])


# -- SpecVerifyTree (core/sampling.py) ----------------------------------------


def _ChainBranches(b, w, k):
  """The engine's static branch table: branch bi's depth-d node bi*k+d."""
  return jnp.broadcast_to(
      jnp.arange(w * k, dtype=jnp.int32).reshape(1, w, k), (b, w, k))


class TestSpecVerifyTree:

  def _Greedy(self, logits, draft, w, k, valid=None):
    b = logits.shape[0]
    r = w * k
    out, m, br = sampling.SpecVerifyTree(
        jnp.asarray(logits), jnp.asarray(draft), _ChainBranches(b, w, k),
        jnp.zeros((b, r, logits.shape[-1])), jax.random.PRNGKey(0),
        draft_valid=None if valid is None else jnp.asarray(valid))
    return np.asarray(out), np.asarray(m), np.asarray(br)

  def test_greedy_accepts_longest_lawful_branch(self):
    # w=2, k=2 over the chain-layout: target argmax after column c is
    # token c+1 only along branch 1's path; branch 0 dies at its head
    b, w, k, v = 1, 2, 2, 16
    r = w * k
    logits = np.full((b, r + 1, v), -5.0, np.float32)
    logits[:, 0, 9] = 5.0      # root argmax: 9
    logits[:, 3, 6] = 5.0      # after b1d0 (draft 2, col 3): 6
    logits[:, 4, 7] = 5.0      # after b1d1 (draft 3, col 4): 7
    draft = np.array([[8, 6, 9, 6]], np.int32)   # b0 head 8 mismatches
    out, m, br = self._Greedy(logits, draft, w, k)
    assert int(m[0]) == 2 and int(br[0]) == 1
    # emitted tokens ARE the target argmax chain: 9 (accepted head),
    # 6 (accepted depth 2), 7 (bonus after the leaf)
    np.testing.assert_array_equal(out[0], [9, 6, 7])

  def test_greedy_never_jumps_branches_mid_path(self):
    # branch 0's head matches but its depth-2 node mismatches; branch 1's
    # depth-2 node WOULD match — a lawful walk must still stop at m=1 on
    # branch 0 (root-to-leaf paths only, no cross-branch grafting)
    b, w, k, v = 1, 2, 2, 16
    r = w * k
    logits = np.full((b, r + 1, v), -5.0, np.float32)
    logits[:, 0, 9] = 5.0      # root argmax: 9 == both heads
    logits[:, 1, 6] = 5.0      # after b0d0 (col 1): 6
    logits[:, 3, 6] = 5.0      # after b1d0 (col 3): 6
    draft = np.array([[9, 4, 9, 6]], np.int32)   # only b1 continues right
    out, m, br = self._Greedy(logits, draft, w, k)
    assert int(br[0]) == 0 and int(m[0]) == 1    # leftmost tie, then stop
    np.testing.assert_array_equal(out[0][:2], [9, 6])

  def test_greedy_sibling_ties_pick_leftmost(self):
    b, w, k, v = 1, 3, 1, 8
    logits = np.full((b, w + 1, v), -5.0, np.float32)
    logits[:, :, 2] = 5.0
    draft = np.array([[2, 2, 2]], np.int32)      # all heads tie
    _, m, br = self._Greedy(logits, draft, w, k)
    assert int(m[0]) == 1 and int(br[0]) == 0

  def test_empty_tree_emits_root_argmax(self):
    # all-invalid drafts (a row_k == 0 row riding a tree verify): m == 0
    # and column 0 carries the plain root argmax
    b, w, k, v = 2, 2, 2, 8
    logits = np.random.RandomState(0).randn(b, w * k + 1, v).astype(
        np.float32)
    draft = np.zeros((b, w * k), np.int32)
    out, m, _ = self._Greedy(logits, draft, w, k,
                             valid=np.zeros((b, w * k), bool))
    assert list(m) == [0, 0]
    np.testing.assert_array_equal(out[:, 0], logits[:, 0].argmax(-1))

  def test_full_acceptance_emits_bonus_at_leaf(self):
    # drafts equal the argmax chain along branch 0: m == k and the last
    # output column is the argmax AFTER the accepted leaf (the bonus)
    b, w, k, v = 1, 2, 3, 16
    r = w * k
    logits = np.full((b, r + 1, v), -5.0, np.float32)
    chain = [3, 4, 5, 6]                         # root, d1, d2, bonus
    logits[:, 0, chain[0]] = 5.0
    for d in range(k):
      logits[:, d + 1, chain[d + 1]] = 5.0       # branch 0 cols 1..k
    draft = np.array([[3, 4, 5, 9, 9, 9]], np.int32)
    out, m, br = self._Greedy(logits, draft, w, k)
    assert int(m[0]) == k and int(br[0]) == 0
    np.testing.assert_array_equal(out[0], chain)

  def test_w1_is_bitwise_spec_verify_tokens(self):
    # chain speculation is the degenerate tree: same outputs BITWISE at
    # temperature 0 and at temperature > 0 (same stream-key convention)
    b, k, v = 3, 4, 32
    rng = np.random.RandomState(5)
    tl = rng.randn(b, k + 1, v).astype(np.float32)
    ql = rng.randn(b, k, v).astype(np.float32)
    draft = rng.randint(0, v, (b, k)).astype(np.int32)
    valid = rng.rand(b, k) < 0.8
    key = jax.random.PRNGKey(3)
    seeds = jnp.asarray([2, 4, 8], jnp.int32)
    pos = jnp.asarray([0, 5, 11], jnp.int32)
    for temp in (0.0, 0.9):
      out_c, m_c = sampling.SpecVerifyTokens(
          jnp.asarray(tl), jnp.asarray(draft), jnp.asarray(ql), key,
          temperature=temp, top_k=0, row_seeds=seeds, row_pos=pos,
          draft_valid=jnp.asarray(valid))
      out_t, m_t, br = sampling.SpecVerifyTree(
          jnp.asarray(tl), jnp.asarray(draft), _ChainBranches(b, 1, k),
          jnp.asarray(ql), key, temperature=temp, top_k=0,
          row_seeds=seeds, row_pos=pos, draft_valid=jnp.asarray(valid))
      np.testing.assert_array_equal(np.asarray(m_c), np.asarray(m_t))
      assert list(np.asarray(br)) == [0] * b
      # the engine consumes out[:, :m+1]; columns past the cut are
      # unconsumed on both sides and need not agree
      for i, mi in enumerate(np.asarray(m_c)):
        np.testing.assert_array_equal(np.asarray(out_c)[i, :mi + 1],
                                      np.asarray(out_t)[i, :mi + 1],
                                      err_msg=f"temp={temp} row={i}")

  def test_temp_full_acceptance_bonus_is_positional_draw(self):
    # peaked target + matching drafts: every branch-0 path accepts, and
    # the bonus must be bitwise the legacy SampleFromLogits draw at
    # stream position row_pos + k
    b, w, k, v = 3, 2, 2, 16
    r = w * k
    rng = np.random.RandomState(7)
    tl = rng.randn(b, r + 1, v).astype(np.float32)
    ql = np.zeros((b, r, v), np.float32)
    chain_cols = [0, 1, 2]                       # branch 0's root path
    draft = np.zeros((b, r), np.int32)
    for d in range(k):
      tok = rng.randint(v, size=b)
      tl[np.arange(b), chain_cols[d], tok] += 100.0
      ql[np.arange(b), d, tok] += 100.0
      draft[:, d] = tok
    key = jax.random.PRNGKey(11)
    seeds = jnp.asarray([5, 6, 7], jnp.int32)
    pos = jnp.asarray([0, 3, 9], jnp.int32)
    out, m, _ = sampling.SpecVerifyTree(
        jnp.asarray(tl), jnp.asarray(draft), _ChainBranches(b, w, k),
        jnp.asarray(ql), key, temperature=0.7, top_k=0, row_seeds=seeds,
        row_pos=pos)
    assert list(np.asarray(m)) == [k] * b
    legacy = sampling.SampleFromLogits(
        jnp.asarray(tl[:, k]), key, temperature=0.7, row_seeds=seeds,
        positions=pos + k)
    np.testing.assert_array_equal(np.asarray(out[:, k]),
                                  np.asarray(legacy))


@pytest.mark.slow
class TestTreeResidualSamplingLaw:

  def test_emitted_marginal_matches_target_law_over_siblings(self):
    """Multi-round sibling rejection must still emit exactly softmax(p):
    empirical frequencies over many rows with w=2 draft-sampled sibling
    heads vs the target law (TV distance). Each sibling must be drawn
    from ITS OWN declared proposal head — that's the contract the
    residual update relies on."""
    b, w, v = 4000, 2, 6
    rng = np.random.RandomState(1)
    tl = np.tile(rng.randn(1, w + 1, v).astype(np.float32), (b, 1, 1))
    ql = np.tile(rng.randn(1, w, v).astype(np.float32), (b, 1, 1))
    draft = np.stack(
        [rng.choice(v, size=(b,),
                    p=np.exp(ql[0, i]) / np.exp(ql[0, i]).sum())
         for i in range(w)], axis=1).astype(np.int32)
    out, _, _ = sampling.SpecVerifyTree(
        jnp.asarray(tl), jnp.asarray(draft), _ChainBranches(b, w, 1),
        jnp.asarray(ql), jax.random.PRNGKey(9), temperature=1.0,
        top_k=0, row_seeds=jnp.arange(b, dtype=jnp.int32),
        row_pos=jnp.zeros((b,), jnp.int32))
    freq = np.bincount(np.asarray(out[:, 0]), minlength=v) / b
    p = np.exp(tl[0, 0]) / np.exp(tl[0, 0]).sum()
    assert np.abs(freq - p).sum() < 0.05   # total-variation tolerance


# -- scheduler tree packing (device-free) -------------------------------------


def _DecodingSched(reqs, slots=2, pages=24):
  alloc = kv_cache.PageAllocator(pages, 4)
  sched = scheduler_lib.Scheduler(slots, alloc, 8)
  for r in reqs:
    sched.Submit(r)
  sched.Admit()
  while any(s is not None and s.state is scheduler_lib.SeqState.PREFILL
            for s in sched.slots):
    batch = sched.BuildRaggedStep(16, 4)
    sched.CommitRaggedStep(batch, np.full((16,), 7, np.int32))
  return sched, alloc


class TestTreeScheduler:

  def test_tree_row_packs_dfs_parents(self):
    sched, _ = _DecodingSched([
        scheduler_lib.Request("a", [1, 2], 16),            # full tree
        scheduler_lib.Request("b", [3, 4], 16, spec_w=1),  # chain opt-down
    ])
    batch = sched.BuildRaggedStep(16, 7, spec_k=2, spec_w=3)
    d = batch.rows_desc
    np.testing.assert_array_equal(d.row_len, [7, 3])
    np.testing.assert_array_equal(batch.row_k, [2, 2])
    np.testing.assert_array_equal(batch.row_w, [3, 1])
    # branch bi's depth-d node at column 1 + bi*rk + d, heads off the root
    np.testing.assert_array_equal(d.col_parent[0], [-1, 0, 1, 0, 3, 0, 5])
    # the chain row ships the bitwise-neutral pre-tree descriptors
    np.testing.assert_array_equal(d.col_parent[1], [-1, 0, 1, 2, 3, 4, 5])
    assert d.anc_lo[d.row_cols[1, 0]] == -1
    assert batch.width_clamps == 0 and batch.any_spec

  def test_width_clamps_before_depth(self):
    sched, _ = _DecodingSched([scheduler_lib.Request("a", [1, 2], 16)],
                              slots=1)
    # wmax 8 can't fit 1 + 4*3: width drops (4 -> 3 -> 2) before depth,
    # THEN depth re-expands into the freed columns ((8-1)//2 = 3)
    batch = sched.BuildRaggedStep(8, 8, spec_k=3, spec_w=4)
    assert int(batch.row_w[0]) == 2 and int(batch.row_k[0]) == 3
    assert int(batch.rows_desc.row_len[0]) == 7
    assert batch.width_clamps == 1
    assert sched.width_clamps == 1
    assert sched.Stats()["width_clamps"] == 1

  def test_stats_width_clamps_key_in_schema(self):
    sched, _ = _DecodingSched([scheduler_lib.Request("a", [1], 8)])
    assert set(sched.Stats()) == observe_schema.SCHEDULER_STATS_KEYS

  def test_budget_exhausted_tree_respects_max_new(self):
    # 2 tokens of max_new budget left => rk clamps to 2 before widths
    sched, _ = _DecodingSched([scheduler_lib.Request("a", [1, 2], 3)],
                              slots=1)
    batch = sched.BuildRaggedStep(16, 9, spec_k=4, spec_w=2)
    assert int(batch.row_k[0]) == 2 and int(batch.row_w[0]) == 2
    assert batch.width_clamps == 0

  def test_tree_writes_stay_inside_reserved_pages(self):
    """A wide tree near the end of its budget must shrink until its
    transient draft slots fit the pages reserved at admission — an
    unclamped row would scatter K/V through table entry 0 into pool
    page 0 (another sequence's page)."""
    sched, alloc = _DecodingSched(
        [scheduler_lib.Request("a", [1, 2, 3, 4, 5], 3)], slots=1)
    seq = sched._by_id["a"]
    # footprint: PagesFor(5 + 3) = 2 pages = 8 slots; feedback at slot 5
    # leaves room for only 2 draft slots -> width collapses to a chain
    batch = sched.BuildRaggedStep(16, 9, spec_k=2, spec_w=3)
    assert int(batch.row_w[0]) == 1 and int(batch.row_k[0]) == 2
    assert batch.width_clamps == 1
    cap_tok = len(alloc.PagesOf("a")) * 4
    assert int(seq.pos) + int(batch.rows_desc.row_len[0]) <= cap_tok

  def test_commit_rolls_back_losing_branches(self):
    sched, alloc = _DecodingSched([scheduler_lib.Request("a", [1, 2], 16)],
                                  slots=1)
    batch = sched.BuildRaggedStep(16, 7, spec_k=2, spec_w=3)
    seq = sched._by_id["a"]
    pos0 = seq.pos
    out = np.zeros((1, 3), np.int32)
    out[0, :2] = [5, 6]
    before = alloc.Stats()["rolled_back_tokens"]
    ev = sched.CommitRaggedStep(batch, np.zeros((16,), np.int32),
                                out_tokens=out,
                                accept_len=np.array([1], np.int32))
    # m=1 of row_w*row_k=6 nodes survive: 5 roll back, 2 tokens commit
    assert [t for _, t, _ in ev] == [5, 6]
    assert seq.pos == pos0 + 2
    assert alloc.Stats()["rolled_back_tokens"] - before == 5


# -- the engine bar: tree byte-identity through one program -------------------


class TestTreeEngine:

  def _Baseline(self, task, theta, reqs, **kw):
    return _RunStream(_Engine(task, theta, **kw), reqs)

  def _AssertTreeStats(self, eng, w):
    stats = eng.Stats()
    comp = stats["compile"]
    assert comp[observe_schema.COMPILE_CENSUS_KEY] == 1
    assert set(comp) & observe_schema.STEP_PROGRAM_NAMES == {"ragged"}
    assert stats["spec_branches"] >= w * (stats["spec_cycles"] > 0)
    # hist[m] counts per speculating ROW (several per cycle); its weighted
    # sum is exactly the accepted-token counter on the other surface
    hist = stats["accepted_depth_hist"]
    assert sum(m * n for m, n in enumerate(hist)) \
        == stats["accepted_tokens"]
    return stats

  def test_self_draft_tree_token_identical_census_one(self, tiny_lm):
    task, theta = tiny_lm
    reqs = _Stream(12, seed=7)
    base = self._Baseline(task, theta, reqs)
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=2, w=2),
                  num_pages=48)
    assert _RunStream(eng, reqs) == base
    stats = self._AssertTreeStats(eng, w=2)
    assert stats["spec_cycles"] > 0
    assert stats["kv_pages"]["free"] == eng.num_pages
    assert stats["spec"]["w"] == 2

  def test_model_draft_tree_token_identical(self, tiny_lm, ssm_draft_lm):
    task, theta = tiny_lm
    dtask, dtheta = ssm_draft_lm
    reqs = _Stream(10, seed=8)
    base = self._Baseline(task, theta, reqs)
    eng = _Engine(task, theta,
                  spec_decode.ModelDraft(dtask, dtheta, k=3, w=2),
                  num_pages=48)
    assert _RunStream(eng, reqs) == base
    self._AssertTreeStats(eng, w=2)

  def test_hybrid_ssm_target_tree_token_identical(self, hybrid_lm,
                                                  ssm_draft_lm):
    """Hybrid SSM+attention target under BOTH draft sources: rejected
    branches must restore the SSM column state AND the in-program KV
    repair must land the accepted path on the canonical chain slots."""
    task, theta = hybrid_lm
    dtask, dtheta = ssm_draft_lm
    reqs = _Stream(8, seed=9)
    base = self._Baseline(task, theta, reqs)
    for spec in (spec_decode.SelfDraft(k=2, w=2),
                 spec_decode.ModelDraft(dtask, dtheta, k=2, w=3)):
      eng = _Engine(task, theta, spec, num_pages=48)
      assert _RunStream(eng, reqs) == base, spec.Describe()
      self._AssertTreeStats(eng, w=spec.w)

  def test_repeat_stack_target_tree_token_identical(self):
    """RepeatedTransformerLayer target: the KV-repair leaf-axis probe
    must find the page axis under the extra leading repeat axis."""
    task, theta = _Instantiate(
        _LmParams().Set(use_repeat_layer=True, num_layers=3))
    reqs = _Stream(6, seed=10)
    base = self._Baseline(task, theta, reqs)
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=2, w=2),
                  num_pages=48)
    assert _RunStream(eng, reqs) == base
    self._AssertTreeStats(eng, w=2)

  def test_int8_kv_tree_token_identical(self, tiny_lm):
    """int8 KV pools: the repair scatter must move the quantized pages
    AND their per-page scale sidecars (offset axis != page axis + 1)."""
    task, theta = tiny_lm
    reqs = _Stream(8, seed=11)
    base = self._Baseline(task, theta, reqs, kv_cache_dtype="int8")
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=2, w=2),
                  kv_cache_dtype="int8", num_pages=48)
    assert _RunStream(eng, reqs) == base
    self._AssertTreeStats(eng, w=2)

  def test_prefix_cache_tree_token_identical(self, tiny_lm):
    """Tree verify over CoW-shared prefix pages: repair writes only ever
    target the row's private tail pages, so sharing survives."""
    task, theta = tiny_lm
    shared = [3, 4, 5, 6, 7, 8, 9, 10]
    reqs = [(shared + [i + 11], 5) for i in range(6)]
    base = self._Baseline(task, theta, reqs, prefix_cache=True)
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=2, w=2),
                  prefix_cache=True, num_pages=48)
    assert _RunStream(eng, reqs) == base
    stats = self._AssertTreeStats(eng, w=2)
    assert stats["prefix_hit_tokens"] > 0

  def test_w1_engine_reproduces_chain_engine(self, tiny_lm):
    """w == 1 keeps the EXACT chain step program: same outputs and same
    acceptance accounting as the pre-tree engine config."""
    task, theta = tiny_lm
    reqs = _Stream(10, seed=12)
    chain = _Engine(task, theta, spec_decode.SelfDraft(k=3))
    tree1 = _Engine(task, theta, spec_decode.SelfDraft(k=3, w=1))
    out_c = _RunStream(chain, reqs)
    out_t = _RunStream(tree1, reqs)
    assert out_c == out_t
    sc, st = chain.Stats(), tree1.Stats()
    for key in ("draft_tokens", "accepted_tokens", "accepted_len_hist",
                "spec_cycles", "tokens_emitted"):
      assert sc[key] == st[key], key
    assert st["spec_width_clamps"] == 0

  def test_per_request_knob_mixing_token_identical(self, tiny_lm):
    """spec_w=1 / spec_k=0 / narrow-tree / default rows ride the SAME
    packed steps without perturbing each other's streams."""
    task, theta = tiny_lm
    reqs = _Stream(8, seed=13)
    base = self._Baseline(task, theta, reqs)
    eng = _Engine(task, theta, spec_decode.SelfDraft(k=3, w=4),
                  num_pages=48)
    handles = []
    for i, (p, m) in enumerate(reqs):
      kw = [dict(spec_w=1), dict(spec_k=0),
            dict(spec_w=2, spec_k=1), {}][i % 4]
      handles.append(eng.Submit(p, m, eos_id=None, **kw))
    while eng.sched.HasWork():
      eng.StepOnce()
    assert [h.Result(timeout=0) for h in handles] == base
    self._AssertTreeStats(eng, w=1)
