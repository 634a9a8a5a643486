"""One ragged step program (serving/scheduler.py + serving/engine.py).

Covers docs/ragged_step.md:
- engine-vs-reference TOKEN IDENTITY: every request of a seeded
  mixed-length stream comes out token-for-token as the dense per-request
  rollout of tests/test_serving_engine.py, which shares no scheduler,
  allocator, pool or packed step with the engine — greedy (`_GreedyRef`)
  across draft sources (none / SelfDraft / ModelDraft) and target shapes
  (dense / hybrid-SSM), with the prefix cache on and off; temperature > 0
  without a draft source (`_SampledRef`) is identical too (per-token draws
  are position-indexed, so packing never moves a request's sampling
  stream),
- the compiled-program census: one serving lifetime with admissions,
  prefill/decode overlap, spec cycles, a cancellation and retirements
  compiles EXACTLY ONE step program (`Stats()["compile"]` census == 1,
  name "ragged", no fallback),
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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu.core import base_layer
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core.nested_map import NestedMap

from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import prefix_cache as prefix_cache_lib
from lingvo_tpu.serving import scheduler as scheduler_lib
from lingvo_tpu.serving import spec_decode

from tests.test_serving_engine import _GreedyRef, _SampledRef
from tests.test_spec_decode import (_Instantiate, _LmParams, _Stream,
                                    _RunStream)  # noqa: F401
# tiny_lm / hybrid_lm / ssm_draft_lm fixtures: session-scoped in conftest.py


def _Engine(task, theta, spec=None, **kw):
  kw.setdefault("page_size", 4)
  kw.setdefault("num_pages", 24)
  kw.setdefault("max_batch", 3)
  kw.setdefault("max_seq_len", 32)
  kw.setdefault("prefill_chunk", 4)
  kw.setdefault("default_max_new", 8)
  return engine_lib.ServingLoop(task, theta, spec=spec, **kw)


def _ServeAgainstGreedyRef(task, theta, reqs, spec=None, **kw):
  """Runs one stream through an engine, holds every request's tokens to
  the dense greedy rollout of its prompt, and returns the engine."""
  eng = _Engine(task, theta, spec, **kw)
  outs = _RunStream(eng, reqs)
  for i, ((prompt, max_new), out) in enumerate(zip(reqs, outs)):
    assert out == _GreedyRef(task, theta, prompt, max_new), (i, kw)
  return eng


# -- the engine against the dense per-request rollout -------------------------


class TestEngineMatchesDenseReference:

  def test_greedy_dense_nospec_prefix_on_and_off(self, tiny_lm):
    """Greedy, no draft source — with a repeated-prompt stream so the
    prefix cache actually shares pages in the cache-on arm."""
    task, theta = tiny_lm
    shared = ([7, 3, 7, 3, 7, 3, 7, 3, 7], 4)  # > 2 full pages of prompt
    reqs = [shared] + _Stream(12, seed=11) + [shared]
    # the first copy retires (and inserts its pages) long before the
    # last admits, so the cache-on arm sees a real hit + CoW split
    for cache in (False, True):
      eng = _ServeAgainstGreedyRef(task, theta, reqs, prefix_cache=cache)
      if cache:
        assert eng.Stats()["prefix_cache"]["hit_tokens"] > 0

  def test_greedy_self_draft(self, tiny_lm):
    task, theta = tiny_lm
    eng = _ServeAgainstGreedyRef(
        task, theta, _Stream(10, seed=12),
        spec_decode.SelfDraft(k=3, num_layers=1))
    assert eng.Stats()["spec_cycles"] > 0

  def test_greedy_model_draft_hybrid_target(self, hybrid_lm, ssm_draft_lm):
    """Hybrid-SSM target (trajectory restore on the real path) driven by
    an independent pageless draft model."""
    task, theta = hybrid_lm
    dtask, dtheta = ssm_draft_lm
    eng = _ServeAgainstGreedyRef(
        task, theta, _Stream(8, seed=13),
        spec_decode.ModelDraft(dtask, dtheta, k=2))
    assert eng.Stats()["spec_cycles"] > 0

  def test_temp_gt0_dense_nospec_draws_the_request_stream(self, tiny_lm):
    """temperature > 0: every draw is keyed by (row seed, output
    position), never by step index or slot — so the packed step must
    reproduce each request's own stream bitwise, not just in
    distribution."""
    task, theta = tiny_lm
    reqs = _Stream(10, seed=14)
    sampling_kw = dict(temperature=0.8, top_k=8, sample_seed=7)
    eng = _Engine(task, theta, **sampling_kw)
    handles = [eng.Submit(p, m, eos_id=None, seed=40 + i)
               for i, (p, m) in enumerate(reqs)]
    while eng.sched.HasWork():
      eng.StepOnce()
    for i, ((prompt, max_new), h) in enumerate(zip(reqs, handles)):
      assert h.Result(timeout=0) == _SampledRef(
          task, theta, prompt, max_new, seed=40 + i, **sampling_kw), i

  @pytest.mark.slow
  def test_greedy_hybrid_nospec_and_repeat_stack_draft(self, hybrid_lm):
    """Matrix tail: hybrid-SSM without a draft source (zero-length rows
    must not reset SSM states) and a RepeatedTransformerLayer target
    under early-exit self-speculation."""
    task, theta = hybrid_lm
    _ServeAgainstGreedyRef(task, theta, _Stream(10, seed=15))
    rtask, rtheta = _Instantiate(
        _LmParams().Set(use_repeat_layer=True, num_layers=3))
    _ServeAgainstGreedyRef(rtask, rtheta, _Stream(8, seed=16),
                           spec_decode.SelfDraft(k=3, num_layers=1))

  @pytest.mark.slow
  def test_temp_gt0_spec_replays(self, tiny_lm):
    """temperature > 0 WITH a draft source is distribution-preserving,
    not identical to the plain stream (the verify coin at a position
    replaces the plain draw there) — the contract is seeded replay
    determinism."""
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
    bq = task.stack.MixerLayers()[0][0].RaggedQueryBlock(4)
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


# -- the stacked pool rides the scan over layers as a carry --------------------

_NP, _PS, _SLOTS, _TPAGES, _T, _WMAX = 12, 4, 4, 6, 16, 8
# page-pool leaves of the decode state: K and V, and their int8 scale sidecars
# (the period body's four attention layers share ONE pool, K and V)
_POOL_LEAVES = {"f32": 2, "int8": 4, "hybrid": 2, "period": 2}


def _RepeatLm(kind):
  """(task, theta, kv_cache_dtype) of a tiny LM whose stack is a
  RepeatedTransformerLayer: 3 TransformerLayers, or 2 repeats of a stacked
  [ssm, attention] block (DenseLmSsmHybrid's shape)."""
  if kind == "hybrid":
    p = _LmParams(every_n=2, use_repeat=True, num_layers=4)
  elif kind == "period":
    # 2 repeats of [full without rotary, window, window, window] over
    # grouped KV heads, every feed-forward a dropless expert layer
    # (SmallThinker's body, models/lm/params/smallthinker.py)
    from lingvo_tpu.core import moe
    from lingvo_tpu.core import attention
    p = _LmParams(num_layers=8).Set(
        use_repeat_layer=True, num_heads=4,
        atten_tpl=attention.MultiHeadedAttention.Params().Set(
            num_kv_heads=2, dim_per_head=4),
        sliding_window_size=5, sliding_window_layout=[0, 1, 1, 1],
        rope_layout=[0, 1, 1, 1],
        expert_ffn_tpl=moe.DroplessMoELayer.Params().Set(
            hidden_dim=16, num_experts=4, num_experts_per_token=2))
  else:
    p = _LmParams(num_layers=3).Set(use_repeat_layer=True)
  task, theta = _Instantiate(p, seed=5)
  return task, theta, {"bf16": "bfloat16", "int8": "int8"}.get(kind)


def _RandomStates(task, theta, kv_cache_dtype, seed):
  """A decode state with every element set, trash pages included, so that
  an op that moves or drops any part of it shows."""
  states = task.InitPagedDecodeState(theta, _NP, _PS, _SLOTS, kv_cache_dtype)
  rng = np.random.RandomState(seed)

  def _Fill(x):
    if x.dtype == jnp.int8:
      return jnp.asarray(rng.randint(-127, 128, x.shape), jnp.int8)
    return jnp.asarray(rng.uniform(0.1, 1.0, x.shape), x.dtype)

  return jax.tree_util.tree_map(_Fill, states)


def _Pack(tree_row=False, all_padding=False):
  """A decode row, a prefill chunk, a 4-token verify row (a tree when
  asked), an empty slot, and 5 padding tokens at the end of the axis."""
  if all_padding:
    lens, q_pos, parents = [0, 0, 0, 0], [6, 10, 8, 1], None
  else:
    lens, q_pos = [1, 6, 4, 0], [5, 4, 7, 1]
    parents = {2: [-1, 0, -1]} if tree_row else None
  rows = ragged_lib.BuildRaggedRows(lens, q_pos, _T, _WMAX,
                                    row_parents=parents)
  return ragged_lib.RaggedRows(*(jnp.asarray(x) for x in rows))


def _Tables(stale=False, layers=0):
  """Disjoint pages per row; dead entries 0, or (stale) other rows' pages,
  -5 and 99, with a LIVE entry of row 1 at _NP + 2: past this layer's
  pages, inside the next layer's in a stack viewed as one pool. layers: a
  block of two kinds of layer reads a table a layer, [layers, B, t_pages]
  (the same one here: its layers then write the same pages of their one
  pool one after the other, in the scan as in the loop)."""
  tables = np.zeros((_SLOTS, _TPAGES), np.int32)
  tables[0, :2] = [0, 1]
  tables[1, :3] = [2, 3, 4]
  tables[2, :3] = [5, 6, 7]
  if stale:
    tables[0, 2:] = [3, 99, -5, 6]
    tables[1, 2] = _NP + 2
    tables[1, 3:] = [0, 5, 99]
    tables[3] = [2, 7, -1, 99, 1, 4]
  if layers:
    tables = np.stack([tables] * layers)
  return jnp.asarray(tables)


def _LayerLoopStep(task, theta, ids, states, tables, rows, **kw):
  """task.RaggedStep with the repeat walked in a Python loop: layer i runs
  body.RaggedStep on slice [i] of every leaf and the results are stacked,
  which is what the scan computed when the states were its xs and ys.
  Each layer is one compiled call, as the scan's body is one computation:
  a loop unrolled into ONE program is fused across layers on the CPU and
  differs from the scan (the old one too) in the last bit."""
  rep = task.stack
  x = jax.jit(task.emb.EmbLookup)(theta.emb, ids)
  step = jax.jit(lambda th, x, st: rep.body.RaggedStep(th, x, st, tables,
                                                       rows, **kw))
  per_layer = []
  for i in range(rep.p.num_layers):
    mine = lambda tree: jax.tree_util.tree_map(lambda a: a[i], tree)  # pylint: disable=cell-var-from-loop
    x, ns = step(mine(theta.stack.body), x, mine(states.body))
    per_layer.append(ns)
  new_body = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *per_layer)

  def _Head(theta, x):
    x = task.final_ln.FProp(theta.final_ln, x)
    return task.emb.Logits(theta.emb, x)

  return jax.jit(_Head)(theta, x), NestedMap(body=new_body)


def _AssertTreesBitwiseEqual(got, want):
  got_flat, got_def = jax.tree_util.tree_flatten_with_path(got)
  want_flat, want_def = jax.tree_util.tree_flatten_with_path(want)
  assert got_def == want_def, (got_def, want_def)
  for (path, a), (_, b) in zip(got_flat, want_flat):
    name = jax.tree_util.keystr(path)
    assert a.shape == b.shape and a.dtype == b.dtype, (name, a, b)
    np.testing.assert_array_equal(
        np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8),
        err_msg=name)


class TestRepeatedStepCarriesThePool:

  @pytest.mark.parametrize("kind,kw,tree_row,stale", [
      ("bf16", {}, False, False),
      ("int8", {}, False, False),
      ("hybrid", {}, False, False),
      ("hybrid", {"ssm_col_states": True}, True, False),
      ("f32", {}, True, False),
      ("f32", {}, False, True),
      ("int8", {}, True, True),
      ("period", {}, False, False),
  ], ids=["bf16_pool", "int8_pool_with_scales", "hybrid", "hybrid_col_states",
          "tree_row", "stale_and_out_of_range_tables", "int8_tree_stale",
          "period_of_window_and_expert_layers"])
  def test_step_is_bitwise_the_layer_loop(self, kind, kw, tree_row, stale):
    """Logits and the WHOLE returned state (trash pages, other layers'
    pages, SSM slots and `col_states`) equal the plain layer loop's."""
    task, theta, kv = _RepeatLm(kind)
    states = _RandomStates(task, theta, kv, seed=3)
    rows = _Pack(tree_row)
    tables = _Tables(stale, layers=4 if kind == "period" else 0)
    ids = jnp.asarray(
        np.random.RandomState(4).randint(0, 64, (1, _T)), jnp.int32)
    logits, new_states = jax.jit(
        lambda th, st: task.RaggedStep(th, ids, st, tables, rows, **kw))(
            theta, states)
    ref_logits, ref_states = _LayerLoopStep(task, theta, ids, states, tables,
                                            rows, **kw)
    _AssertTreesBitwiseEqual(logits, ref_logits)
    _AssertTreesBitwiseEqual(new_states, ref_states)
    # and the step did write: the chunk's pages differ from what came in
    assert not np.array_equal(np.asarray(new_states.body.Flatten()[0]),
                              np.asarray(states.body.Flatten()[0]))

  @pytest.mark.parametrize("kind", ["f32", "int8", "hybrid", "period"])
  def test_scan_carries_every_state_leaf(self, kind):
    """In the jaxpr of task.RaggedStep the scan over layers has every
    stacked state leaf among its CARRIES and nothing of a pool's shape
    among its constants, scanned inputs or scanned outputs: what a new
    body (MoE, Mamba) has to keep for the step not to copy the pool."""
    task, theta, kv = _RepeatLm(kind)
    states = _RandomStates(task, theta, kv, seed=3)
    rows, tables = _Pack(), _Tables(layers=4 if kind == "period" else 0)
    ids = jnp.zeros((1, _T), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda th, st: task.RaggedStep(th, ids, st, tables, rows))(
            theta, states)
    reps = task.stack.p.num_layers
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == reps]
    assert len(scans) == 1, [e.primitive.name for e in jaxpr.jaxpr.eqns]
    scan = scans[0]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    aval = lambda vs: [(v.aval.shape, v.aval.dtype) for v in vs]
    consts = aval(scan.invars[:n_consts])
    carries = aval(scan.invars[n_consts:n_consts + n_carry])
    scanned = (aval(scan.invars[n_consts + n_carry:])
               + aval(scan.outvars[n_carry:]))
    leaves = aval(jax.tree_util.tree_leaves(states))
    pools = [(shape, dtype) for shape, dtype in leaves if shape[1] == _NP]
    assert len(pools) == _POOL_LEAVES[kind], leaves
    for leaf in leaves:
      assert leaf in carries, (leaf, carries)
    assert aval(scan.outvars[:n_carry]) == carries
    for shape, dtype in pools:
      for where in (consts, scanned):
        # neither the stack nor one layer's pool
        assert (shape, dtype) not in where, (shape, where)
        assert (shape[1:], dtype) not in where, (shape, where)

  @pytest.mark.parametrize("kind", ["f32", "int8", "hybrid"])
  def test_padding_writes_only_its_own_layers_trash_page(self, kind):
    """A pack of nothing but padding tokens changes at most page NP - 1 of
    every layer and not one element besides: no layer writes outside
    [i * NP, (i + 1) * NP). K and V move by runs (ops/run_write.py) and
    padding is in no run, so their pools come back whole; an int8 pool's
    scales still scatter a token, padding to the layer's own trash page."""
    task, theta, kv = _RepeatLm(kind)
    states = _RandomStates(task, theta, kv, seed=3)
    rows, tables = _Pack(all_padding=True), _Tables(stale=True)
    ids = jnp.asarray(
        np.random.RandomState(4).randint(0, 64, (1, _T)), jnp.int32)
    _, new_states = jax.jit(
        lambda th, st: task.RaggedStep(th, ids, st, tables, rows))(
            theta, states)
    pools = 0
    for (path, new), old in zip(
        jax.tree_util.tree_flatten_with_path(new_states)[0],
        jax.tree_util.tree_leaves(states)):
      name = jax.tree_util.keystr(path)
      new, old = np.asarray(new), np.asarray(old)
      if new.shape[1] != _NP:                     # an SSM slot state
        np.testing.assert_array_equal(new, old, err_msg=name)
        continue
      pools += 1
      np.testing.assert_array_equal(new[:, :_NP - 1], old[:, :_NP - 1],
                                    err_msg=name)
      for i in range(new.shape[0]):
        assert np.array_equal(new[i, _NP - 1], old[i, _NP - 1]) == (
            "scale" not in name), (name, i)
    assert pools == _POOL_LEAVES[kind]


# -- BuildRaggedStep / CommitRaggedStep (device-free) -------------------------


def _MakeSched(slots=3, pages=24, page=4, table_pages=8, **kw):
  alloc = kv_cache.PageAllocator(pages, page)
  return scheduler_lib.Scheduler(slots, alloc, table_pages, **kw), alloc


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
    sched = scheduler_lib.Scheduler(2, alloc, 4, prefix_cache=cache)
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
    sched = scheduler_lib.Scheduler(1, alloc, 4, prefix_cache=cache)
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


# -- the width a step runs (docs/ragged_step.md, "The width a step runs") -----

from tests.test_head_cols import _Calls, _FAMILIES  # noqa: E402


def _WidthEngineCalls(family):
  """Every "ragged" call of a small engine of `family` (f32) over a prompt
  of 3, a prompt of 2 beside its decode row, a prompt of 30 in chunks of the
  budget beside two decode rows, and decode rows alone: (engine, [(theta,
  states, tok_ids, rows, tables)])."""
  task, theta = _FAMILIES[family](jnp.float32)
  eng = engine_lib.ServingLoop(
      task, theta, page_size=8, num_pages=48, max_batch=4, max_seq_len=128,
      prefill_token_budget=8)
  calls = _Calls(eng)
  eng.Submit([5, 9, 2], 8, eos_id=None, seed=11)
  eng.StepOnce()
  eng.Submit([7, 1], 8, eos_id=None, seed=12)
  eng.StepOnce()
  eng.Submit(list(range(1, 31)), 4, eos_id=None, seed=13)
  for _ in range(6):
    eng.StepOnce()
  return task, eng, [args[:5] for args, _ in calls.calls]


_WIDTH_CALLS = {}


def _OnePackWidth(rows, t):
  """The same rows in a pack that has no narrower width (`row_cols` as wide
  as the pack, ragged.BuildLiveWidth): the step program without a
  conditional, which is the one every step ran before."""
  pad = t - rows.row_cols.shape[1]
  return rows._replace(
      row_cols=jnp.pad(rows.row_cols, ((0, 0), (0, pad))),
      col_parent=jnp.pad(rows.col_parent, ((0, 0), (0, pad)),
                         constant_values=-1))


def _SlotStates(states):
  """The leaves that are a slot's or a step's (scan and convolution states,
  a retention state, tokens by expert), not pages of a pool: a page holds
  slots no token of the step wrote (a whole-page write lays a padding token's
  K and V behind a row's last one, zeros from a narrow block, the token's own
  from a wide), which nothing reads before the row's next tokens overwrite
  them. The pools are held to what the NEXT step reads of them."""
  return [np.asarray(leaf.astype(jnp.float32))
          for path, leaf in jax.tree_util.tree_flatten_with_path(states)[0]
          if not {"key", "value", "gate"} & set(base_layer.PathKeys(path))]


@pytest.mark.parametrize("branch", ["narrow", "wide"])
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_a_step_is_the_same_at_the_width_it_holds(family, branch):
  """Steps an engine really dispatched, through the step program with its
  conditionals (the engine's rows: W = max_batch, so a prompt of 3 and a
  prompt of 2 beside a decode row take the narrow branch, a chunk of 8 the
  wide one) and through the program without any (`_OnePackWidth`): the same
  logits on every live column, the same new slot states, and pages from
  which the engine's next step computes the same logits."""
  if family not in _WIDTH_CALLS:
    _WIDTH_CALLS[family] = _WidthEngineCalls(family)
  task, eng, calls = _WIDTH_CALLS[family]
  t, w = eng._ragged_t, eng._narrow_rows
  assert w == eng.max_batch == 4
  live = [int(np.asarray(c[3].row_len).sum()) for c in calls]
  picked = [i for i, n in enumerate(live[:-1])
            if (n <= w) == (branch == "narrow")]
  # narrow: a chunk alone, a chunk beside a decode row, decode rows alone
  assert len({tuple(np.asarray(calls[i][3].row_len).tolist())
              for i in picked}) >= (3 if branch == "narrow" else 2), live
  step = jax.jit(lambda th, st, ids, rows, tables: task.RaggedStep(
      th, ids[None], st, tables, rows))

  def _Live(logits, rows):
    return np.asarray(logits[0])[np.flatnonzero(np.asarray(rows.valid))]

  for i in picked:
    theta, states, tok_ids, rows, tables = calls[i]
    assert ragged_lib.BuildLiveWidth(rows).rows == w
    assert ragged_lib.BuildLiveWidth(_OnePackWidth(rows, t)) is None
    got, got_states = step(theta, states, tok_ids, rows, tables)
    want, want_states = step(theta, states, tok_ids, _OnePackWidth(rows, t),
                             tables)
    np.testing.assert_allclose(_Live(got, rows), _Live(want, rows),
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(_SlotStates(got_states), _SlotStates(want_states)):
      np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    _, _, next_ids, next_rows, next_tables = calls[i + 1]
    np.testing.assert_allclose(
        _Live(step(theta, got_states, next_ids, next_rows, next_tables)[0],
              next_rows),
        _Live(step(theta, want_states, next_ids, next_rows, next_tables)[0],
              next_rows), atol=1e-5, rtol=1e-5)


def _Conds(jaxpr, out=None):
  """Every `cond` equation of a jaxpr and of the jaxprs inside it."""
  out = [] if out is None else out
  for eqn in jaxpr.eqns:
    if eqn.primitive.name == "cond":
      out.append(eqn)
    for sub in jax.core.jaxprs_in_params(eqn.params):
      _Conds(sub, out)
  return out


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_a_whole_sequence_forward_traces_no_conditional(family):
  """`FProp` hands no plan down, so `ragged.OverLiveRows` is its function and
  nothing else: training traces what it traced."""
  task, theta = _FAMILIES[family](jnp.float32)
  x = jnp.zeros((2, 8, task.p.model_dim), jnp.float32)
  jaxpr = jax.make_jaxpr(lambda th, x: task.stack.FProp(th, x))(
      theta.stack, x)
  assert not _Conds(jaxpr.jaxpr)


# The whole-sequence forward of each family's stack (2 x 8 tokens, f32; the
# dense family's, which the train cells run, with its gradient) as the PARENT
# of PR 51 (`6246f70`) traces it under JAX 0.9.0: equations, sub-jaxprs
# included, and the first 16 hex digits of the sha256 of the jaxpr's text.
# The test below is the recipe: run it on a parent's tree to take a number
# again. The two families with an expert layer were taken again at PR 60,
# whose combine (core/moe.py: one gather, k major) `FProp` shares with the
# step: 716 / "de7d6e176f8724fe" and 795 / "a25d30d52123d314" before it.
_PARENT_FORWARD = {
    "dense": (453, "0127a45b397c9af1"),
    "smallthinker": (712, "f54d05066bc821bb"),
    "phi4flash": (831, "e7e44788e2b4d76b"),
    "nemotron_h": (792, "a0148a3ba89aa8e0"),
    "brumby": (153, "c3fea7069c9406d6"),
}


def _Equations(jaxpr) -> int:
  return sum(1 + sum(_Equations(sub)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
             for eqn in jaxpr.eqns)


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_training_traces_the_parents_jaxpr(family):
  """What a train step traces of the stack is what the parent traced, to the
  letter: the serving step's halves (`RaggedMix` / `RaggedOut`, `_Export` /
  `_Finish`), the stack slices `CastTheta` takes and the plans' rewrites
  leave `FProp` and its gradient alone."""
  import hashlib
  task, theta = _FAMILIES[family](jnp.float32)
  x = jnp.zeros((2, 8, task.p.model_dim), jnp.float32)
  forward = lambda th, x: task.stack.FProp(th, x)
  fn = (jax.value_and_grad(lambda th, x: jnp.sum(forward(th, x)))
        if family == "dense" else forward)
  jaxpr = jax.make_jaxpr(fn)(theta.stack, x)
  equations, digest = _PARENT_FORWARD[family]
  assert _Equations(jaxpr.jaxpr) == equations
  if jax.__version__ == "0.9.0":      # the text is that version's
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == digest


def test_a_paged_step_traces_no_conditional(tiny_lm):
  task, theta = tiny_lm
  states = task.InitPagedDecodeState(theta, 9, 4, 3)
  jaxpr = jax.make_jaxpr(lambda th, st: task.PagedStep(
      th, jnp.zeros((3, 1), jnp.int32), st, jnp.zeros((3, 4), jnp.int32),
      jnp.zeros((3,), jnp.int32), jnp.ones((3,), jnp.int32)))(theta, states)
  assert not _Conds(jaxpr.jaxpr)


def test_the_serving_step_branches_once_a_layer_on_whole_stacks():
  """A scanned dense stack's step: ONE conditional in the traced body (the
  residual and the feed-forward), and it takes no layer's SLICE of a weight
  as an operand: the feed-forward's stacks enter whole and are sliced inside
  the branch (base_layer.StackSlice), where the slice fuses into the product
  that reads it. The attention's own `[D, N, H]` projections are re-laid a
  layer at a time (`relaid_weights`): they run outside the conditional, on
  slices taken as a scan takes them, and no conditional sees them."""
  from tests.conftest import InstantiateLm, TinyLmParams
  task, theta = InstantiateLm(TinyLmParams(use_repeat_layer=True,
                                           num_layers=3))
  states = task.InitPagedDecodeState(theta, 9, 4, 3)
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in
                                 ragged_lib.BuildRaggedRows(
                                     [1, 2, 0], [4, 0, 1], 11, 8)))
  jaxpr = jax.make_jaxpr(lambda th, st: task.RaggedStep(
      th, jnp.zeros((1, 11), jnp.int32), st, jnp.zeros((3, 8), jnp.int32),
      rows))(theta, states)
  (cond,) = _Conds(jaxpr.jaxpr)
  body = theta.stack.body
  ff_stacks = {leaf.shape for leaf in jax.tree_util.tree_leaves(body.fflayer)
               if leaf.ndim >= 3}
  atten = {leaf.shape[1:] for leaf in jax.tree_util.tree_leaves(
      body.self_atten.atten) if leaf.ndim >= 3}
  operands = {v.aval.shape for v in cond.invars}
  assert ff_stacks and ff_stacks <= operands
  assert not {shape[1:] for shape in ff_stacks} & operands
  assert atten and not atten & operands


# conditionals a layer (docs/ragged_step.md's table): what precedes a mixer's
# kernel where that is row-wise and its weights are not re-laid, and one more
# for what follows it (the output projection and the residual) WITH a dense
# feed-forward; a layer that is its mixer alone branches nothing behind it;
# an expert layer of a BlockSequence runs at the width the step holds under a
# conditional of its own (PR 57)
_MIXER_CONDS = {"Mamba1Layer": 1, "Mamba2Layer": 1,
                "DifferentialAttention": 1, "GatedMemoryUnit": 0,
                "PooledAttention": 0, "PowerRetention": 0,
                "MultiHeadedAttention": 0}


def _LayerConds(mixer, dense: bool, experts: bool = False) -> int:
  before = _MIXER_CONDS[type(mixer).__name__] if mixer else 0
  return before + (1 if dense or experts else 0)


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_conditionals_a_traced_step_by_stack(family):
  """The step's conditionals are what its traced layer bodies ask for, by
  the table: 1 in the tiny dense stack's one body, none beside experts, the
  Phi-4-flash and Nemotron siblings' by their blocks' lists, 1 in a retention
  layer's."""
  task, _, calls = (_WIDTH_CALLS.get(family)
                    or _WIDTH_CALLS.setdefault(family,
                                               _WidthEngineCalls(family)))
  theta, states, tok_ids, rows, tables = calls[0]
  jaxpr = jax.make_jaxpr(lambda th, st: task.RaggedStep(
      th, tok_ids[None], st, tables, rows))(theta, states)
  stack = task.stack
  if hasattr(stack, "_bodies"):
    want = sum(_LayerConds(l.mixer, hasattr(l, "fflayer")
                           and not l._experts, l._experts)
               for layers in stack._bodies for l in layers)
  else:
    layers = getattr(stack.body, "x_layers", [stack.body])
    want = sum(_LayerConds(l.self_atten.atten,
                           not hasattr(l.fflayer, "RouterLogits"))
               for l in layers)
  assert want == {"dense": 1, "smallthinker": 0, "brumby": 1}.get(family,
                                                                  want)
  assert len(_Conds(jaxpr.jaxpr)) == want
