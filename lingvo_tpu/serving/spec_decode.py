"""Speculative decoding: draft-and-verify multi-token serving cycles.

Every plain engine step emits exactly ONE token per decode row, so decode
throughput is bounded by per-step launch + memory-bandwidth cost no matter
how cheap the model is. This module adds the draft-and-verify path on top
of the continuous-batching engine with NO new kernels:

1. DRAFT — a cheap source proposes k tokens per decode row:
   - `SelfDraft`: early-exit self-speculation. The draft pass runs only
     the first `num_layers` of the SAME stack/theta (then the full
     final_ln + logits head) via `TransformerLm.PagedStepPrefix`. Draft
     steps thread the engine states as a TRANSIENT copy — drafted KV/SSM
     writes are discarded, the verify step re-writes every kept position.
   - `ModelDraft`: an independent tiny draft model — pure O(1)-state
     (SSM) stacks only, so draft rows cost ZERO KV pages (the SSD-duality
     trade: flat [slots, N, H, S] state instead of paged KV). Its
     recurrent state advances ONLY over committed tokens: each cycle a
     ragged catch-up pass consumes the tokens committed since last cycle
     (<= k+1 wide in steady state), then k-1 transient proposal steps run
     whose state mutations are discarded — so draft rejection needs no
     rollback machinery at all.

2. VERIFY — a speculating row rides the engine's one packed step as a
   row of width row_k + 1 carrying [t0, d_1..d_k] (k+1 causal queries
   against its paged prefix); opted-out rows ride along at width 1,
   which is bitwise the plain decode row.

3. ACCEPT/ROLLBACK — `core/sampling.SpecVerifyTokens` picks the accepted
   prefix (greedy match, or residual speculative sampling at
   temperature > 0, composing with the per-request seeded streams).
   Rolling back the rejected tail is free for KV pages (the write cursor
   is host-side and reads never pass q_pos + in_len — the scheduler just
   doesn't advance `seq.pos`); O(1)-state mixers instead return their
   per-column state trajectory (`ssm_col_states`) and `_SelectAcceptedCols`
   restores each slot to the last accepted column on device, inside the
   same compiled step program.

Step-program cost: the verify lane is FOLDED INTO the engine's one step
program (spec rows are simply rows of width k+1 on the packed token
axis, and SpecVerifyTokens runs on their gathered logits inside the same
jit), so speculation adds only the draft program(s), and
admission/eviction still only rewrite int32 block tables.

TREE speculation (w > 1 on either draft source): the
draft proposes a token TREE per row — w branches forked at depth 1, each
a chain of k tokens, packed branch-major so draft index bi * k + d is
branch bi's depth-(d+1) node. Branch heads are the top-w tokens of the
root distribution at temperature 0 and w i.i.d. draws from it otherwise
(i.i.d. siblings are what keeps the verify's multi-round residual
rejection exactly target-distributed); each branch then continues as an
ordinary chain draft under a branch-folded key. Draft-phase KV/state
writes stay TRANSIENT — branches sequentially overwrite each other's
scratch slots, which can only cost acceptance rate, never correctness,
because the unified step re-writes every tree slot at full depth and
core/sampling.SpecVerifyTree guarantees the emitted stream. ModelDraft
checkpoints its recurrent state after the committed catch-up and replays
every branch from that checkpoint.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from lingvo_tpu.core import sampling
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import scheduler as scheduler_lib

# key salt separating the draft model's sampling streams from the target's
# acceptance/bonus streams (both are per-request replayable)
_DRAFT_KEY_SALT = 0x5BEC


# -- draft-source configs -----------------------------------------------------


class SelfDraft:
  """Early-exit self-speculation: first `num_layers` of the target stack.

  k: draft depth proposed per decode row per cycle (chain verify width
  k+1; tree verify width 1 + w*k). w: draft-tree width — 1 (default)
  keeps the exact linear-chain draft, w > 1 forks w branches at depth 1.
  num_layers: flat trunk depth of the draft pass (must divide the scanned
  repeat-body depth for RepeatedTransformerLayer stacks)."""

  def __init__(self, k: int = 4, num_layers: int = 1, w: int = 1):
    assert k >= 1 and num_layers >= 1 and w >= 1, (k, num_layers, w)
    self.k = int(k)
    self.w = int(w)
    self.num_layers = int(num_layers)

  def Describe(self) -> dict:
    return {"draft": "self", "k": self.k, "w": self.w,
            "num_layers": self.num_layers}


class ModelDraft:
  """Independent tiny draft model (pure O(1)-state stack, pageless).

  w: draft-tree width — 1 (default) keeps the exact linear-chain draft,
  w > 1 forks w branches at depth 1, each replayed from the recurrent
  state checkpointed after the committed catch-up."""

  def __init__(self, task, theta, k: int = 4, w: int = 1):
    assert k >= 1 and w >= 1, (k, w)
    self.k = int(k)
    self.w = int(w)
    self.task = task
    self.theta = theta

  def Describe(self) -> dict:
    return {"draft": "model", "k": self.k, "w": self.w,
            "num_layers": self.task.p.num_layers}


# -- device-side helpers ------------------------------------------------------


def _SelectAcceptedCols(states, accept_len):
  """Rolls every collected SSM trajectory back to the accepted column.

  Walks the states pytree; wherever a node carries `col_states`
  [..., slots, C, N, H, S] (core/ssm.py spec-verify mode), replaces
  `state` with the column at accept_len (the state AFTER processing the
  last committed verify input) and strips the trajectory so the returned
  pytree matches the engine's steady-state structure."""
  idx = accept_len.astype(jnp.int32)

  def _Walk(node):
    if isinstance(node, NestedMap):
      if "col_states" in node:
        cols = node["col_states"]
        shape = (1,) * (cols.ndim - 5) + (idx.shape[0], 1, 1, 1, 1)
        sel = jnp.take_along_axis(cols, idx.reshape(shape), axis=-4)
        out = NestedMap({k: v for k, v in node.items()
                         if k != "col_states"})
        out.state = jnp.squeeze(sel, axis=-4)
        return out
      return NestedMap({k: _Walk(v) for k, v in node.items()})
    if isinstance(node, list):
      return [_Walk(v) for v in node]
    if isinstance(node, tuple):
      return tuple(_Walk(v) for v in node)
    return node

  return _Walk(states)


# -- the runner ---------------------------------------------------------------


class SpecRunner:
  """Owns the draft compiled programs and draft-model state.

  Built by ServingLoop when a draft source is configured; all scheduler
  bookkeeping stays in serving/scheduler.py, all device programs live
  here. Host-side it additionally tracks, for ModelDraft, each slot
  sequence's `draft_pos` (committed tokens the draft state has consumed).
  """

  def __init__(self, config, *, task, theta, max_batch: int,
               page_size: int, prefill_chunk: int, temperature: float,
               top_k: int, sample_seed: int):
    self.config = config
    self.k = config.k
    self.w = getattr(config, "w", 1)
    self.is_self = isinstance(config, SelfDraft)
    self._task = task
    self._temperature = float(temperature)
    self._top_k = int(top_k)
    self._sample_seed = int(sample_seed)
    self._max_batch = max_batch
    self._prefill_chunk = prefill_chunk
    # accepted-length histogram: hist[m] = verify rows whose accepted
    # draft prefix (tree: accepted root-to-leaf DEPTH along the winning
    # branch) had length m — each such row committed m + 1 tokens
    self.accepted_len_hist = np.zeros((self.k + 1,), np.int64)

    if self.is_self:
      depth = task.p.num_layers
      assert config.num_layers <= depth, (config.num_layers, depth)
      self.draft_task = None
      self.draft_theta = None
      self.draft_states = None
    else:
      census = kv_cache.StackCensus(config.task)
      assert census["num_attention"] == 0, (
          "ModelDraft requires a pageless draft (pure O(1)-state mixer "
          f"stack); draft has {census['num_attention']} attention layers "
          "— a paged draft would need its own page pool")
      assert config.task.p.vocab_size == task.p.vocab_size, (
          config.task.p.vocab_size, task.p.vocab_size)
      self.draft_task = config.task
      self.draft_theta = config.theta
      init_fn = jax.jit(config.task.InitPagedDecodeState,
                        static_argnums=(1, 2, 3, 4))
      # pageless: the pool geometry is ignored, only num_slots matters
      self.draft_states = init_fn(config.theta, 2, page_size, max_batch,
                                  None)
    self._BuildPrograms()

  # -- compiled programs -----------------------------------------------------

  def _BuildPrograms(self):
    k, temp, topk = self.k, self._temperature, self._top_k
    task = self._task
    base_key = self._sample_seed

    def _DraftKey():
      return jax.random.fold_in(jax.random.PRNGKey(base_key),
                                _DRAFT_KEY_SALT)

    w = self.w

    def _BranchHeads(l0, key_d, seeds, pos0):
      # depth-1 sibling set from the shared root distribution l0: the
      # top-w distinct tokens at temperature 0 (maximum acceptance mass),
      # w i.i.d. branch-keyed draws otherwise — the i.i.d. sibling law
      # SpecVerifyTree's multi-round residual rejection is exact for
      if temp <= 0.0:
        return jax.lax.top_k(l0, w)[1].astype(jnp.int32)
      cols = []
      for bi in range(w):
        kb = key_d if bi == 0 else jax.random.fold_in(key_d, bi)
        cols.append(sampling.SampleFromLogits(
            l0, kb, temperature=temp, top_k=topk, row_seeds=seeds,
            positions=pos0))
      return jnp.stack(cols, 1)

    if self.is_self:
      num_layers = self.config.num_layers

      def _SelfPropose(theta, states, ids0, q_pos, act, tables, seeds,
                       pos0):
        key_d = _DraftKey()
        st, cur = states, ids0
        d_toks, q_logits = [], []
        for j in range(k):
          logits, st = task.PagedStepPrefix(theta, cur, st, tables,
                                            q_pos + j, act, num_layers)
          lj = logits[:, 0]
          tok = sampling.SampleFromLogits(
              lj, key_d, temperature=temp, top_k=topk, row_seeds=seeds,
              positions=pos0 + j)
          d_toks.append(tok)
          q_logits.append(lj)
          cur = tok[:, None]
        # st (drafted KV writes through the prefix layers) is DISCARDED:
        # the verify step re-writes every kept position at full depth
        return jnp.stack(d_toks, 1), jnp.stack(q_logits, 1)

      def _SelfProposeTree(theta, states, ids0, q_pos, act, tables, seeds,
                           pos0):
        key_d = _DraftKey()
        # root step: the shared depth-1 distribution every branch head is
        # picked from (its KV write at q_pos is transient, like all draft
        # writes — the unified step re-writes every tree slot)
        logits0, st = task.PagedStepPrefix(theta, ids0, states, tables,
                                           q_pos, act, num_layers)
        l0 = logits0[:, 0]
        heads = _BranchHeads(l0, key_d, seeds, pos0)           # [B, w]
        d_toks = [None] * (w * k)
        q_logits = [None] * (w * k)
        for bi in range(w):
          kb = key_d if bi == 0 else jax.random.fold_in(key_d, bi)
          cur = heads[:, bi]
          d_toks[bi * k] = cur
          q_logits[bi * k] = l0
          # each branch continues as an ordinary chain draft over the
          # SAME scratch slots q_pos+1.. — later branches overwrite
          # earlier ones' transient KV, and each step only attends slots
          # <= its own position, so every branch sees exactly
          # prefix + root + its own prefix
          for d in range(1, k):
            logits, st = task.PagedStepPrefix(theta, cur[:, None], st,
                                              tables, q_pos + d, act,
                                              num_layers)
            lj = logits[:, 0]
            cur = sampling.SampleFromLogits(
                lj, kb, temperature=temp, top_k=topk, row_seeds=seeds,
                positions=pos0 + d)
            d_toks[bi * k + d] = cur
            q_logits[bi * k + d] = lj
        return jnp.stack(d_toks, 1), jnp.stack(q_logits, 1)

      self._self_draft_fn = jax.jit(_SelfPropose if w == 1
                                    else _SelfProposeTree)
    else:
      draft_task = self.draft_task

      def _Consume(theta_d, states_d, ids, q_pos, in_len):
        tables = jnp.zeros((ids.shape[0], 1), jnp.int32)  # pageless
        _, st = draft_task.PagedStep(theta_d, ids, states_d, tables,
                                     q_pos, in_len)
        return st

      self._consume_fn = jax.jit(_Consume)

      def _Propose(theta_d, states_d, catch_ids, dpos, clen, seeds, pos0):
        tables = jnp.zeros((catch_ids.shape[0], 1), jnp.int32)
        key_d = _DraftKey()
        # ragged catch-up over the tokens committed since last cycle;
        # this is the ONLY draft-state advance — proposals below are
        # transient, so draft rejection needs no rollback
        logits_c, st = draft_task.PagedStep(theta_d, catch_ids, states_d,
                                            tables, dpos, clen)
        last = jnp.clip(clen - 1, 0, k)[:, None, None]
        cur = jnp.take_along_axis(logits_c, last, axis=1)[:, 0]
        act = (clen > 0).astype(jnp.int32)
        st_t = st
        d_toks, q_logits = [], []
        for j in range(k):
          tok = sampling.SampleFromLogits(
              cur, key_d, temperature=temp, top_k=topk, row_seeds=seeds,
              positions=pos0 + j)
          d_toks.append(tok)
          q_logits.append(cur)
          if j < k - 1:
            lj, st_t = draft_task.PagedStep(
                theta_d, tok[:, None], st_t, tables,
                dpos + clen + j, act)
            cur = lj[:, 0]
        return jnp.stack(d_toks, 1), jnp.stack(q_logits, 1), st

      def _ProposeTree(theta_d, states_d, catch_ids, dpos, clen, seeds,
                       pos0):
        tables = jnp.zeros((catch_ids.shape[0], 1), jnp.int32)
        key_d = _DraftKey()
        # committed catch-up advances the KEPT draft state st; every
        # branch below replays from that checkpoint transiently
        logits_c, st = draft_task.PagedStep(theta_d, catch_ids, states_d,
                                            tables, dpos, clen)
        last = jnp.clip(clen - 1, 0, k)[:, None, None]
        l0 = jnp.take_along_axis(logits_c, last, axis=1)[:, 0]
        act = (clen > 0).astype(jnp.int32)
        heads = _BranchHeads(l0, key_d, seeds, pos0)           # [B, w]
        d_toks = [None] * (w * k)
        q_logits = [None] * (w * k)
        for bi in range(w):
          kb = key_d if bi == 0 else jax.random.fold_in(key_d, bi)
          st_t = st
          cur_tok = heads[:, bi]
          cur = l0
          for d in range(k):
            d_toks[bi * k + d] = cur_tok
            q_logits[bi * k + d] = cur
            if d < k - 1:
              lj, st_t = draft_task.PagedStep(
                  theta_d, cur_tok[:, None], st_t, tables,
                  dpos + clen + d, act)
              cur = lj[:, 0]
              cur_tok = sampling.SampleFromLogits(
                  cur, kb, temperature=temp, top_k=topk, row_seeds=seeds,
                  positions=pos0 + d + 1)
        return jnp.stack(d_toks, 1), jnp.stack(q_logits, 1), st

      self._propose_fn = jax.jit(_Propose if w == 1 else _ProposeTree)

  # -- host-side draft-state bookkeeping (ModelDraft) ------------------------

  @staticmethod
  def _StreamToken(seq, idx: int) -> int:
    """Committed token idx of a sequence (prompt then generated)."""
    pl = len(seq.req.prompt)
    return seq.req.prompt[idx] if idx < pl else seq.out[idx - pl]

  def _DrainBacklog(self, rows, row_k):
    """Catches the draft state up when a row's backlog outgrew the k+1
    catch-up window — most commonly the row just finished prompt prefill
    (the draft state never consumes the prompt on the wire; it replays
    the committed stream host-side, which also covers prefix-cache
    admissions whose prefill skipped cached tokens entirely). Runs the
    consume program in prefill_chunk-wide bites before the row's first
    draft; steady state never enters the loop."""
    cp = self._prefill_chunk
    while True:
      todo = []
      for i, seq in enumerate(rows):
        if (seq is None or seq.state is not scheduler_lib.SeqState.DECODE
            or row_k[i] == 0):
          continue
        backlog = seq.pos + 1 - seq.draft_pos
        excess = backlog - (self.k + 1)
        if excess > 0:
          todo.append((i, seq, min(excess, cp)))
      if not todo:
        return
      b = len(rows)
      ids = np.zeros((b, cp), np.int32)
      q_pos = np.zeros((b,), np.int32)
      in_len = np.zeros((b,), np.int32)
      for i, seq, n in todo:
        for j in range(n):
          ids[i, j] = self._StreamToken(seq, seq.draft_pos + j)
        q_pos[i] = seq.draft_pos
        in_len[i] = n
      self.draft_states = self._consume_fn(
          self.draft_theta, self.draft_states, jnp.asarray(ids),
          jnp.asarray(q_pos), jnp.asarray(in_len))
      for i, seq, n in todo:
        seq.draft_pos += n

  def _BuildCatchup(self, rows, row_k):
    b, kp1 = len(rows), self.k + 1
    ids = np.zeros((b, kp1), np.int32)
    dpos = np.zeros((b,), np.int32)
    clen = np.zeros((b,), np.int32)
    for i, seq in enumerate(rows):
      if (seq is None or seq.state is not scheduler_lib.SeqState.DECODE
          or row_k[i] == 0):
        continue
      n = seq.pos + 1 - seq.draft_pos
      assert 1 <= n <= kp1, (n, kp1)
      for j in range(n):
        ids[i, j] = self._StreamToken(seq, seq.draft_pos + j)
      dpos[i] = seq.draft_pos
      clen[i] = n
    return ids, dpos, clen

  # -- per-cycle entry points ------------------------------------------------

  def Draft(self, theta, states, vbatch, tables):
    """Proposes k tokens per spec row; returns (np [B, k], device q_logits).

    ModelDraft: also advances the committed draft state (catch-up) and
    each row's draft_pos."""
    if self.is_self:
      act = (vbatch.in_len > 0).astype(np.int32)
      d, q = self._self_draft_fn(
          theta, states, jnp.asarray(vbatch.ids[:, :1]),
          jnp.asarray(vbatch.q_pos), jnp.asarray(act), jnp.asarray(tables),
          jnp.asarray(vbatch.row_seeds), jnp.asarray(vbatch.row_pos))
      return np.asarray(d), q
    self._DrainBacklog(vbatch.rows, vbatch.row_k)
    ids, dpos, clen = self._BuildCatchup(vbatch.rows, vbatch.row_k)
    d, q, self.draft_states = self._propose_fn(
        self.draft_theta, self.draft_states, jnp.asarray(ids),
        jnp.asarray(dpos), jnp.asarray(clen),
        jnp.asarray(vbatch.row_seeds), jnp.asarray(vbatch.row_pos))
    for i, seq in enumerate(vbatch.rows):
      if clen[i]:
        seq.draft_pos += int(clen[i])
    return np.asarray(d), q

  def Describe(self) -> dict:
    return self.config.Describe()
