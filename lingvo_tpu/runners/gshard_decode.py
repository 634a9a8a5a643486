"""GShard decode driver: checkpoint-watching streaming LM decode service.

Re-designs `lingvo/gshard_decode.py` (`GShardDecode:100`): a standalone job
that watches a trainer's checkpoint directory and, for every new checkpoint,
runs prompt continuations through the LM and streams results to JSONL. The
reference's infinite-infeed/outfeed-thread machinery collapses into a jitted
sampler plus the shared checkpoint-polling loop.

Decode fast path (docs/decode_fast_path.md):
- **Chunked prefill** — the prompt primes the KV cache through
  `task.Prefill` (one full-attention pass per chunk, K/V for the whole
  chunk written in one dynamic_update_slice) instead of an O(prompt_len)
  `lax.scan` of single-token ExtendSteps. `prefill_chunk_size=0` takes the
  whole prompt in one pass; `use_legacy_prime=True` keeps the old scan
  (A/B harness for tests and bench).
- **Donated decode state** — the KV cache is built by a jitted init
  program and donated into the decode program, so the multi-megabyte
  cache buffers update in place instead of being copied at the jit
  boundary.
- **Shape bucketing** — decode programs are specialized on the static
  `(prompt_len, t_max)` pair; rounding `prompt_len` up to `len_buckets`
  makes repeat traffic with ragged prompt widths hit the jit cache instead
  of recompiling (`t_max` is a constructor constant and needs no
  bucketing). Left-pad slots added by bucketing are masked through
  `cache_paddings` exactly like ragged-prompt padding, and rotary
  attention depends only on relative position, so bucketed numerics match
  unbucketed.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from lingvo_tpu import observe
from lingvo_tpu.core import checkpointer as checkpointer_lib
from lingvo_tpu.core import py_utils
from lingvo_tpu.core import sampling
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.serving import kv_cache

# Decode-program shape buckets (slots, ascending). Lengths beyond the last
# bucket run at their exact size (a compile per distinct length).
DEFAULT_LEN_BUCKETS = (16, 32, 64, 128, 256, 512, 1024)


class GShardDecode:
  """Streams LM samples for a fixed prompt set on every new checkpoint."""

  def __init__(self, task, train_dir: str, output_path: str,
               max_decode_steps: int = 32, temperature: float = 0.0,
               top_k: int = 0,
               poll_interval_secs: float = 10.0,
               timeout_secs: float = 3600.0,
               init_seed: int = 1234,
               prefill_chunk_size: int = 0,
               use_legacy_prime: bool = False,
               serve_int8_weights: bool = False,
               len_buckets=DEFAULT_LEN_BUCKETS,
               serve_port=None):
    """task: a TransformerLm-style task exposing InitDecodeState/ExtendStep.

    temperature/top_k: sampling controls (core/sampling.py). temperature
    <= 0 is greedy argmax — bitwise the pre-sampling behavior; top_k > 0
    restricts temperature sampling to the k largest logits. Sampling is
    seeded per request: row i draws from fold_in(step_key, i), so a
    request's continuation doesn't depend on its batch neighbors.
    prefill_chunk_size: prompt tokens per prefill attention pass (0 = the
    whole prompt in one pass). use_legacy_prime: prime the cache with the
    per-token ExtendStep scan instead of chunked prefill (slow; kept as
    the A/B reference). serve_int8_weights: rewrite each restored theta so
    decode projections run int8 integer matmuls (quant.weights — rewritten
    once per checkpoint, cached). len_buckets: prompt-width buckets.
    serve_port: when not None, a StatusServer over this driver's registry
    serves /metrics and /statusz (0 = ephemeral; read
    `self.status_server.port`); /statusz `stats` carries the last
    DecodeOnce telemetry.
    """
    self._task = task
    self._train_dir = train_dir
    self._output_path = output_path
    self._max_steps = max_decode_steps
    self._temperature = temperature
    self._top_k = top_k
    self._checkpointer = checkpointer_lib.Checkpointer(train_dir)
    self._poll_interval = poll_interval_secs
    self._timeout = timeout_secs
    self._last_step = -1
    self._prefill_chunk = prefill_chunk_size
    self._use_legacy_prime = use_legacy_prime
    self._serve_int8_weights = bool(serve_int8_weights)
    # (checkpoint step, rewritten theta) — int8 rewrite runs once per
    # restored checkpoint, not once per DecodeOnce call
    self._int8_theta = None
    self._len_buckets = tuple(len_buckets)
    self._template = jax.eval_shape(
        self._task.CreateTrainState, jax.random.PRNGKey(init_seed))
    # jitted (init_fn, prefill_fn, sample_fn) per bucketed static
    # (p_len, t_max)
    self._decode_fns = {}
    # per-call timing of the last DecodeOnce (also attached to every
    # result rec under "telemetry"): prefill_s / decode_s / total_s /
    # tokens_per_sec — the apples-to-apples numbers the serving-engine
    # bench compares against. The dict itself is a VIEW over this driver's
    # metrics registry, generated from observe.schema.GSHARD_TELEMETRY_KEYS
    # so the two serving surfaces cannot drift apart again.
    self.metrics = observe.MetricsRegistry("gshard_decode")
    self._decodes = self.metrics.Counter("serving/decodes")
    self._last_telemetry = None
    self.status_server = None
    if serve_port is not None:
      self.status_server = observe.StatusServer(
          serve_port, registry=self.metrics, name="gshard_decode",
          statusz_fn=lambda: {"telemetry": self._last_telemetry}).Start()

  def _GetDecodeFn(self, p_len: int, t_max: int):
    """Builds (init_fn, decode_fn) for a static (p_len, t_max) pair."""
    cache_key = (p_len, t_max)
    if cache_key in self._decode_fns:
      return self._decode_fns[cache_key]
    task = self._task
    temp = self._temperature
    top_k = self._top_k
    total = p_len + t_max
    chunk = self._prefill_chunk if self._prefill_chunk > 0 else p_len
    legacy_prime = self._use_legacy_prime

    def _Init(theta, batch_size):
      return task.InitDecodeState(theta, batch_size, total)

    def _CachePaddings(prompt_lens):
      # slot s is pad for row i iff s < P - len_i
      slot = jnp.arange(total)[None, :]
      return (slot < (p_len - prompt_lens)[:, None]).astype(
          jnp.float32)                                     # [B, total]

    def _Prefill(theta, prompts, prompt_lens, states):
      """prompts [B, P] RIGHT-ALIGNED (left-padded) -> (last_logits [B, V],
      primed states).

      Variable-length support: each row's prompt occupies cache slots
      [P - len_i, P), so every row's last prompt token sits at slot P-1 and
      sampling starts at slot P for all rows. Left-pad slots are excluded
      from attention forever via cache_paddings (their K/V are garbage).
      Rotary attention depends only on relative positions, so global slot
      indices give the same numerics as an unpadded per-length batch.
      """
      cache_paddings = _CachePaddings(prompt_lens)
      if legacy_prime:
        # teacher-force the prompt one token at a time (O(p_len) sequential
        # full-cache attention calls; the pre-fast-path behavior)
        def _Prime(carry, ids_t):
          states = carry
          logits, states = task.ExtendStep(theta, ids_t[:, None], states,
                                           cache_paddings=cache_paddings)
          return states, logits

        states, logits = jax.lax.scan(_Prime, states,
                                      prompts.swapaxes(0, 1))
        return logits[-1], states                          # [B, V]
      # chunked prefill: ceil(p_len / chunk) attention passes write the
      # whole prompt's K/V and produce the last-position logits; each
      # pass reads only the written cache prefix (live_len), not the
      # max_len decode tail
      chunk_logits = None
      for start in range(0, p_len, chunk):
        ids_c = prompts[:, start:start + chunk]
        chunk_logits, states = task.Prefill(
            theta, ids_c, states, cache_paddings=cache_paddings,
            live_len=start + ids_c.shape[1])
      return chunk_logits[:, -1, :], states                # [B, V]

    def _SampleLoop(theta, last_logits, prompt_lens, key, states):
      """Greedy/temperature sampling scan -> continuations [B, t_max]."""
      cache_paddings = _CachePaddings(prompt_lens)
      # per-request streams: row i folds its row index into the step key,
      # so a row's draws are a function of (checkpoint key, row, step)
      # only — not of how many neighbors share the batch
      row_seeds = jnp.arange(last_logits.shape[0], dtype=jnp.int32)

      def _Sample(carry, key_t):
        states, logits = carry
        nxt = sampling.SampleFromLogits(logits, key_t, temperature=temp,
                                        top_k=top_k, row_seeds=row_seeds)
        new_logits, states = task.ExtendStep(theta, nxt[:, None], states,
                                             cache_paddings=cache_paddings)
        return (states, new_logits), nxt

      keys = jax.random.split(key, t_max)
      _, out_ids = jax.lax.scan(_Sample, (states, last_logits), keys)
      return out_ids.swapaxes(0, 1)                        # [B, t_max]

    # the KV cache is donated through BOTH jit boundaries: the prefill
    # program reuses the init program's buffers in place and the sample
    # program reuses the prefill program's, instead of copying at each
    # boundary (XLA:CPU can't alias these buffers and warns, so donate
    # off-cpu only). The prefill/sample split (vs the old fused _Decode)
    # exists for per-phase telemetry: DecodeOnce times each program
    # separately so prefill_s/decode_s in the result dict are real
    # device-time measurements, not estimates.
    on_cpu = jax.default_backend() == "cpu"
    fns = (jax.jit(_Init, static_argnums=(1,)),
           jax.jit(_Prefill, donate_argnums=() if on_cpu else (3,)),
           jax.jit(_SampleLoop, donate_argnums=() if on_cpu else (4,)))
    self._decode_fns[cache_key] = fns
    return fns

  @staticmethod
  def _RightAlign(prompts: np.ndarray, prompt_lens: np.ndarray,
                  width: int | None = None) -> np.ndarray:
    """Shifts each row's first len_i tokens to the row's END (left-pad).

    width: output row width (>= prompts.shape[1]; defaults to it) — the
    bucketed prompt width, with bucketing pad folded into the left-pad.
    """
    prompts = np.asarray(prompts)
    p = prompts.shape[1]
    w = p if width is None else int(width)
    assert w >= p, (w, p)
    out = np.zeros((prompts.shape[0], w), prompts.dtype)
    lens = np.asarray(prompt_lens)
    if lens.shape[0] != prompts.shape[0] or (lens < 0).any() or (
        lens > p).any():
      rng = f"[{lens.min()}, {lens.max()}]" if lens.size else "[]"
      raise ValueError(
          f"prompt_lens must be [batch={prompts.shape[0]}] with values in "
          f"[0, {p}]; got shape {lens.shape}, values in {rng}")
    for i, ln in enumerate(lens):
      ln = int(ln)
      out[i, w - ln:] = prompts[i, :ln]
    return out

  def DecodeOnce(self, step: int, prompts: np.ndarray,
                 prompt_lens: np.ndarray) -> list:
    state, restored = self._checkpointer.Restore(self._template, step=step)
    theta = state.theta
    if self._serve_int8_weights:
      if self._int8_theta is None or self._int8_theta[0] != restored:
        from lingvo_tpu.quant import weights as quant_weights
        self._int8_theta = (
            restored, quant_weights.Int8ServingTheta(theta)[0])
      theta = self._int8_theta[1]
    if prompts.shape[1] == 0:
      raise ValueError("prompts must have width >= 1 (got [B, 0]); the "
                       "prefill loop needs at least one chunk")
    # only p_len varies across calls; max_steps is a constructor constant,
    # so bucketing it would just run extra discarded decode steps
    p_len = py_utils.RoundUpToBucket(prompts.shape[1], self._len_buckets)
    init_fn, prefill_fn, sample_fn = self._GetDecodeFn(p_len, self._max_steps)
    aligned = self._RightAlign(prompts, prompt_lens, width=p_len)
    states = init_fn(theta, prompts.shape[0])
    jax.block_until_ready(states)
    # measured BEFORE donation (shape metadata only): total decode-state
    # HBM per sequence — KV caches grow with p_len + max_steps, O(1) SSM
    # mixer states don't, so this is the number the mixer bench sweeps
    state_bytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(states)
        if hasattr(x, "nbytes"))
    lens_dev = jnp.asarray(prompt_lens)
    # per-phase wall timing (block_until_ready fences async dispatch so
    # each phase's time is its own, not its predecessor's flush)
    t0 = time.perf_counter()
    last_logits, states = prefill_fn(theta, jnp.asarray(aligned),
                                     lens_dev, states)
    jax.block_until_ready(last_logits)
    t1 = time.perf_counter()
    out = sample_fn(theta, last_logits, lens_dev,
                    jax.random.PRNGKey(restored), states)
    out = jax.block_until_ready(out)
    t2 = time.perf_counter()
    self._last_step = restored
    b = prompts.shape[0]
    decode_s = t2 - t1
    # KV-cache telemetry: the same visibility contract the serving engine's
    # Stats() carries — a quantized (or non-default-dtype) cache is never
    # silent. Non-LM tasks without a recognizable stack report None/0.
    census = kv_cache.StackCensus(self._task) or {}
    observe_schema.PublishTelemetry(self.metrics, observe_schema.GShardTelemetry(
        prefill_s=t1 - t0,
        decode_s=decode_s,
        total_s=t2 - t0,
        prompt_tokens=int(np.sum(prompt_lens)),
        decode_tokens=b * self._max_steps,
        tokens_per_sec=(b * self._max_steps / decode_s
                        if decode_s > 0 else 0.0),
        decode_state_bytes_per_seq=state_bytes // b,
        kv_cache_dtype=census.get("kv_cache_dtype"),
        kv_bytes_per_token=census.get("kv_bytes_per_token", 0),
        serve_int8_weights=self._serve_int8_weights,
        # speculative-decoding acceptance telemetry, mirrored with the
        # serving engine's Stats() key-set so bench comparisons line up;
        # batch-synchronous decode never drafts, so always zeros here
        draft_tokens=0,
        accepted_tokens=0,
        accepted_len_hist=[],
        spec_branches=0,
        spec_width_clamps=0,
        accepted_depth_hist=[],
        # prefix-cache telemetry, same mirroring contract: the batch-
        # synchronous driver re-prefills every prompt, so no cache exists
        prefix_hit_tokens=0,
        prefix_cache=observe_schema.DisabledPrefixCacheStats(),
        # compiled-step-program census, mirrored with the serving engine's
        # Stats()["compile"]["step_programs"]: this driver compiles a
        # (prefill, sample) program pair per (p_len, t_max) bucket
        step_programs=2 * len(self._decode_fns),
        # SLO scheduling counters, same mirroring contract: the batch-
        # synchronous driver admits everything up front and never
        # preempts, so no host tier exists
        preemptions=0,
        spilled_pages=0,
        restored_pages=0,
        host_bytes=0,
    ))
    self._decodes.Inc()
    # the dict every result record carries is rebuilt FROM the registry —
    # the registry is the source of truth, the dict is the view
    telemetry = observe_schema.TelemetryFromRegistry(self.metrics)
    self._last_telemetry = telemetry
    results = []
    with open(self._output_path, "a") as f:
      for i in range(b):
        rec = {
            "checkpoint_step": int(restored),
            "prompt_ids": [int(x) for x in
                           prompts[i, :int(prompt_lens[i])]],
            "output_ids": [int(x) for x in np.asarray(out[i])],
            "telemetry": telemetry,
        }
        f.write(json.dumps(rec) + "\n")
        results.append(rec)
    return results

  def Run(self, prompts: np.ndarray, prompt_lens: np.ndarray):
    """Polls for new checkpoints forever (until timeout/FINISHED marker)."""
    last_new = time.time()
    max_steps = self._task.p.train.max_steps
    try:
      while True:
        latest = self._checkpointer.LatestStep()
        if latest is not None and latest > self._last_step:
          self.DecodeOnce(latest, prompts, prompt_lens)
          last_new = time.time()
          print(f"[gshard_decode] decoded @ step {latest}", flush=True)
          if latest >= max_steps or os.path.exists(
              os.path.join(self._train_dir, "FINISHED")):
            return
        elif os.path.exists(os.path.join(self._train_dir, "FINISHED")):
          return
        elif time.time() - last_new > self._timeout:
          return
        else:
          time.sleep(self._poll_interval)
    finally:
      self._checkpointer.Close()
