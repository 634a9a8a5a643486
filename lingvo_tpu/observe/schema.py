"""Shared telemetry schema: the single source of truth for metric keys.

Before this module, the stack had grown parallel telemetry dialects —
engine `ServingLoop.Stats()`, `GShardDecode`'s ad-hoc telemetry dict,
per-program `infeed_wait_s` timers — whose key sets drifted apart as each
PR added its keys to whichever surface it touched (the kv/paged-path keys
landed twice, once per surface, in PRs 10-11). Every key set is now
declared HERE, constructors validate against it, and the key-set tests
assert both runtime surfaces against these constants, so the next key
either lands everywhere or fails a test.

Conventions:
- Registry metric names are `namespace/key` with namespaces `serving/*`,
  `scheduler/*`, `kv_pages/*`, `state_slots/*`, `infeed/*`, `train/*`.
- A *surface* (Stats() dict, telemetry dict) is a plain-key view derived
  from registry values; the schema maps between the two.
"""

from __future__ import annotations

import jax

# -- serving engine Stats() --------------------------------------------------

# Monotonic counters the engine increments per step/commit; Stats() carries
# them under these exact plain keys, the registry under "serving/<key>".
ENGINE_COUNTER_KEYS = (
    "steps", "decode_steps", "mixed_steps",
    # steps whose live tokens fit the pack's decode width (the pack less the
    # widest row it admits: core/ragged.LiveWidth), counted at dispatch from
    # the host's own row lengths: the steps whose row-wise blocks the step
    # program runs over that width and not the pack's (ragged.OverLiveRows).
    # Not `decode_steps`: a short chunk beside few live rows fits too.
    "narrow_steps",
    "tokens_emitted", "prompt_tokens",
    "dense_fallback_steps", "quantized_steps",
    "spec_cycles", "draft_tokens", "accepted_tokens",
    "spec_branches", "spec_width_clamps",
    "prefix_hit_tokens",
    # query blocks the ragged attend kernel ran, and the valid queries in
    # them; their ratio over the block size is the block fill. A block's
    # products run the rows of M its queries need, not always the block's
    # bound (ops/ragged_block_attend.BlockRungs): `attend_block_rows` sums
    # them.
    "attend_query_blocks", "attend_block_queries", "attend_block_rows",
    # the (block, page) pairs the attend kernels' grids ran, summed over the
    # step's plans (one a PlanKey), and the pairs their lists have room for,
    # which is the grid every call ran before PR 46: their ratio is the share
    # of that grid that held work. Both 0 where the twins run.
    "attend_live_pairs", "attend_grid_pairs",
    # the live pairs whose program ran no mask (the grouped attend kernel's
    # and ops/latent_attend.py's: a block of the widest rung at a page that
    # lies whole under every query's horizon and, in a window layer, whole
    # inside every query's window), by ops/ragged_block_attend.ClearPairs from
    # the host's rows:
    # over `attend_live_pairs`, the share of programs that ran unmasked. 0
    # where no kernel of the step reads the plan's `clear`.
    "attend_clear_pairs",
    # the programs the attend kernels' grids ran (`AttendPlan.pairs` summed
    # over the step's plans, by ops/ragged_block_attend.Programs from the
    # host's rows): a program a live pair, but in the grouped attend kernel,
    # where a decode row's program walks a span of its pages.
    # `attend_live_pairs` over it is the pages a program: 1.0 where no kernel
    # of the step walks spans.
    "attend_programs",
    # the page write by runs (ops/run_write.py), counted when a step is
    # dispatched from the host's own rows: the runs of tokens the step's list
    # holds (a layer moves each as a few copies) and the tokens in them. Their
    # ratio is the tokens a run; `kv_write_tokens` over the packed width a
    # step is the share of the pack that was written at all. Both 0 where no
    # layer of the stack writes by runs.
    "kv_write_runs", "kv_write_tokens",
    # the whole-page write (ops/diff_attend.WritePages' kernel), counted
    # beside them: the live (row, page) pairs a step's write runs, a program
    # each in every owning layer, and the static bound on them, which is the
    # grid every such write ran before PR 56 (`PageWrites`): their ratio is
    # the share of that grid that was ever live. Both 0 where no layer of the
    # stack writes whole pages.
    "kv_page_writes", "kv_page_write_bound",
    # the loop's pipeline: steps dispatched while the step before was still
    # undelivered (`steps` less the pipeline's fills), and rows computed
    # for a sequence that had ended by the time their tokens arrived
    "steps_overlapped", "inflight_rows_dropped",
    # expert layers (core/moe.py), from the [layers, experts] token counts
    # a step returns beside its tokens: (token, expert) pairs routed; summed
    # over steps and layers, the tokens of the fullest expert, the tokens
    # of the mean expert (routed / experts, a float), and the experts that
    # got any token. All zero on a stack without expert layers. Where a
    # layer holds a share of the experts its router scores (`first_expert`,
    # `num_experts_held`), these four count the HELD experts' pairs, and
    # `moe_pairs_elsewhere` the pairs whose expert lives on another chip
    # (0 where every layer holds all its experts).
    "moe_tokens_routed", "moe_expert_load_max", "moe_expert_load_mean",
    "moe_experts_active", "moe_pairs_elsewhere",
    # power-retention layers (core/retention.PowerRetention), counted when a
    # step is dispatched: live rows that read or write a slot state, pages
    # folded into one (a layer: times the layers for the stack's), and the
    # keys the step's tokens attended in open chunks (a layer, a query head)
    "retention_rows", "retention_folds", "retention_chunk_tokens",
    # a stack with slot-state mixers (core/ssm.Mamba1Layer) and layers that
    # read pages they do not own (transformer.BlockSequence): tokens that
    # went through the scan of every such mixer, counted when a step is
    # dispatched; packed tokens that are neither a row's decode token nor
    # the last token of its prompt, for which the layers past the last
    # page-owning one compute what nothing reads. All zero on a stack
    # without them. Beside them in Stats(), not a counter:
    # `shared_kv_read_layers`.
    "ssm_tokens", "cross_tokens_unread",
    # Mamba-2 layers (core/ssm.Mamba2Layer): live rows times the layers whose
    # slot state (scan state and convolution tail) a step read and wrote,
    # counted when the step is dispatched; and those of them whose row holds
    # ONE token in the step, for which the scan's row pass
    # (ops/packed_ssd_scan._PallasRowPass) runs its narrow body:
    # `ssd_narrow_rows / ssd_state_rows` is the share of the live (row,
    # layer) pairs that take it. Both 0 on a stack without such layers
    "ssd_state_rows", "ssd_narrow_rows",
    # gated short-convolution layers (core/ssm.ShortConvLayer), counted when a
    # step is dispatched: live rows times the layers whose convolution tail
    # the step rewrote; and, over EVERY mixer of the stack that keeps a state
    # a slot (that tail, a Mamba layer's scan state and tail, a retention
    # layer's state), the bytes of it the step's live rows read and wrote
    # (`StateBytesPerSlot`, once in and once out). The first is 0 on a stack
    # without such layers, the second on one without slot state
    "conv_tail_rows", "slot_state_bytes",
    # the packed convolution of the Mamba-2 and gated short-convolution layers
    # (core/ssm._PackedConv), counted when a step is dispatched: the tokens
    # that read their slot's tail (a row's first K - 1 of the step:
    # `sum(min(row_len, K - 1))`), times such layers. Over the step's live
    # tokens times the layers: the share of the convolution's operand that
    # the tails' path reaches. 0 on a stack without such layers
    "conv_tail_tokens",
)

# Static engine configuration facts (set once at construction). `head_rows`:
# the token columns the step program's final norm and head run over (a draw
# a slot, and a draft source's verify lane), of the max_batch * row width +
# prefill_token_budget it packs. `attend_calls`, `attend_plans`: the attend
# kernels the step program calls, and the sets of query-block descriptors it
# builds for them, one for every distinct ops/ragged_block_attend.PlanKey
# among the calls (core/attention.BuildRaggedPlan); both 0 where the twins
# run. The counters `attend_query_blocks` ... above count what the kernels
# are given; these two count the program.
ENGINE_INFO_KEYS = (
    "paged_path", "kv_cache_dtype", "kv_bytes_per_token",
    "serve_int8_weights", "head_rows", "attend_calls", "attend_plans",
)

# Nested sub-dict sections always present in Stats().
ENGINE_SECTION_KEYS = ("scheduler", "kv_pages", "mixers", "prefix_cache")

# Keys every engine Stats() dict must carry. accepted_len_hist and
# accepted_depth_hist are two readings of the same per-verify histogram:
# hist[m] = rows whose accepted draft prefix length / accepted
# root-to-leaf tree depth was m (identical for chain speculation).
ENGINE_STATS_REQUIRED = frozenset(
    ENGINE_COUNTER_KEYS + ENGINE_INFO_KEYS + ENGINE_SECTION_KEYS
    + ("accepted_len_hist", "accepted_depth_hist"))

# Keys present only under specific configurations:
#   state_slots, shared_kv_read_layers — stacks with O(1)-state mixers
#   layer_kinds — stacks told as data (transformer.BlockSequence): how many
#                 layers are a mixer of which class, a feed-forward of which
#   spec        — engines with a draft source
#   trace       — engines with tracing enabled (the default)
#   compile     — per-compiled-program records (observe/profile.py)
#   watchdog    — engines with a stall watchdog (observe/watchdog.py)
ENGINE_STATS_OPTIONAL = frozenset(
    {"state_slots", "shared_kv_read_layers", "layer_kinds", "spec", "trace",
     "compile", "watchdog"})


def ValidateEngineStats(stats: dict) -> dict:
  """Asserts a Stats() dict matches the schema; returns it unchanged."""
  keys = set(stats)
  missing = ENGINE_STATS_REQUIRED - keys
  assert not missing, f"engine Stats() missing schema keys: {sorted(missing)}"
  unknown = keys - ENGINE_STATS_REQUIRED - ENGINE_STATS_OPTIONAL
  assert not unknown, f"engine Stats() keys not in schema: {sorted(unknown)}"
  pc = set(stats["prefix_cache"])
  assert pc == PREFIX_CACHE_STATS_KEYS, (
      f"prefix_cache section keys drifted from schema: {sorted(pc)}")
  kv = set(stats["kv_pages"])
  assert KV_PAGES_REQUIRED <= kv, (
      f"kv_pages section missing keys: {sorted(KV_PAGES_REQUIRED - kv)}")
  return stats


# -- GShardDecode telemetry --------------------------------------------------

# The batch-synchronous decode driver's per-DecodeOnce telemetry dict —
# also attached to every result record under "telemetry". Shared keys
# (below) mirror the engine surface so bench comparisons line up.
GSHARD_TELEMETRY_KEYS = (
    "prefill_s", "decode_s", "total_s",
    "prompt_tokens", "decode_tokens", "tokens_per_sec",
    "decode_state_bytes_per_seq",
    "kv_cache_dtype", "kv_bytes_per_token", "serve_int8_weights",
    "draft_tokens", "accepted_tokens", "accepted_len_hist",
    "spec_branches", "spec_width_clamps", "accepted_depth_hist",
    "prefix_hit_tokens", "prefix_cache", "step_programs",
    # SLO scheduling counters (engine scheduler section mirror) — the
    # batch-synchronous driver never preempts, so it zero-fills these
    "preemptions", "spilled_pages", "restored_pages", "host_bytes",
)

# Keys both serving surfaces advertise (values must mean the same thing).
SHARED_SERVING_KEYS = frozenset(GSHARD_TELEMETRY_KEYS) & (
    ENGINE_STATS_REQUIRED)


def GShardTelemetry(**values) -> dict:
  """Builds a telemetry dict, validating the exact schema key set."""
  keys = set(values)
  missing = set(GSHARD_TELEMETRY_KEYS) - keys
  assert not missing, f"telemetry missing schema keys: {sorted(missing)}"
  unknown = keys - set(GSHARD_TELEMETRY_KEYS)
  assert not unknown, f"telemetry keys not in schema: {sorted(unknown)}"
  return {k: values[k] for k in GSHARD_TELEMETRY_KEYS}


def PublishTelemetry(registry, values: dict, prefix: str = "serving/"):
  """Publishes a telemetry dict into a registry as gauges."""
  for k, v in values.items():
    registry.Gauge(prefix + k).Set(v)


def TelemetryFromRegistry(registry, prefix: str = "serving/") -> dict:
  """The telemetry dict as a VIEW over registry gauges (inverse of
  PublishTelemetry) — the single-source-of-truth path GShardDecode uses."""
  snap = registry.Snapshot()
  return GShardTelemetry(
      **{k: snap[prefix + k] for k in GSHARD_TELEMETRY_KEYS})


# -- compiled-step-program census ---------------------------------------------

# Names under which serving surfaces register per-step compiled programs
# with observe.CompileLog: "ragged" is the engine's one packed step.
# Draft programs deliberately don't count: the census answers "how many
# distinct shapes does one serving iteration dispatch through".
STEP_PROGRAM_NAMES = frozenset({"ragged"})

# The census key both serving surfaces expose: engine
# Stats()["compile"]["step_programs"] and GShardDecode telemetry's
# "step_programs" (2 per length bucket there — prefill + sample).
COMPILE_CENSUS_KEY = "step_programs"


# -- sub-surface key sets ----------------------------------------------------

# serving/scheduler.py Scheduler.Stats(). The SLO block (scheduler_mode
# onward) is all-zeros/'fifo' on legacy schedulers; queue_depth_high is
# the router's class-aware load signal ("scheduler/queue_depth_high" in
# registry snapshots: parked work ABOVE the default priority class).
SCHEDULER_STATS_KEYS = frozenset({
    "slots", "slots_live", "slots_live_peak", "queue_depth",
    "admitted", "finished", "cancelled", "rejected_overlong",
    "needs_kv_pages", "prefix_ordered_admissions", "width_clamps",
    "scheduler_mode", "preemptions", "restores", "preempted_queued",
    "quota_rejections", "spilled_pages", "restored_pages", "host_bytes",
    "queue_depth_high",
})

# serving/kv_cache.py PageAllocator.Stats() (page_bytes/pool_bytes only
# when the engine priced the pool via its KV census)
KV_PAGES_REQUIRED = frozenset({
    "num_pages", "page_size", "in_use", "free", "utilization",
    "peak_in_use", "num_sequences", "rolled_back_tokens", "shared_pages",
})
KV_PAGES_OPTIONAL = frozenset({"page_bytes", "pool_bytes"})

# serving/prefix_cache.py PrefixCache.Stats() — present on BOTH serving
# surfaces (engine Stats() section + GShardDecode telemetry key); surfaces
# without a cache report DisabledPrefixCacheStats().
PREFIX_CACHE_STATS_KEYS = frozenset({
    "enabled", "hits", "misses", "hit_tokens", "evictions", "cow_copies",
    "cached_pages", "cached_tokens", "stale_pages", "refreshed_pages",
})


def DisabledPrefixCacheStats() -> dict:
  """The prefix_cache section a surface WITHOUT a cache reports — same
  key set, all-zero counters, enabled=False."""
  out = {k: 0 for k in sorted(PREFIX_CACHE_STATS_KEYS)}
  out["enabled"] = False
  return out

# serving/router.py PrefixRouter.Stats() — the `router/*` registry section
# a fleet front-end exports. shadow_* describe the router-side radix
# index of what it has routed where; the *_routed counters partition
# requests_routed by why the chosen replica won (session pin, shadow
# prefix score, pure load balance).
ROUTER_STATS_KEYS = frozenset({
    "requests_routed", "pinned_routed", "prefix_routed", "balanced_routed",
    "rerouted_down", "sessions_pinned", "shadow_nodes", "shadow_evictions",
    "priority_routed",
})

# serving/fleet.py ServingFleet.Stats() — fleet-level view over N replica
# engines; `router` nests the ROUTER_STATS_KEYS dict above.
FLEET_STATS_KEYS = frozenset({
    "policy", "disaggregated", "replicas", "replicas_up", "replicas_down",
    "requests", "failovers", "resubmitted_requests",
    "handoffs", "handoff_pages", "handoff_fallbacks", "theta_swaps",
    "priority_requests", "quota_rejections",
    "router",
})

# observe/trace.py TraceRecorder.Stats()
TRACE_STATS_KEYS = frozenset({
    "events_buffered", "events_dropped",
    "requests_open", "requests_completed",
    "steps_recorded", "steps_buffered",
})


# -- HTTP status endpoints (observe/export.py) --------------------------------

# Every path a StatusServer serves. The server builds its route table FROM
# this tuple (and asserts the two match), so a new endpoint lands here or
# the server refuses to start.
ENDPOINT_PATHS = ("/metrics", "/statusz", "/traces", "/healthz")

# /statusz JSON document: top-level keys. `snapshot`/`describe` are the
# owning registry's Snapshot()/Describe(); `stats` is the owner's richer
# structured view (engine Stats() with compile records, executor program
# records) or None; `build` is BuildInfo() below.
STATUSZ_REQUIRED = frozenset({"name", "build", "snapshot", "describe",
                              "stats"})
# `startup`: the process's start-up record (observe.profile.Startup().
# Document(): set-up phases, one row a named program with what its compile
# was made of, the compile events under no named program by fun_name)
STATUSZ_OPTIONAL = frozenset({"watchdog", "startup"})

# observe/export.py BuildInfo() — the jax/config facts /statusz carries.
BUILD_INFO_KEYS = frozenset({
    "jax_version", "jaxlib_version", "backend", "device_count",
    "device_kind", "process_index", "process_count",
})


def ValidateStatusz(doc: dict) -> dict:
  """Asserts a /statusz document matches the schema; returns it unchanged."""
  keys = set(doc)
  missing = STATUSZ_REQUIRED - keys
  assert not missing, f"/statusz missing schema keys: {sorted(missing)}"
  unknown = keys - STATUSZ_REQUIRED - STATUSZ_OPTIONAL
  assert not unknown, f"/statusz keys not in schema: {sorted(unknown)}"
  bkeys = set(doc["build"])
  bmissing = BUILD_INFO_KEYS - bkeys
  assert not bmissing, f"/statusz build missing keys: {sorted(bmissing)}"
  return doc


# -- goodput / badput accounting (observe/goodput.py) -------------------------

# Wall-time classification buckets. `step` is the productive bucket;
# everything else is badput; `other` is the residual (wall − accounted), so
# the buckets always sum to ~wall time.
GOODPUT_BUCKETS = ("step", "compile", "checkpoint_save", "checkpoint_restore",
                   "eval", "infeed_wait", "recovery", "other")
GOODPUT_PRODUCTIVE = frozenset({"step"})

# observe/goodput.py GoodputTracker.Stats() — the `goodput/*` section.
GOODPUT_STATS_KEYS = frozenset(
    {f"{b}_s" for b in GOODPUT_BUCKETS} | {"wall_s", "productive_ratio"})


# -- stall watchdog (observe/watchdog.py) -------------------------------------

# Trip taxonomy: no heartbeat within k×EMA step time, a step-time
# regression, or serving queue growth without retirement.
WATCHDOG_TRIP_KINDS = ("no_heartbeat", "step_regression", "queue_stall")

# observe/watchdog.py StallWatchdog.Stats() — the `watchdog/*` section.
WATCHDOG_STATS_KEYS = frozenset({
    "healthy", "beats", "trips", "tripped", "last_beat_age_s",
    "step_ema_s", "capture_armed",
})


# -- device scopes -----------------------------------------------------------

# Every `jax.named_scope` the jitted programs enter, as one tree: name ->
# (parent, what it holds). A scope is a string in an op's metadata (its
# `op_name` in a profiler trace says which block it belongs to); no shape,
# number or compiled instruction depends on it. A block (parent None) is a
# stretch of the step program; a child names a lump inside its block. XLA
# names a Pallas kernel's op after the innermost scope round its call, and
# the benchmark's readers find kernels by that name, so no scope that is
# new goes directly round a `pallas_call` (docs/observability.md) unless the
# kernel is to have a name of its own (`ssd_row_pass`, PR 57).
# `benchmarks/harness/scope_ms.py` reads this tree: milliseconds a step by
# block. Names are path segments of `op_name`: none may be a JAX primitive's
# or a jitted function's.
DEVICE_SCOPES = {
    "embed": (None, "token embedding lookup (and the absolute position "
              "embedding where the model has one)"),
    "attend_plan": (None, "what a serving step's attention derives from its "
                    "rows alone, built once before the scans over layers "
                    "(core/attention.BuildRaggedPlan): the token view, the "
                    "kernels' query-block descriptors, the page write's "
                    "pairs"),
    "norm": (None, "every layer norm: a block's pre-norm, the final norm, "
             "and in the serving step the gather of the head's columns"),
    "atten": (None, "a layer's sequence mixer with its residual add: "
              "attention, a Mamba-1 or Mamba-2 layer or a gated memory unit"),
    "qkv_proj": ("atten", "the query, key and value projections"),
    "rope": ("atten", "rotary position embedding of q and k, and the "
             "query's scale"),
    "out_proj": ("atten", "the output projection (a differential layer's "
                 "with its pair norm)"),
    "kv_write": ("atten", "new tokens' K and V into the page pool: the "
                 "runs' copies (ops/run_write.py) or the whole-page write "
                 "(ops/diff_attend.WritePages), kernels named after it, and "
                 "an int8 pool's scales' scatter"),
    "kv_layout": ("kv_write", "round the page-write kernel "
                  "(ops/diff_attend.WritePages), not the write itself: the "
                  "pairs' pages looked up in the layer's table and the new "
                  "tokens padded to whole tiles of P (the kernel lays them "
                  "out on the lanes itself)"),
    "ragged_attend": ("atten", "ops/ragged_block_attend.RaggedAttend: the "
                      "ragged attend kernels (named after it) and the "
                      "group's re-layout round them"),
    "attend_descriptors": ("ragged_attend", "the query-block descriptors "
                           "of a ragged kernel called without the step's "
                           "plan (with it: `attend_plan`)"),
    "mla_attend": ("atten", "ops/latent_attend.LatentAttend: the latent "
                   "attend kernels of the absorbed form (named after it), "
                   "32 query heads over one latent row a token, with the "
                   "heads' re-layout round them"),
    "mla_absorb": ("atten", "latent attention's absorption: q_nope through "
                   "W_kvb's key half into the latent space before the "
                   "attend (with the query's scale), the context through its "
                   "value half after it"),
    "diff_attend": ("atten", "ops/diff_attend.DiffAttend: the differential "
                    "attend kernels (named after it)"),
    "diff_layout": ("diff_attend", "round those kernels: the padded queries, "
                    "the group's re-layout in and out, the pools as they "
                    "lie, and the pairs' difference"),
    "diff_descriptors": ("diff_attend", "the query-block descriptors of a "
                         "differential kernel called without the step's "
                         "plan (with it: `attend_plan`)"),
    "ssm_in_proj": ("atten", "a Mamba-1 layer's input projection to u, z"),
    "ssm_conv": ("atten", "its causal depthwise convolution: the taps, the "
                 "slot tail's gather and its write-back"),
    "ssm_params": ("atten", "the scan's inputs from the convolution: silu, "
                   "w_x, w_dt, softplus"),
    "ssm_scan": ("atten", "the selective scan (ops/selective_scan.py; its "
                 "kernel is named after it)"),
    "ssm_out_proj": ("atten", "the gate by z and the output projection"),
    "gmu": ("atten", "a gated memory unit, whole"),
    "ssd_in_proj": ("atten", "a Mamba-2 layer's input projection to z, xBC "
                    "and dt"),
    "ssd_conv": ("atten", "its causal depthwise convolution over x, B and C "
                 "together with the silu, the slot tail's gather and its "
                 "write-back, and the step size's softplus"),
    "ssd_scan": ("ssm_scan", "the scalar-decay scan over the packed tokens "
                 "(ops/packed_ssd_scan.py), slot state in and out: the "
                 "chunked form's operations and the pass over the slots' "
                 "states"),
    "ssd_row_pass": ("ssd_scan", "that pass: the kernel named after it "
                     "(ops/packed_ssd_scan._PallasRowPass), a program a "
                     "(slot, channel tile) that reads and writes its block "
                     "of the state once"),
    "ssd_gate_norm": ("atten", "the gate by z and the RMSNorm over groups of "
                      "channels after it"),
    "ssd_out_proj": ("atten", "the output projection"),
    "qk_norm": ("atten", "the RMSNorm over each head of q and of k, before "
                "the rotation (a power-retention layer's; attention."
                "MultiHeadedAttention's `qk_norm_epsilon`)"),
    "atten_gate": ("atten", "an attention layer's output gate "
                   "(`output_gate`): the gate's own projection of the "
                   "layer's input, the sigmoid and the product with the "
                   "attend's output, before the output projection"),
    "short_conv": ("atten", "a gated short-convolution mixer, whole "
                   "(core/ssm.ShortConvLayer): the input projection to B, C "
                   "and X, the two gates and the output projection"),
    "short_conv_taps": ("short_conv", "inside it, what is not a matmul or a "
                        "gate: the packed causal depthwise sum, the slot "
                        "tail's gather and its write-back"),
    "post_norm": (None, "a norm on a branch's OUTPUT before the residual "
                  "add (`post_norm_tpl`), entered inside `atten` for the "
                  "mixer's and inside `ffn` for the feed-forward's (dense or "
                  "experts); one name in a tree of one parent a scope, so "
                  "it rolls up into neither, as `norm` does"),
    "retention_gate": ("atten", "a power-retention layer's gates: the "
                       "log-sigmoid, the log-gates cumulated over a row's "
                       "open chunk and the step's tokens, the page each "
                       "token's count starts at, and the query blocks"),
    "retention_chunk": ("atten", "the attention form over a row's open "
                        "chunk and the step's own tokens "
                        "(ops/power_retention.py; its kernel is named after "
                        "it) with the descriptors and gathers round it"),
    "retention_state": ("atten", "the slot state's query and fold "
                        "(ops/power_retention.py; its kernels are named "
                        "after it): a row of one token on the VPU, the "
                        "blocks of longer rows on the MXU, the pages a step "
                        "completes folded in place, and the operands "
                        "gathered for them"),
    "retention_out": ("atten", "the two parts joined: the state's through "
                      "the decay since the chunk's start, the normaliser"),
    "ffn": (None, "a layer's feed-forward with its residual add: dense, or "
            "the expert layer"),
    "moe_route": ("ffn", "router logits, top-k and the softmax over them"),
    "moe_dispatch": ("ffn", "sort of the (token, expert) pairs, counts, and "
                     "the gather of tokens into expert order"),
    "moe_experts": ("ffn", "the grouped matmuls, three of a gated expert or two "
                    "of an ungated one (megablox names its "
                    "kernels `gmm` inside it)"),
    "moe_combine": ("ffn", "un-sort of the experts' rows by one gather in "
                    "their own dtype, k major; then the weighting and the "
                    "sum of a token's experts in f32"),
    "moe_shared": ("ffn", "the shared expert every token goes through, "
                   "beside the routed ones"),
    "layer_scan": (None, "a scan over stacked layers, less what its layers "
                   "name: the slices of the stacked weights and states a "
                   "trip reads and the stacking of what it hands back"),
    "head_loss": (None, "the training head: logits and cross-entropy"),
    "head_sample": (None, "the serving head: the draw columns, logits over "
                    "them and the draw"),
    "optimizer_update": (None, "Learner.Apply: gradient norm and clip, the "
                         "optimizer's update, the new weights"),
}


def Scope(name: str):
  """`jax.named_scope(name)` for a name DEVICE_SCOPES declares: the one way
  the program enters a device scope. The check is a dict lookup while
  Python traces; nothing of it reaches the compiled program."""
  if name not in DEVICE_SCOPES:
    raise KeyError(f"device scope {name!r} is not declared in "
                   "observe.schema.DEVICE_SCOPES")
  return jax.named_scope(name)
