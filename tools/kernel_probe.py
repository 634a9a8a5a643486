#!/usr/bin/env python3
"""ONE layer's call of a kernel alone, at a cell's shapes. Five cases.

`--case page_write` (the default): the whole-page write
(ops/diff_attend.WritePages) at the shapes `phi4flash_serve_reason` runs it
at: a pool `[6561, 128, 20, 64]` bf16 for K and one for V, a pack of 576
tokens over 64 rows with 64 pages each.

  python3 tools/kernel_probe.py [--steps decode,chunk] [--calls 50]
      [--variants scatter,kernel] [--parent DIR] [--seed 0]
      [--pool_pages 6561] [--out chiprun_out/kernel_probe.jsonl]

Two kinds of step: `decode` (64 one-token rows, the step four in five of the
cell are) and `chunk` (63 one-token rows beside a 512-token chunk that starts
mid-page). Variants, each `fn(k_pool, v_pool, k_new, v_new, tables, rows,
plan) -> (k_pool, v_pool)`: `scatter` is the XLA lowering (on the chip a
re-layout of the whole pool: the result every other variant is held to, not
a time anyone pays), `kernel` the chip's lowering as this tree has it, and
`parent` the same call through DIR/lingvo_tpu/ops/diff_attend.py (a `git
archive` of another commit) where `--parent DIR` is given. A builder adds a
form under trial to `VARIANTS` from a script of their own.

Every variant's pools are compared BITWISE with the first's on the device
(all pages but the trash page, which the scatter's padding tokens write).
The time is one program that runs the write `--calls` times over the same
pools (the plan built outside it, as a step builds it once for its nine
owners; the new tokens behind a barrier a trip, so the preparation in front
of the kernel is not hoisted out of the loop) over `--calls`: milliseconds a
layer's write. Prints one JSON line a (step, variant).

`--case row_pass`: the Mamba-2 scan's pass over the slots' states
(ops/packed_ssd_scan._PallasRowPass) at the two cells that run it:
`--shapes granite` (128 heads of 64 in ONE group: 16 tiles of 512 a slot)
and `nemotron` (64 heads in 8 groups of 512), 64 slots, a pack of 1,088,
chunks of 64, N = 128, the layer's states the fourth of a stack of five.

  python3 tools/kernel_probe.py --case row_pass [--shapes granite,nemotron]
      [--steps decode,chunk] [--variants none,hand_over,narrow,all]
      [--parent DIR] [--calls 50] [--tiny]

Steps: `decode` (64 one-token rows) and `chunk` (63 beside one chunk of
1,024 that comes into its last scan chunk from an earlier one). Variants:
`none` is the kernel with none of `packed_ssd_scan.CUTS`, each cut's name
that cut alone (several joined by `+`), `all` the kernel as the step runs
it, `parent` DIR's kernel.
Every variant's scan (y and the stack of states) is held to the first's on
the device at the tests' tolerance (3e-5). Two times a (shape, step,
variant): `row_pass_ms`, the kernel's `jit` (what it gathers in front of the
call included) over operands its own module's chunked form built once, and
`scan_ms`, the layer's whole `PackedSsdScan`; both a loop of `--calls` trips
that carries the stack. `--tiny` is the CPU's rehearsal (interpret mode, 4
slots, heads of the tests' cases): counts, never a time.

`--case moe_combine`: ONE expert layer's combine (core/moe.py, the block
under the scope `moe_combine`) at the four cells that run it, a full step of
1,088 tokens: `--shapes granite` (k 10, D 4096, 36 of 72 experts held),
`smallthinker` (6, 2560, all 64), `nemotron` (6, 2688, all 128), `mistral`
(4, 4096, 32 of 128).

  python3 tools/kernel_probe.py --case moe_combine
      [--shapes granite,smallthinker,nemotron,mistral]
      [--variants parent,argsort,scatter] [--calls 50] [--seed 0] [--tiny]

The operands are what the layer's grouped matmul hands its combine: random
bf16 `ys [T * k, D]`, NaN in every row past the last run; the permutation
`order` a stable sort by expert of seeded choices gives (a pair of an expert
held elsewhere carries the index E_held and sorts last); seeded weights
`[T, k]` f32. Variants, each `fn(ys, order, top_idx, weights, e) -> f32
[T, D]`: `parent` is the form PR 60 replaced, kept HERE as the reference
(f32 product over the sorted rows, an f32 gather through a second argsort, a
reshape to `[T, k, D]`, the sum over axis 1); `argsort` the layer's form (one
gather of the bf16 rows into `[k, T, D]`, then weight, mask and sum over axis
0 in f32) and `scatter` the same with the inverse permutation from
`zeros.at[order].set(arange)`; a form under trial is registered in
`COMBINE_VARIANTS`. Every variant is held to the first within the f32 sum's
reordering (1e-5 of the largest magnitude). The time is a loop of `--calls`
trips, each over one of FOUR layers' operands by the trip's index (nothing is
the loop's to hoist, and four layers' rows do not fit the chip's 128 MiB of
VMEM; XLA still stages a layer's `ys` there where the program holds nothing
else, which a step does not promise), the result cast to bf16 as the layer's
is; `gb_s` is the 276 MB form's bytes (the bf16 rows read and written by the
gather, read by the fused pass, `[T, D]` bf16 written) over that time,
whatever the variant moves. `--tiny` rehearses on the CPU.

`--case grouped_attend`: ONE layer's `RaggedAttend` through the grouped
kernel (ops/ragged_block_attend._GroupedAttendKernel) at the two cells whose
steps it holds the largest share of: `--shapes smallthinker` (28 query heads
over 4 KV heads of 128, windows 0 and 4,096, a table of 128 pages) and
`trinity` (32 over 4, windows 0 and 2,048, 272 pages); bf16 pages of 128, 64
rows, a pack of 1,088 tokens. `--shapes lfm2` is `lfm2_24b_serve_chat_wide`'s
attention layer: 32 query heads over 8 KV heads of 64, TWO KV heads side by
side on a token's row of the pool (`[pages, 128, 4, 128]`), a table of 76
pages, 256 rows, a pack of 1,280; `lfm2_padded` the form that did not land,
every head padded to 128 lanes (the kernel as it was over 8 KV heads of 128:
twice the pool's bytes).

  python3 tools/kernel_probe.py --case grouped_attend
      [--shapes smallthinker,trinity,lfm2,lfm2_padded] [--steps decode,chunk]
      [--chunk_at 3k,12k,30k] [--variants masked,clear] [--parent DIR]
      [--calls 50] [--seed 0] [--tiny]

Steps: `decode` (64 one-token rows at seeded contexts) and `chunk` (63 such
rows beside one 1,024-token chunk that starts mid-page past each of
`--chunk_at`, where the shape's table holds it: `chunk@3k` ...). Variants,
each `fn(q, k_pool, v_pool, tables, tokens, plan) -> [T, N, H]`: `masked` is
the kernel with the plan's clear range emptied (every page takes the masked
body: the parent's arithmetic), `clear` the kernel as the step runs it,
`span1` the kernel with the pages a decode row's program walks
(`_GROUPED_SPAN`) forced to 1 (PR 63's grid: a program a page; `span<n>`
forces any n), and `parent` the same call through
DIR/lingvo_tpu/ops/ragged_block_attend.py. A form under trial is registered
in `ATTEND_VARIANTS` from a script of the builder's own. The plan is built outside the timed loop (a step builds it
once for its layers); every variant's output is held BITWISE to the first's
on the device. The time is a loop of `--calls` trips over the same pools, the
queries behind a barrier with the trip's index. Prints ms a call and, from
the host's count of the step's pairs (`LivePairs` of the one-token rows and
of the chunk), us a decode pair (the decode step's) and us a chunk pair (the
chunk step's time less its one-token rows' pairs at the decode step's price).

`--case packed_conv`: ONE layer's convolution scope (`ssd_conv`, `ssm_conv`,
`short_conv_taps`: core/ssm._PackedConv over the packed axis, what the layer
does with the sum before it hands it on, and `_PackedConvTail`) at the four
cells that run it: `--shapes granite` (C 8448 = E 8192 + 2 x 128, K 4, 64
slots, a pack of 1,088; bias, silu and the split into u, B, C in f32),
`nemotron` (C 6144, the same), `lfm2` (C 2048, K 3, 256 slots, a pack of
1,280; the gate's product, cast to bf16) and `phi4flash` (C 5120, K 4, 64
slots, a pack of 576; bias and silu, cast to bf16).

  python3 tools/kernel_probe.py --case packed_conv
      [--shapes granite,nemotron,lfm2,phi4flash] [--steps decode,chunk]
      [--variants loop,tree,scatter_add,gather_share]
      [--parent DIR] [--calls 50] [--seed 0] [--tiny]

Steps: `decode` (every slot a one-token row, the pack cut to the B columns
`ragged.OverLiveRows` runs such a step over: EVERY token reads a tail) and
`chunk` (B - 1 such rows beside one chunk of the whole budget that continues a
prompt, one slot starting a request). Variants, each `fn(ssm, u32, held_tail,
conv_w, rows) -> [T, C] f32`: `loop` is the form PR 66 replaced, kept HERE as
the reference (a pad, a gather of a `[T, C]` array from the tails and a select
a tap); `tree` this tree's `_PackedConv`; `parent` DIR/lingvo_tpu/core/ssm.py's
`_PackedConv` AND its `_PackedConvTail`; the forms that lost lay the same
tails' share `[B, K - 1, C]` into the same fused sum another way:
`scatter_add` (`.at[places].add`) and `gather_share` (ONE gather of T rows
from the share; a third, the share scattered into zeros and added in the
pass, read 0.48 / 0.26 / 0.14 / 0.13 ms at the four chunk steps and is gone). A form under
trial is registered in `CONV_VARIANTS`. Every variant's sum is held to the
first's within the f32 sum's reordering (1e-5 of the largest magnitude, live
tokens) and its new tails to equality; `token_row_gathers` counts the gathers
of T rows in its jaxpr. The time is a loop of `--calls` trips, each over one
of FOUR layers' inputs by the trip's index and the tails the trip before
left; `once_ms_at_819gb_s` is the operand read once in bf16 and the scope's
result written once, at the chip's HBM speed. `--tiny` rehearses on the CPU
(counts, and no time at all).

The rows that settled PR 66 (one v5e, ms a layer, `parent` -> `tree`, the
final tree; granite's chunk step is the one its cell runs nine times a step):
granite chunk 0.985 -> 0.268, decode 0.118 -> 0.064; nemotron 0.481 -> 0.201
and 0.096 -> 0.057; lfm2 0.139 -> 0.102 and 0.079 -> 0.055; phi4flash 0.203 ->
0.086 and 0.073 -> 0.042. What lost, in the same call: `scatter_add` 0.286 at
granite's chunk but 0.384 at nemotron's (its scatter 208 us there for 46 at
granite's, the same 192 update rows: 0.2-1.1 us a row, by a rule of XLA's that
the shapes do not tell), 0.170 at lfm2's (512 update rows; OVER the parent's
0.139) and over the parent at three of the four decode steps; `gather_share`
0.506 at granite's chunk (one gather of 1,088 rows of 33 KB: 111 us, and a
layout XLA then chose worse), level with `tree` at the decode steps and 0.005
ahead at lfm2's chunk. A one-hot product costs what its FLOPs cost (T x (K -
1) B x C, three bf16 passes: 36-65 us a layer), fuses into the pass, and is
the same at every shape: one form, no choice by shape. Of a decode step's
scope two thirds were `_PackedConvTail` (a 26 us gather along the tail's own
axis, relayouts of `[B, K - 1, C]`), which is why it was touched too, and why
the share is built entry-major (`[K - 1, B, C]`).

Its readings are a builder's, never the ledger's: one call in a loop has no
neighbours to share the chip's memory system with, and no step round it.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the cell's shapes (benchmarks/configs/phi4flash.json, `serving`)
PAGE, KV_HEADS, HEAD, ROWS, BUDGET, TABLE_PAGES = 128, 20, 64, 64, 512, 64
HEAD_DIM = 128   # the grouped attend's head size (both of its shapes)

VARIANTS = {}   # name -> fn(diff_attend module) -> the variant's function


def _Lowered(lowering):
  def _Make(diff_attend):
    return lambda k, v, k_new, v_new, tables, rows, plan: (
        diff_attend.WritePages(k, v, k_new, v_new, tables, rows,
                               lowering=lowering,
                               plan=plan if lowering == "pallas" else None))
  return _Make


VARIANTS["scatter"] = _Lowered("xla")
VARIANTS["kernel"] = _Lowered("pallas")


def _ParentModule(root, name="diff_attend", package="ops"):
  """DIR/lingvo_tpu/<package>/<name>.py under a name of its own; what it
  imports is this tree's."""
  path = os.path.join(root, "lingvo_tpu", package, name + ".py")
  spec = importlib.util.spec_from_file_location("parent_" + name, path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


def StepRows(kind: str, rng, t: int, wmax: int):
  """(tokens a row, first position a row) of a `kind` step: rows at seeded
  contexts inside their 64 pages; the chunk starts mid-page at an odd slot."""
  import numpy as np
  context = rng.randint(PAGE, TABLE_PAGES * PAGE - BUDGET - 1, size=ROWS)
  lens = np.ones(ROWS, np.int64)
  if kind == "chunk":
    lens[ROWS // 2] = t - (ROWS - 1)
    context[ROWS // 2] = 3 * PAGE + 37
  else:
    assert kind == "decode", kind
  assert lens.sum() <= t and lens.max() <= wmax
  return lens, context


# -- the row pass of the packed Mamba-2 scan -----------------------------------

# heads and groups (benchmarks/configs/granite4hsmall.json, nemotron3nano.json)
ROW_PASS_SHAPES = {"granite": (128, 1), "nemotron": (64, 8)}
ROW_PASS_TINY = {"granite": (16, 1), "nemotron": (16, 2)}   # the tests' cases
HEAD_CHANNELS, STATE, CHUNK, STACK, LAYER = 64, 128, 64, 5, 3


def RowPassInputs(shape: str, step: str, seed: int, tiny: bool):
  """(x, dt, a, b, c, d_skip, stack of states, rows, chunk) of one layer's
  scan in a `step` step at `shape`'s heads and groups, all seeded."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  from lingvo_tpu.core import ragged as ragged_lib
  hm, g = (ROW_PASS_TINY if tiny else ROW_PASS_SHAPES)[shape]
  slots, budget, q = (4, 24, 8) if tiny else (ROWS, 1024, CHUNK)
  t = slots + budget
  rng = np.random.RandomState(seed)
  lens = np.ones(slots, np.int64)
  context = rng.randint(1, 4096, size=slots)
  if step == "chunk":
    lens[slots // 2], context[slots // 2] = budget, 2 * budget
  else:
    assert step == "decode", step
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in
                                 ragged_lib.BuildRaggedRows(
                                     lens, context, t, budget + 1)))
  f32 = lambda v: jnp.asarray(v, jnp.float32)
  dt = f32(rng.uniform(0.001, 0.5, (t, hm)))
  x, b, c = (f32(rng.randn(t, *k)) for k in (
      (hm, HEAD_CHANNELS), (g, STATE), (g, STATE)))
  a, d_skip = -f32(rng.uniform(1, 16, hm)), f32(rng.randn(hm))
  stack = jax.random.normal(jax.random.PRNGKey(seed), (
      STACK, slots, hm, HEAD_CHANNELS, STATE), jnp.float32)
  return (x, dt, a, b, c, d_skip, stack, rows), q


def RowPassVariant(module, cuts, interpret: bool):
  """-> (operands(inputs..., q): what `module`'s chunked form hands its row
  pass; kernel(operands): the pass's `jit` -> the stack; scan(inputs..., q):
  the layer's whole scan -> (y, stack)), the kernel with `cuts` (None: the
  module's own, a parent's that knows none)."""
  import functools
  import jax
  kw = {} if cuts is None else {"cuts": tuple(cuts)}

  def _Pass(g):
    return functools.partial(module._PallasRowPass, g=g, interpret=interpret,
                             **kw)

  def _Operands(x, dt, a, b, c, d_skip, stack, rows, q):
    seen = []

    def _Spy(*operands):
      seen.append(operands)
      return module._XlaRowPass(*operands, g=b.shape[1])

    module._ChunkedPackedScan(x, dt, a, b, c, d_skip, stack, rows, q, _Spy,
                              LAYER)
    return seen[0]

  def _Kernel(operands, g):
    return _Pass(g)(*operands)[-1]

  def _Scan(x, dt, a, b, c, d_skip, stack, rows, q):
    with jax.named_scope("ssd_scan"):
      return module._ChunkedPackedScan(x, dt, a, b, c, d_skip, stack, rows,
                                       q, _Pass(b.shape[1]), LAYER)

  return _Operands, _Kernel, _Scan


def RowPassMain(args) -> int:
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.core import compile_cache
  from lingvo_tpu.ops import packed_ssd_scan

  compile_cache.Configure()
  on_tpu = jax.default_backend() == "tpu"
  assert on_tpu or args.tiny, "a time comes from the chip; --tiny rehearses"
  # a variant is its cuts, joined by "+"
  variants = {"none": (packed_ssd_scan, ()),
              "all": (packed_ssd_scan, packed_ssd_scan.CUTS)}
  if args.parent:
    variants["parent"] = (_ParentModule(args.parent, "packed_ssd_scan"), None)
  names = args.variants.split(",") + (["parent"] if args.parent else [])

  def _MsACall(body, carry, rest):
    """body(carry, rest) -> carry, `--calls` trips in one program; the rest
    behind a barrier with the trip's index: what a call prepares in front
    of its kernel is the trip's, not the loop's."""
    def _Run(carry, *rest):
      def _Trip(i, carry):
        _, held = jax.lax.optimization_barrier((i, rest))
        return body(carry, held)
      return jax.lax.fori_loop(0, args.calls, _Trip, carry)

    loop = jax.jit(_Run, donate_argnums=0)
    carry = jax.block_until_ready(loop(carry, *rest))           # compiles
    start = time.perf_counter()
    jax.block_until_ready(loop(carry, *rest))
    return (time.perf_counter() - start) * 1e3 / args.calls

  device = jax.devices()[0]
  lines = []
  for shape in args.shapes.split(","):
    for step in args.steps.split(","):
      (*ops, stack, rows), q = RowPassInputs(shape, step, args.seed,
                                             args.tiny)
      g = ops[3].shape[1]
      first = None
      for name in names:
        module, cuts = variants.get(name) or (packed_ssd_scan,
                                              name.split("+"))
        operands, kernel, scan = RowPassVariant(module, cuts, not on_tpu)
        y, new = jax.jit(scan, static_argnums=8)(*ops, stack + 0.0, rows, q)
        if first is None:
          first, err = (y, new), None
        else:
          err = float(max(jnp.max(jnp.abs(got - want))
                          for got, want in zip((y, new), first)))
        del y, new
        state, *given = jax.jit(operands, static_argnums=8)(*ops, stack, rows,
                                                            q)
        pass_ms = _MsACall(
            lambda state, held: kernel((state, *held), g), state, given)
        del state, given
        scan_ms = _MsACall(
            lambda stack, held: scan(*held[:-1], stack, held[-1], q)[1],
            stack + 0.0, (*ops, rows))
        lines.append({
            "case": "row_pass", "shape": shape, "step": step,
            "variant": name, "row_pass_ms": pass_ms, "scan_ms": scan_ms,
            "max_abs_err_to_first": err,
            "within_3e-5": None if err is None else err <= 3e-5,
            "heads": int(ops[0].shape[1]), "groups": int(g),
            "slots": int(stack.shape[1]), "tokens": int(ops[0].shape[0]),
            "calls": args.calls, "seed": args.seed, "tiny": args.tiny,
            "device": {"platform": device.platform,
                       "kind": device.device_kind}})
        print(json.dumps(lines[-1]), flush=True)
      del first
  _Append(args.out, lines)
  return 0 if all(l["within_3e-5"] is not False for l in lines) else 1


# -- the expert layer's combine ------------------------------------------------

# k, D, experts held, experts routed over (benchmarks/configs/<cell>.json)
COMBINE_SHAPES = {"granite": (10, 4096, 36, 72),
                  "smallthinker": (6, 2560, 64, 64),
                  "nemotron": (6, 2688, 128, 128),
                  "mistral": (4, 4096, 32, 128)}
COMBINE_TOKENS = 1088


def _CombineParent(ys, order, top_idx, weights, e):
  """The combine as it stood before PR 60: the reference."""
  import jax.numpy as jnp
  t, k = top_idx.shape
  w_sorted = weights.reshape(-1)[order]
  live = jnp.arange(t * k) < jnp.sum(top_idx < e)
  ys = jnp.where(live[:, None], ys.astype(jnp.float32) * w_sorted[:, None],
                 0.0)
  return ys[jnp.argsort(order)].reshape(t, k, -1).sum(axis=1)


def _CombineKMajor(scatter: bool):
  """The layer's form; the pairs' places in sorted order by a second argsort
  (the layer's) or, `scatter`, by `zeros.at[order].set(arange)`."""
  def _Combine(ys, order, top_idx, weights, e):
    import jax.numpy as jnp
    t, k = top_idx.shape
    pos = (jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype)) if scatter
           else jnp.argsort(order)).reshape(t, k).T
    live = (top_idx < e).T[..., None]
    ys = jnp.where(live, ys[pos], 0).astype(jnp.float32)
    return jnp.sum(ys * jnp.where(live, weights.T[..., None], 0.0), axis=0)
  return _Combine


COMBINE_VARIANTS = {"parent": _CombineParent, "argsort": _CombineKMajor(False),
                    "scatter": _CombineKMajor(True)}


COMBINE_LAYERS = 4   # a trip reads one of as many layers' operands, by its
#                      index: four layers' rows are more than the chip's VMEM


def CombineInputs(shape: str, seed: int, tiny: bool):
  """(ys bf16 [L, T * k, D], order [L, T * k], top_idx [L, T, k], weights f32
  [L, T, k], experts held) of COMBINE_LAYERS layers' combines at `shape`,
  seeded; a layer's last 64 tokens padding, as a step's that is not full."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  k, d, held, routed = COMBINE_SHAPES[shape]
  t = 64 if tiny else COMBINE_TOKENS
  d = 256 if tiny else d
  rng = np.random.RandomState(seed)
  top_idx = np.argsort(rng.rand(COMBINE_LAYERS, t, routed), axis=-1)[..., :k]
  top_idx = np.where(top_idx < held, top_idx, held)             # k distinct
  top_idx[:, t - t // 17:] = held
  flat = top_idx.reshape(COMBINE_LAYERS, -1)
  order = np.argsort(flat, axis=-1, kind="stable")
  live = np.arange(t * k) < (flat < held).sum(-1, keepdims=True)
  ys = jnp.where(live[..., None], jax.random.normal(
      jax.random.PRNGKey(seed), (COMBINE_LAYERS, t * k, d), jnp.bfloat16),
                 jnp.nan)
  weights = rng.dirichlet(np.ones(k), size=(COMBINE_LAYERS, t))
  return (ys, jnp.asarray(order, jnp.int32), jnp.asarray(top_idx, jnp.int32),
          jnp.asarray(weights, jnp.float32), held)


def CombineMain(args) -> int:
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.core import compile_cache

  compile_cache.Configure()
  assert jax.default_backend() == "tpu" or args.tiny, (
      "a time comes from the chip; --tiny rehearses")
  device = jax.devices()[0]
  lines = []
  for shape in args.shapes.split(","):
    *ops, e = CombineInputs(shape, args.seed, args.tiny)
    (_, t, k), d = ops[2].shape, ops[0].shape[-1]
    form_bytes = 3 * t * k * d * 2 + t * d * 2
    first = None
    for name in args.variants.split(","):
      fn = COMBINE_VARIANTS[name]
      out = jax.jit(lambda *ops, fn=fn: fn(*(a[0] for a in ops), e))(*ops)
      if first is None:
        first, err = out, None
      else:
        err = float(jnp.max(jnp.abs(out - first)) / jnp.max(jnp.abs(first)))
      finite = bool(jnp.all(jnp.isfinite(out)))

      def _Run(carry, *ops, fn=fn):
        def _Trip(i, carry):
          # the trip's layer by its index: nothing is the loop's to hoist
          return fn(*(jax.lax.dynamic_index_in_dim(
              a, i % COMBINE_LAYERS, keepdims=False) for a in ops),
                    e).astype(carry.dtype)
        return jax.lax.fori_loop(0, args.calls, _Trip, carry)

      loop = jax.jit(_Run, donate_argnums=0)
      carry = jax.block_until_ready(
          loop(jnp.zeros((t, d), jnp.bfloat16), *ops))           # compiles
      start = time.perf_counter()
      jax.block_until_ready(loop(carry, *ops))
      ms = (time.perf_counter() - start) * 1e3 / args.calls
      lines.append({
          "case": "moe_combine", "shape": shape, "variant": name,
          "ms_a_layer": ms, "gb_s": form_bytes / ms / 1e6,
          "form_mb": form_bytes / 1e6, "rel_err_to_first": err,
          "within_1e-5": None if err is None else err <= 1e-5,
          "finite": finite, "tokens": t, "k": k, "d": d, "held": e,
          "live_pairs": int(jnp.sum(ops[2][0] < e)), "calls": args.calls,
          "seed": args.seed, "tiny": args.tiny,
          "device": {"platform": device.platform,
                     "kind": device.device_kind}})
      print(json.dumps(lines[-1]), flush=True)
  _Append(args.out, lines)
  return 0 if all(l["within_1e-5"] is not False and l["finite"]
                  for l in lines) else 1


# -- the grouped attend kernel -------------------------------------------------

# query heads, KV heads, the layer's windows, table pages (benchmarks/configs/
# smallthinker21b.json, trinitymini.json: `serving.max_seq_len` / 128), and
# the deepest context a one-token row is seeded at
ATTEND_SHAPES = {"smallthinker": (28, 4, (0, 4096), 128, 6000),
                 "trinity": (32, 4, (0, 2048), 272, 12000),
                 "lfm2": (32, 8, (0,), 76, 4000),
                 "lfm2_padded": (32, 8, (0,), 76, 4000)}
# (head size, KV heads a row of the pool, rows) where they are not (HEAD_DIM,
# 1, ROWS)
ATTEND_HEADS = {"lfm2": (64, 2, 256), "lfm2_padded": (128, 1, 256)}
ATTEND_BUDGET = 1024


def _AttendMasked(rba):
  """The call with the plan's clear range emptied: one body at every page."""
  def _Call(q, kp, vp, tables, tokens, plan, **kw):
    key, = plan
    blocks = plan[key]
    if blocks.clear is not None:
      plan = {key: blocks._replace(clear=blocks.clear * 0)}
    return rba.RaggedAttend(q, kp, vp, tables, *tokens, plan=plan, **kw)
  return _Call


def _AttendAsBuilt(rba):
  return lambda q, kp, vp, tables, tokens, plan, **kw: rba.RaggedAttend(
      q, kp, vp, tables, *tokens, plan=plan, **kw)


ATTEND_VARIANTS = {"masked": _AttendMasked, "clear": _AttendAsBuilt,
                   "span1": _AttendAsBuilt}


def _ForcedSpan(name: str):
  """The n of a variant `span<n>`, else None."""
  return int(name[4:]) if name.startswith("span") and name[4:].isdigit() \
      else None


def AttendRows(step: str, rng, rows: int, budget: int, deepest: int,
               table_pages: int):
  """(tokens a row, first position a row) of a grouped-attend step: `decode`,
  or `chunk@<n>k` (the chunk in the middle row, from n * 1024 + 293: two
  pages and 37 slots in)."""
  import numpy as np
  context = rng.randint(PAGE, deepest, size=rows)
  lens = np.ones(rows, np.int64)
  if step.startswith("chunk@"):
    lens[rows // 2] = budget
    context[rows // 2] = int(step[6:-1]) * 1024 + 293
  else:
    assert step == "decode", step
  assert (context + lens).max() <= table_pages * PAGE, (step, table_pages)
  return lens, context


def AttendMain(args) -> int:
  import jax
  import jax.numpy as jnp
  import numpy as np
  from lingvo_tpu.core import compile_cache
  from lingvo_tpu.core import ragged as ragged_lib
  from lingvo_tpu.ops import ragged_block_attend

  compile_cache.Configure()
  on_tpu = jax.default_backend() == "tpu"
  assert on_tpu or args.tiny, "a time comes from the chip; --tiny rehearses"
  parent = (_ParentModule(args.parent, "ragged_block_attend")
            if args.parent else None)
  names = args.variants.split(",") + (["parent"] if parent else [])
  kw = dict(page_size=PAGE, lowering="pallas", interpret=not on_tpu)
  device = jax.devices()[0]
  lines = []
  for shape in args.shapes.split(","):
    n, n_kv, windows, table_pages, deepest = ATTEND_SHAPES[shape]
    head_dim, tile, rows_n = ATTEND_HEADS.get(shape, (HEAD_DIM, 1, ROWS))
    rows_n, budget = (4, 128) if args.tiny else (rows_n, ATTEND_BUDGET)
    t = rows_n + budget
    if args.tiny:
      table_pages, deepest = 8, 600
    steps = []
    for step in args.steps.split(","):
      steps += [step] if step == "decode" else [
          "chunk@" + at for at in (["0k"] if args.tiny
                                   else args.chunk_at.split(","))
          if int(at[:-1]) * 1024 + 293 + budget <= table_pages * PAGE]
    for window in windows:
      decode_us = {}
      for step in steps:
        rng = np.random.RandomState(args.seed)
        lens, context = AttendRows(step, rng, rows_n, budget, deepest,
                                   table_pages)
        rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in
                                       ragged_lib.BuildRaggedRows(
                                           lens, context, t, budget + 1)))
        tok = ragged_lib.BuildTokenView(rows, rows_n, table_pages, PAGE)
        tokens = (tok.row, tok.q_end)
        tree = dict(q_start=tok.q_start, anc_lo=rows.anc_lo,
                    anc_hi=rows.anc_hi)
        held = -(-(context + lens) // PAGE)                  # pages a row
        pool_pages = int(held.sum()) + 1
        tables = np.zeros((rows_n, table_pages), np.int32)
        perm = rng.permutation(pool_pages - 1)
        for r, (a, b) in enumerate(zip(np.cumsum(held) - held,
                                       np.cumsum(held))):
          tables[r, :b - a] = perm[a:b]
        tables = jnp.asarray(tables)
        key = jax.random.PRNGKey(args.seed)
        kq, kk, kv = jax.random.split(key, 3)
        q = (jax.random.normal(kq, (t, n, head_dim), jnp.float32)
             * head_dim ** -0.5).astype(jnp.bfloat16)
        kp, vp = (jax.random.normal(
            k, (pool_pages, PAGE, n_kv // tile, head_dim * tile),
            jnp.bfloat16) for k in (kk, kv))
        first = None
        for name in names:
          rba = parent if name == "parent" else ragged_block_attend
          fn = ATTEND_VARIANTS.get(name, _AttendAsBuilt)(rba)
          # the constant is read where a key is made, at every trace below
          built_span = getattr(rba, "_GROUPED_SPAN", 1)
          if _ForcedSpan(name) is not None:
            rba._GROUPED_SPAN = _ForcedSpan(name)
          plan_key = rba.AttendPlanKey(n, n_kv, head_dim, PAGE, q.dtype,
                                       kp.dtype, window=window,
                                       lowering="pallas")
          plan = {plan_key: jax.jit(lambda tokens, tree, rba=rba, k=plan_key:
                                    rba.BuildAttendPlan(
                                        k, *tokens, tree["q_start"],
                                        tree["anc_lo"], tree["anc_hi"],
                                        b=rows_n, t_pages=table_pages))(
                                            tokens, tree)}
          # every array an argument: a closed-over pool is a constant of the
          # program, gigabytes of it
          call = lambda q, kp, vp, tables, tokens, tree, plan, fn=fn: fn(
              q, kp, vp, tables, tokens, plan, window=window, **tree, **kw)
          rest = (kp, vp, tables, tokens, tree, plan)
          out = jax.block_until_ready(jax.jit(call)(q, *rest))
          same = None if first is None else bool(jnp.array_equal(out, first))
          if first is None:
            first = out

          def _Run(carry, q, *rest, call=call):
            def _Trip(i, carry):
              # behind a barrier with the trip's index and the last trip's
              # output: a trip's call is the trip's, not the loop's
              _, held_q, _ = jax.lax.optimization_barrier((i, q, carry))
              return call(held_q, *rest)
            return jax.lax.fori_loop(0, args.calls, _Trip, carry)

          loop = jax.jit(_Run)
          jax.block_until_ready(loop(out, q, *rest))             # compiles
          start = time.perf_counter()
          jax.block_until_ready(loop(out, q, *rest))
          ms = (time.perf_counter() - start) * 1e3 / args.calls
          del out
          one = lens == 1
          decode_pairs = rba.LivePairs(plan_key, context[one], lens[one],
                                       table_pages)
          chunk_pairs = rba.LivePairs(plan_key, context[~one], lens[~one],
                                      table_pages)
          line = {
              "case": "grouped_attend", "shape": shape, "window": window,
              "step": step, "variant": name, "ms_a_call": ms,
              "bitwise_the_first": same, "decode_pairs": decode_pairs,
              "chunk_pairs": chunk_pairs,
              "programs": getattr(rba, "Programs", rba.LivePairs)(
                  plan_key, context, lens, table_pages),
              "clear_pairs": 0 if name == "masked" else (
                  ragged_block_attend.ClearPairs(plan_key, context, lens,
                                                 table_pages)),
              "heads": n, "kv_heads": n_kv, "head_dim": head_dim,
              "heads_a_pool_row": tile, "rows": rows_n,
              "pool_pages": pool_pages,
              "calls": args.calls, "seed": args.seed, "tiny": args.tiny,
              "device": {"platform": device.platform,
                         "kind": device.device_kind}}
          if step == "decode":
            decode_us[name] = ms * 1e3 / max(decode_pairs, 1)
            line["us_a_decode_pair"] = decode_us[name]
          elif name in decode_us:
            line["us_a_chunk_pair"] = (
                ms * 1e3 - decode_us[name] * decode_pairs) / chunk_pairs
          rba._GROUPED_SPAN = built_span
          lines.append(line)
          print(json.dumps(line), flush=True)
          _Append(args.out, [line])     # a line at a time: a long case
        del first, kp, vp
  return 0 if all(l["bitwise_the_first"] is not False for l in lines) else 1


# -- the packed convolution ----------------------------------------------------

# channels, taps, slots, prefill budget, what follows the sum in the scope
# (benchmarks/configs/<cell>.json): `ssd` is Mamba2Layer's bias, silu and split
# into u [E], B and C; `silu` Mamba1Layer's bias and silu, cast for `w_x`;
# `gate` ShortConvLayer's product with C, cast for `w_out`
CONV_SHAPES = {"granite": (8448, 4, 64, 1024, ("ssd", 8192)),
               "nemotron": (6144, 4, 64, 1024, ("ssd", 4096)),
               "lfm2": (2048, 3, 256, 1024, ("gate",)),
               "phi4flash": (5120, 4, 64, 512, ("silu",))}
CONV_LAYERS = 4   # a trip reads one of as many layers' inputs, by its index


def _ConvLoop(ssm, u32, held_tail, conv_w, rows):
  """The sum as it stood before PR 66: the reference. A loop over the
  earlier taps, each a pad of the operand, a gather of a [T, C] array from
  the slots' tails, and a select between the two."""
  import jax.numpy as jnp
  k, t = conv_w.shape[0], u32.shape[0]
  row = jnp.clip(rows.row_of.astype(jnp.int32), 0, held_tail.shape[0] - 1)
  col = rows.col_of.astype(jnp.int32)
  tail = ssm._FreshTail(held_tail, rows)
  w = conv_w.astype(jnp.float32)
  conv = w[k - 1] * u32
  for back in range(1, k):
    here = jnp.pad(u32, ((back, 0), (0, 0)))[:t]
    held = tail[row, jnp.clip(k - 1 - back + col, 0, k - 2)]
    conv += w[k - 1 - back] * jnp.where((col >= back)[:, None], here, held)
  return conv


def _ConvParts(ssm, u32, held_tail, conv_w, rows):
  """-> (the step's own tokens' sum [T, C]; the tails' share [B, n, C]; the
  share's places on the packed axis [B, n], T where a row has no such token),
  what every form below lays together."""
  import jax.numpy as jnp
  import numpy as np
  k, t = conv_w.shape[0], u32.shape[0]
  col = rows.col_of.astype(jnp.int32)[:, None]
  w = conv_w.astype(jnp.float32)
  conv = w[k - 1] * u32
  for back in range(1, k):
    here = jnp.pad(u32, ((back, 0), (0, 0)))[:t]
    conv += jnp.where(col >= back, w[k - 1 - back] * here, 0.0)
  n = min(k - 1, rows.row_cols.shape[1])
  tail = ssm._FreshTail(held_tail, rows)
  share = jnp.stack([sum(w[i - j] * tail[:, i] for i in range(j, k - 1))
                     for j in range(n)], axis=1)
  reads = np.arange(n)[None] < rows.row_len.astype(jnp.int32)[:, None]
  at = jnp.where(reads, rows.row_cols[:, :n].astype(jnp.int32), t)
  return conv, share, at


def _ConvScatterAdd(ssm, *operands):
  """The share added at its places in the own tokens' sum."""
  conv, share, at = _ConvParts(ssm, *operands)
  return conv.at[at].add(share, mode="drop")


def _ConvGatherShare(ssm, u32, held_tail, conv_w, rows):
  """ONE gather of T rows from the share (a token's row and column), selected
  where the token reads a tail: what is cheap where T <= B (K - 1)."""
  import jax.numpy as jnp
  conv, share, _ = _ConvParts(ssm, u32, held_tail, conv_w, rows)
  n = share.shape[1]
  row = jnp.clip(rows.row_of.astype(jnp.int32), 0, share.shape[0] - 1)
  col = rows.col_of.astype(jnp.int32)
  return conv + jnp.where((col < n)[:, None],
                          share[row, jnp.clip(col, 0, n - 1)], 0.0)


CONV_VARIANTS = {"loop": _ConvLoop,
                 "tree": lambda ssm, *operands: ssm._PackedConv(*operands),
                 "scatter_add": _ConvScatterAdd,
                 "gather_share": _ConvGatherShare}


def ConvInputs(shape: str, step: str, seed: int, tiny: bool):
  """(u bf16 [L, T, C], tails f32 [B, K - 1, C], w [K, C], bias [C], gate
  bf16 [T, C], rows), what follows the sum, and the rows' lengths, of
  CONV_LAYERS layers' scopes in a `step` step at `shape`, seeded. `decode`: every slot a one-token row, the
  pack cut to the B columns `ragged.OverLiveRows` runs such a step over
  (every token reads a tail); `chunk`: B - 1 such rows beside one chunk of
  the whole budget that continues a prompt, on the whole pack, one slot of
  them starting a request."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  from lingvo_tpu.core import ragged as ragged_lib
  c, k, slots, budget, after = CONV_SHAPES[shape]
  if tiny:
    after = ("ssd", 192) if after[0] == "ssd" else after
    c, slots, budget = 256, 4, 24
  rng = np.random.RandomState(seed)
  lens = np.ones(slots, np.int64)
  context = rng.randint(1, 4096, size=slots)
  context[0] = 0
  t = slots + budget
  if step == "chunk":
    lens[slots // 2], context[slots // 2] = budget, 2 * budget
  else:
    assert step == "decode", step
  built = ragged_lib.BuildRaggedRows(lens, context, t, budget)
  if step == "decode":
    t = ragged_lib.DecodeWidth(t, budget)
    assert t == slots
    built = built._replace(row_of=built.row_of[:t], col_of=built.col_of[:t])
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in built))
  keys = jax.random.split(jax.random.PRNGKey(seed), 5)
  u = jax.random.normal(keys[0], (CONV_LAYERS, t, c), jnp.bfloat16)
  tails = jax.random.normal(keys[1], (slots, k - 1, c), jnp.float32)
  w = jax.random.normal(keys[2], (k, c), jnp.bfloat16)
  bias = jax.random.normal(keys[3], (c,), jnp.bfloat16)
  gate = jax.random.normal(keys[4], (t, c), jnp.bfloat16)
  return (u, tails, w, bias, gate, rows), after, lens


def ConvScope(ssm, conv_fn, after):
  """-> scope(u bf16 [T, C], tails, w, bias, gate, rows) -> (what the scope
  hands on, the new tails): one layer's convolution scope, the sum through
  `conv_fn`."""
  import jax
  import jax.numpy as jnp

  def _Scope(u, tails, w, bias, gate, rows):
    with jax.named_scope("packed_conv"):
      conv = conv_fn(ssm, u.astype(jnp.float32), tails, w, rows)
      if after[0] == "ssd":
        e = after[1]
        gn = (conv.shape[1] - e) // 2
        xbc = jax.nn.silu(conv + bias.astype(jnp.float32))
        out = (xbc[:, :e].reshape(-1, e // 64, 64), xbc[:, e:e + gn],
               xbc[:, e + gn:])
      elif after[0] == "silu":
        out = (jax.nn.silu(conv + bias.astype(jnp.float32))
               .astype(jnp.bfloat16),)
      else:
        out = ((gate.astype(jnp.float32) * conv).astype(jnp.bfloat16),)
      new = ssm._PackedConvTail(u, ssm._FreshTail(tails, rows), rows)
    return out, new

  return _Scope


def ConvMain(args) -> int:
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.core import compile_cache
  from lingvo_tpu.core import ssm

  compile_cache.Configure()
  assert jax.default_backend() == "tpu" or args.tiny, (
      "a time comes from the chip; --tiny rehearses")
  modules = ({"parent": _ParentModule(args.parent, "ssm", "core")}
             if args.parent else {})
  names = args.variants.split(",") + list(modules)
  device = jax.devices()[0]
  lines = []
  for shape in args.shapes.split(","):
    for step in args.steps.split(","):
      (u, tails, w, bias, gate, rows), after, lens = ConvInputs(
          shape, step, args.seed, args.tiny)
      k = w.shape[0]
      first = None
      for name in names:
        module = modules.get(name, ssm)
        conv_fn = CONV_VARIANTS["tree" if name == "parent" else name]
        scope = ConvScope(module, conv_fn, after)
        conv = jax.jit(lambda u, *rest, fn=conv_fn, m=module: fn(
            m, u.astype(jnp.float32), *rest))(u[0], tails, w, rows)
        _, new = jax.jit(scope)(u[0], tails, w, bias, gate, rows)
        live = (jnp.arange(conv.shape[0]) < int(lens.sum()))[:, None]
        conv = jnp.where(live, conv, 0.0)
        if first is None:
          first, err, same = (conv, new), None, None
        else:
          err = float(jnp.max(jnp.abs(conv - first[0]))
                      / jnp.max(jnp.abs(first[0])))
          same = bool(jnp.array_equal(new, first[1]))
        gathers = _TokenRowGathers(jax.make_jaxpr(
            lambda *ops, fn=conv_fn, m=module: fn(m, *ops))(
                u[0].astype(jnp.float32), tails, w, rows).jaxpr,
                                   conv.shape[0])

        def _Run(carry, u, w, bias, gate, rows, scope=scope):
          def _Trip(i, carry):
            # the trip's layer by its index, the tails the trip's before
            # left: nothing is the loop's to hoist
            return scope(jax.lax.dynamic_index_in_dim(
                u, i % CONV_LAYERS, keepdims=False), carry[1], w, bias, gate,
                         rows)
          return jax.lax.fori_loop(0, args.calls, _Trip, carry)

        loop = jax.jit(_Run, donate_argnums=0)
        carry = jax.jit(scope)(u[0], tails, w, bias, gate, rows)
        carry = jax.block_until_ready(
            loop(carry, u, w, bias, gate, rows))                # compiles
        start = time.perf_counter()
        jax.block_until_ready(loop(carry, u, w, bias, gate, rows))
        ms = ((time.perf_counter() - start) * 1e3 / args.calls
              if device.platform == "tpu" else None)    # a time is the chip's
        del carry
        t, c = conv.shape
        out_bytes = 4 if after[0] == "ssd" else 2
        lines.append({
            "case": "packed_conv", "shape": shape, "step": step,
            "variant": name, "ms_a_layer": ms,
            "once_mb": t * c * (2 + out_bytes) / 1e6,
            "once_ms_at_819gb_s": t * c * (2 + out_bytes) / 819e6,
            "rel_err_to_first": err,
            "within_1e-5": None if err is None else err <= 1e-5,
            "tail_equal_first": same, "token_row_gathers": gathers,
            "tokens": int(lens.sum()) if step == "chunk" else t,
            "conv_tail_tokens": int(sum(min(int(n), k - 1) for n in lens)),
            "t": t, "c": c, "k": k, "slots": int(tails.shape[0]),
            "calls": args.calls, "seed": args.seed, "tiny": args.tiny,
            "device": {"platform": device.platform,
                       "kind": device.device_kind}})
        print(json.dumps(lines[-1]), flush=True)
      del first
  _Append(args.out, lines)
  return 0 if all(l["within_1e-5"] is not False
                  and l["tail_equal_first"] is not False for l in lines) else 1


def _TokenRowGathers(jaxpr, t: int) -> int:
  """Gathers in `jaxpr` (and in what it calls) whose result has `t` rows: the
  arrays of T rows built from the slots' tails."""
  import jax
  count = 0
  for eqn in jaxpr.eqns:
    shape = eqn.outvars[0].aval.shape
    count += (eqn.primitive.name == "gather" and len(shape) > 1
              and shape[0] == t)
    count += sum(_TokenRowGathers(sub, t)
                 for sub in jax.core.jaxprs_in_params(eqn.params))
  return count


def _Append(path, lines):
  if path:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "a") as f:
      for line in lines:
        f.write(json.dumps(line) + "\n")


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--case", default="page_write",
                  choices=["page_write", "row_pass", "moe_combine",
                           "grouped_attend", "packed_conv"])
  ap.add_argument("--steps", default="decode,chunk")
  ap.add_argument("--variants", default="")
  ap.add_argument("--shapes", default="")
  ap.add_argument("--chunk_at", default="3k,12k,30k")
  ap.add_argument("--tiny", action="store_true")
  ap.add_argument("--parent", default="")
  ap.add_argument("--calls", type=int, default=50)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--pool_pages", type=int, default=6561)
  ap.add_argument("--out", default="")
  args = ap.parse_args(argv)
  if args.case == "grouped_attend":
    args.shapes = args.shapes or ",".join(ATTEND_SHAPES)
    args.variants = args.variants or ",".join(ATTEND_VARIANTS)
    return AttendMain(args)
  if args.case == "packed_conv":
    args.shapes = args.shapes or ",".join(CONV_SHAPES)
    args.variants = args.variants or "loop,tree"
    return ConvMain(args)
  if args.case == "moe_combine":
    args.shapes = args.shapes or ",".join(COMBINE_SHAPES)
    args.variants = args.variants or ",".join(COMBINE_VARIANTS)
    return CombineMain(args)
  if args.case == "row_pass":
    args.shapes = args.shapes or "granite,nemotron"
    args.variants = args.variants or "none,hand_over,narrow,all"
    return RowPassMain(args)
  args.variants = args.variants or "scatter,kernel"

  import jax
  import jax.numpy as jnp
  import numpy as np
  from lingvo_tpu.core import compile_cache
  from lingvo_tpu.core import ragged as ragged_lib
  from lingvo_tpu.ops import diff_attend
  from lingvo_tpu.ops import run_write

  compile_cache.Configure()
  modules = {"parent": _ParentModule(args.parent)} if args.parent else {}
  names = args.variants.split(",") + list(modules)
  t, wmax = ROWS + BUDGET, BUDGET + 1
  pool_shape = (args.pool_pages, PAGE, KV_HEADS, HEAD)
  assert args.pool_pages > ROWS * TABLE_PAGES, "a page a table entry, and trash"

  @jax.jit
  def _Pools(key):
    return tuple(jax.random.normal(k, pool_shape, jnp.bfloat16)
                 for k in jax.random.split(key))

  @jax.jit
  def _Same(got, want):
    return jnp.stack([jnp.array_equal(a[:-1], b[:-1])
                      for a, b in zip(got, want)])

  def _Loop(fn):
    def _Run(k, v, k_new, v_new, tables, rows, plan):
      def _Trip(i, pools):
        # behind a barrier with the trip's index: a trip's preparation is
        # the trip's, not the loop's
        _, kn, vn = jax.lax.optimization_barrier((i, k_new, v_new))
        return tuple(fn(*pools, kn, vn, tables, rows, plan))
      return jax.lax.fori_loop(0, args.calls, _Trip, (k, v))
    return jax.jit(_Run, donate_argnums=(0, 1))

  device = jax.devices()[0]
  lines = []
  for step in args.steps.split(","):
    rng = np.random.RandomState(args.seed)
    lens, context = StepRows(step, rng, t, wmax)
    rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in
                                   ragged_lib.BuildRaggedRows(
                                       lens, context, t, wmax)))
    tables = jnp.asarray(rng.permutation(args.pool_pages - 1)[
        :ROWS * TABLE_PAGES].reshape(ROWS, TABLE_PAGES), jnp.int32)
    key = jax.random.PRNGKey(args.seed)
    k_new, v_new = (jax.random.normal(k, (t, KV_HEADS, HEAD), jnp.bfloat16)
                    for k in jax.random.split(jax.random.fold_in(key, 1)))
    first = None
    for name in names:
      module = modules.get(name, diff_attend)
      fn = VARIANTS.get(name, VARIANTS["kernel"])(module)
      plan = jax.jit(lambda rows, m=module: m.BuildWritePlan(
          rows, ROWS, TABLE_PAGES, PAGE))(rows)
      once = jax.jit(fn, donate_argnums=(0, 1))
      pools = jax.block_until_ready(
          once(*_Pools(key), k_new, v_new, tables, rows, plan))
      same = (None if first is None
              else bool(np.all(np.asarray(_Same(pools, first)))))
      loop = _Loop(fn)
      pools = jax.block_until_ready(
          loop(*pools, k_new, v_new, tables, rows, plan))       # compiles
      start = time.perf_counter()
      pools = jax.block_until_ready(
          loop(*pools, k_new, v_new, tables, rows, plan))
      ms = (time.perf_counter() - start) * 1e3 / args.calls
      if first is None:
        first = pools     # the same tokens written again: the same pools
      del pools
      lines.append({
          "step": step, "variant": name, "ms_a_layer": ms,
          "bitwise_the_first": same,
          "live_pairs": run_write.RunCounts(context, lens, PAGE)[0],
          "bound": diff_attend.PageWrites(ROWS, t, PAGE),
          "tokens": int(lens.sum()), "calls": args.calls,
          "pool_pages": args.pool_pages, "seed": args.seed,
          "device": {"platform": device.platform, "kind": device.device_kind}})
      print(json.dumps(lines[-1]), flush=True)
    del first
  _Append(args.out, lines)
  return 0 if all(l["bitwise_the_first"] is not False for l in lines) else 1


if __name__ == "__main__":
  sys.exit(main())
