#!/usr/bin/env python3
"""One layer's whole-page write (ops/diff_attend.WritePages) alone, at the
shapes `phi4flash_serve_reason` runs it at: a pool `[6561, 128, 20, 64]` bf16
for K and one for V, a pack of 576 tokens over 64 rows with 64 pages each.

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


def _ParentModule(root):
  """DIR/lingvo_tpu/ops/diff_attend.py under a name of its own; what it
  imports is this tree's."""
  path = os.path.join(root, "lingvo_tpu", "ops", "diff_attend.py")
  spec = importlib.util.spec_from_file_location("parent_diff_attend", path)
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


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--steps", default="decode,chunk")
  ap.add_argument("--variants", default="scatter,kernel")
  ap.add_argument("--parent", default="")
  ap.add_argument("--calls", type=int, default=50)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--pool_pages", type=int, default=6561)
  ap.add_argument("--out", default="")
  args = ap.parse_args(argv)

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
  if args.out:
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
      for line in lines:
        f.write(json.dumps(line) + "\n")
  return 0 if all(l["bitwise_the_first"] is not False for l in lines) else 1


if __name__ == "__main__":
  sys.exit(main())
