#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that lingvo_tpu still starts on a TPU chip.

Drives the two main paths once through the entry points a user calls, at the
registered widths of `lm.synthetic_packed_input.DenseLm1B` (d=2048, 16 heads x
128, ff 8192, vocab 32000, seq 1024, batch 8, bf16, weights from
`PRNGKey(--seed)`), and checks what comes out by the repo's own means:

  device   what JAX sees, library versions, the compile-cache directory
  kernels  every Pallas kernel of the two paths, compiled (never interpret
           mode), against its XLA twin on the same chip; the grouped ragged
           kernel too, which DenseLm1B's plain heads do not reach: 28 query
           heads over 4 KV heads of 128 on bf16 pages, a decode row, a short
           row and a chunk in one pack, without and with a 4096-token window;
           and a kernel of each family handed the step's plan (its query-block
           descriptors, the page write's pairs) against the same kernel
           building its own
  train    model_registry -> TrainProgram -> ExecutorTpu.Start(): two loops of
           tpu_steps_per_loop with flash attention and 'dots' remat, an async
           checkpoint (SaveAsync) and the final one, both restored
  serve    ServingLoop.Start()/Submit()/Stop() at full depth: 8 prompts of
           64-600 tokens, 32 new tokens each, first tokens checked against a
           plain unpaged forward of the same task and theta

  hybrid   the tiny sibling of `lm.nemotron_h` (Mamba-2 mixers with slot
           state, sigmoid-routed experts beside a shared one, one
           grouped-query attention layer; one branch a layer) through
           ServingLoop in bf16 at the smallest widths the chip's kernels
           take: one prompt over two chunk boundaries, a few decode steps,
           every streamed token against the plain reference's
           (benchmarks/references/nemotron_h.py) f32 forward of the same
           weights: ten seconds that say the packed Mamba-2 scan still
           compiles and computes on the chip

`--multichip` runs, and only runs, the GSPMD trainer on a {"data": 2,
"model": 2} mesh against the same two steps on one device. `--tiny` runs the
same phases at DenseLmTiny size with the kernels in interpret mode: the CPU
rehearsal, never a chip run.

One JSON object per phase on its own line. The last line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`; a
phase that fails prints its error and the last line says `"ok": false` with a
non-zero exit code. Without `--tiny`, no TPU means no phase runs at all. One
process, no children: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import itertools
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from typing import Any, Callable, NamedTuple

MODEL = "lm.synthetic_packed_input.DenseLm1B"
TINY_MODEL = "lm.synthetic_packed_input.DenseLmTiny"


@dataclasses.dataclass(frozen=True)
class Size:
  """Everything that differs between the chip run and the CPU rehearsal."""
  model: str
  # Train depth, the only cut. Of the 16.91 GB the chip reports, the phase
  # peaked at 10.08, 11.45 and 12.86 GB at 6, 7 and 8 layers (my chip runs,
  # CHANGES.md PR 21): 0.47 GB of train state and 0.8 GB of temporaries a
  # layer. SaveAsync also holds a second copy of the state on the device
  # until orbax has pulled it to the host. In those runs it was gone before
  # the first step's temporaries came, but only because the step is traced a
  # second time in between, so the depth must fit with the copy: 15.3 GB at
  # 7 layers, 17.2 GB at 8. None = the registered depth.
  train_layers: int | None
  steps_per_loop: int | None    # None = the model's tpu_steps_per_loop
  interpret: bool               # Pallas kernels in interpret mode (CPU only)
  # serving engine geometry
  page_size: int
  num_pages: int
  max_batch: int
  max_seq_len: int
  prompt_lens: tuple[int, int]
  new_tokens: int
  # kernel widths: heads, head dim, model dim, vocab, xent block, batch, seq,
  # pool pages, block-table width, dense-cache length, SSD heads/state/chunk
  n: int
  h: int
  d: int
  vocab: int
  xent_block: int
  b: int
  t: int
  pool_pages: int
  table_pages: int
  cache_len: int
  ssd_heads: int
  ssd_state: int
  ssd_chunk: int
  # the ragged pack: block-table rows, packed width, the prefill chunk's
  # tokens (REAL: the serving step of DenseLm1B, 32 slots + a 512 budget)
  ragged_rows: int
  ragged_t: int
  ragged_chunk: int
  # the grouped ragged kernel: (query heads, KV heads) of `grouped_h` (whole
  # lanes, whatever `h` is), a row's table and the window of the windowed
  # case (REAL: SmallThinker's 28 over 4 of 128 and its 4096-token layers)
  grouped_heads: tuple[int, int]
  grouped_h: int
  grouped_table_pages: int
  grouped_window: int
  # differential attention over pages: (query heads, K heads) of `diff_h`
  # and the window of the windowed case (REAL: Phi-4-mini-flash's 40 over 20
  # of 64 and its 512-token layers); it packs as the grouped case does
  diff_heads: tuple[int, int]
  diff_h: int
  diff_window: int
  # the selective scan on the packed axis: channels, state indices, one-token
  # rows and one chunk after them (REAL: Phi-4-mini-flash's 5120 x 16 under
  # 64 slots + a 512 budget)
  scan_channels: int
  scan_state: int
  scan_rows: int
  scan_chunk: int
  # power retention on the packed axis: (query heads, KV heads) of `ret_h`,
  # one-token rows and one chunk after them that crosses a page boundary
  # (REAL: Brumby-14B's 40 over 8 of 128, pages of 128; a few rows only: the
  # XLA twin gathers a state of 34 MB a token)
  ret_heads: tuple[int, int]
  ret_h: int
  ret_rows: int
  ret_chunk: int


REAL = Size(
    model=MODEL, train_layers=7, steps_per_loop=None, interpret=False,
    page_size=128, num_pages=128, max_batch=8, max_seq_len=2048,
    prompt_lens=(64, 600), new_tokens=32,
    n=16, h=128, d=2048, vocab=32000, xent_block=1024, b=8, t=1024,
    pool_pages=512, table_pages=16, cache_len=2048,
    ssd_heads=8, ssd_state=128, ssd_chunk=64,
    ragged_rows=32, ragged_t=544, ragged_chunk=300,
    grouped_heads=(28, 4), grouped_h=128, grouped_table_pages=48,
    grouped_window=4096,
    diff_heads=(40, 20), diff_h=64, diff_window=512,
    scan_channels=5120, scan_state=16, scan_rows=64, scan_chunk=512,
    ret_heads=(40, 8), ret_h=128, ret_rows=3, ret_chunk=140)

TINY = Size(
    model=TINY_MODEL, train_layers=None, steps_per_loop=2, interpret=True,
    page_size=8, num_pages=32, max_batch=4, max_seq_len=64,
    prompt_lens=(4, 24), new_tokens=4,
    n=2, h=16, d=32, vocab=96, xent_block=32, b=2, t=32,
    pool_pages=16, table_pages=4, cache_len=32,
    ssd_heads=2, ssd_state=8, ssd_chunk=8,
    ragged_rows=4, ragged_t=24, ragged_chunk=14,
    grouped_heads=(14, 2), grouped_h=128, grouped_table_pages=8,
    grouped_window=20,
    diff_heads=(8, 4), diff_h=8, diff_window=20,
    scan_channels=128, scan_state=8, scan_rows=4, scan_chunk=14,
    ret_heads=(4, 2), ret_h=16, ret_rows=3, ret_chunk=14)


# -- kernels -----------------------------------------------------------------


class KernelCase(NamedTuple):
  """One Pallas kernel variant and its XLA twin.

  inputs: the entry of `KernelInputs` it runs on; fn(pallas) -> callable over
  that tuple, the Pallas lowering when `pallas` else the twin (a `_plan`
  case: the kernel both times, handed the step's plan when `pallas`).
  `tests/test_chip_compile.py` compiles fn(True) of every case for a
  described v5e from the shapes of its inputs (jax.eval_shape), so this table
  is the single list of what must lower.
  """
  name: str
  inputs: str
  fn: Callable[[bool], Callable]


# The error allowed per output leaf, as a share of that leaf's largest
# reference magnitude. bf16 outputs differ from the twin by a rounding or two
# of 2^-8. An f32 dot runs on the MXU as bf16 passes in Mosaic and in XLA's
# default precision alike, so the f32 kernels agree with their twins far more
# closely than either does with exact f32 (what the chip run measured is in
# CHANGES.md, PR 21).
REL_TOL = 2e-2


def KernelInputs(size: Size, key) -> dict[str, tuple]:
  """Every kernel case's operands from one PRNG key (jit this: one program)."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  from lingvo_tpu.core import ragged as ragged_lib
  from lingvo_tpu.quant import kv as kv_quant

  s = size
  bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
  page = s.page_size
  # the first 32 as they always were; the cases added since draw from more
  keys = itertools.chain(jax.random.split(key, 32),
                         jax.random.split(jax.random.fold_in(key, 1), 32))

  def _Normal(shape, dtype=bf16, scale=1.0):
    return (scale * jax.random.normal(next(keys), shape, f32)).astype(dtype)

  # the paged kernels take their query pre-scaled, as the attention layer
  # hands it over; unscaled, logits of +-40 make the softmax one-hot and the
  # comparison a test of rounding luck
  q_scale = 1.0 / math.sqrt(s.h)

  def _Tables(rows):
    # disjoint random pages per row, as the allocator hands them out
    perm = jax.random.permutation(next(keys), s.pool_pages)
    return perm[:rows * s.table_pages].reshape(rows, -1).astype(i32)

  # flash attention: a cotangent, q, k, v and four packed segments per row,
  # the tail of the last one padding (segment 0)
  shape = (s.b, s.t, s.n, s.h)
  pos = jnp.arange(s.t, dtype=i32)
  seg = jnp.where(pos >= s.t - s.t // 16, 0, 1 + pos // (s.t // 4))
  flash = (_Normal(shape, f32), _Normal(shape), _Normal(shape),
           _Normal(shape), jnp.broadcast_to(seg[None], (s.b, s.t)))

  # page pools, and their int8 form as the engine's quantize-on-write leaves
  # it: sidecars [pages, N, page]
  pool_shape = (s.pool_pages, page, s.n, s.h)
  k_pool, v_pool = _Normal(pool_shape), _Normal(pool_shape)
  k8, ks = kv_quant.QuantizeKv(k_pool)
  v8, vs = kv_quant.QuantizeKv(v_pool)
  pools = {False: (k_pool, v_pool, None, None),
           True: (k8, v8, jnp.swapaxes(ks, 1, 2), jnp.swapaxes(vs, 1, 2))}

  # block decode: one query per row; a full row, an idle row, random others
  cap = s.table_pages * page
  lens = jax.random.randint(next(keys), (s.b,), 1, cap + 1, i32)
  lens = lens.at[0].set(cap).at[-1].set(0)
  decode = (_Normal((s.b, 1, s.n, s.h), scale=q_scale), _Tables(s.b), lens)

  # ragged: decode rows, one prefill chunk that spans several query blocks
  # with a ragged last one, a one-token row right after it (so it starts
  # mid-block), a speculating row of five tokens, then padding (q_end 0):
  # each row's tokens contiguous on the one packed axis, as the engine
  # packs them
  widths = (1,) * (s.ragged_rows - 3) + (s.ragged_chunk, 1, 5)
  starts = jnp.minimum(
      jax.random.randint(next(keys), (s.ragged_rows,), page, cap - 8, i32),
      cap - jnp.asarray(widths, i32))
  row_of, col = [], []
  for r, width in enumerate(widths):
    row_of += [r] * width
    col += list(range(width))
  live = len(row_of)
  t = s.ragged_t
  assert live < t, (live, t)
  row_of = jnp.asarray(row_of + [0] * (t - live), i32)
  q_start = jnp.zeros((t,), i32).at[:live].set(starts[row_of[:live]])
  q_end = jnp.zeros((t,), i32).at[:live].set(
      q_start[:live] + jnp.asarray(col, i32) + 1)
  ragged = (_Normal((t, s.n, s.h), scale=q_scale), _Tables(s.ragged_rows),
            row_of, q_end)
  # the last row as a 2 x 2 token tree: columns root, b0d0, b0d1, b1d0, b1d1;
  # bit c of a token's mask = step column c is an ancestor or the token
  masks = jnp.asarray((0b00001, 0b00011, 0b00111, 0b01001, 0b11001), i32)
  anc_lo = jnp.full((t,), -1, i32).at[live - 5:live].set(masks)
  anc_hi = jnp.full((t,), -1, i32).at[live - 5:live].set(0)
  chain, tree = (None, None, None), (q_start, anc_lo, anc_hi)

  # grouped ragged: a decode row, a short row and a chunk that spans query
  # blocks, then padding, on bf16 pages of the KV heads alone; every row's
  # context ends near the end of its table, past the windowed case's window
  g_n, g_kv = s.grouped_heads
  g_widths = (1, 5, s.ragged_chunk)
  g_cap = s.grouped_table_pages * page
  g_pool = (len(g_widths) * s.grouped_table_pages + 1, page, g_kv,
            s.grouped_h)
  g_row_of, g_end = [], []
  for r, width in enumerate(g_widths):
    g_row_of += [r] * width
    g_end += list(range(g_cap - width - 3 * r, g_cap - 3 * r))
  g_pad = [0] * (t - len(g_row_of))
  grouped = (
      _Normal((t, g_n, s.grouped_h), scale=1.0 / math.sqrt(s.grouped_h)),
      _Normal(g_pool), _Normal(g_pool),
      jax.random.permutation(next(keys), g_pool[0] - 1).reshape(
          len(g_widths), -1).astype(i32),
      jnp.asarray(g_row_of + g_pad, i32), jnp.asarray(g_end + g_pad, i32))

  # differential attention: the grouped case's pack and tables over pools of
  # the K heads, and the same tokens as rows to write (one row starts a page,
  # the chunk crosses several)
  d_n, d_kv = s.diff_heads
  d_pool = (g_pool[0], page, d_kv, s.diff_h)
  diff = (_Normal((t, d_n, s.diff_h), scale=1.0 / math.sqrt(s.diff_h)),
          _Normal(d_pool), _Normal(d_pool)) + grouped[3:]
  d_q_pos = [g_cap - width - 3 * r for r, width in enumerate(g_widths)]
  d_rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in (
      ragged_lib.BuildRaggedRows(np.asarray(g_widths), np.asarray(d_q_pos),
                                 t, max(g_widths)))))
  diff_write = diff[1:3] + (_Normal((t, d_kv, s.diff_h)),
                            _Normal((t, d_kv, s.diff_h)), grouped[3], d_rows)
  # the page write by runs: the same rows' tokens into the grouped case's
  # pools (a run of one token, one inside a page, a chunk over several)
  run_write = grouped[1:3] + (_Normal((t, g_kv, s.grouped_h)),
                              _Normal((t, g_kv, s.grouped_h)), grouped[3],
                              d_rows)

  # selective scan: one-token rows (two of them a request's first token),
  # then a chunk that carries on from its slot's state, then padding
  sc_widths = (1,) * s.scan_rows + (s.scan_chunk,)
  sc_t = -(-(sum(sc_widths) + 3) // 8) * 8
  sc_rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in (
      ragged_lib.BuildRaggedRows(
          np.asarray(sc_widths),
          np.asarray([0, 0] + [7 + 3 * r for r in range(s.scan_rows - 1)]),
          sc_t, s.scan_chunk))))
  e_, n_ = s.scan_channels, s.scan_state
  scan = (0.001 + 0.1 * jax.nn.sigmoid(_Normal((sc_t, e_), f32)),
          _Normal((sc_t, e_), f32), _Normal((sc_t, n_), f32),
          _Normal((sc_t, n_), f32),
          -jnp.broadcast_to(jnp.arange(1, n_ + 1, dtype=f32)[:, None],
                            (n_, e_)),
          _Normal((e_,), f32), _Normal((len(sc_widths), n_, e_), f32),
          sc_rows)

  # power retention: one-token rows deep in their open chunks (one of them a
  # request's first token), then a chunk that starts inside a page, carries
  # on from its slot's state and completes a page or two; slot states that
  # are not zero, gates near one
  r_n, r_kv = s.ret_heads
  r_h = s.ret_h
  r_widths = (1,) * s.ret_rows + (s.ret_chunk,)
  r_t = -(-(sum(r_widths) + 3) // 8) * 8
  r_q_pos = [0] + [page + 5 + 3 * r for r in range(s.ret_rows - 1)] + [
      2 * page + page // 2]
  r_rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in (
      ragged_lib.BuildRaggedRows(np.asarray(r_widths), np.asarray(r_q_pos),
                                 r_t, s.ret_chunk))))
  r_slots = len(r_widths)
  r_span = 3 + (s.ret_chunk + page // 2) // page
  r_pages = r_slots * r_span + 1
  r_pool = (r_pages, page, r_kv, r_h)
  from lingvo_tpu.ops import power_retention as retention_op
  r_d = retention_op.StoredDim(r_h)
  retention = (
      _Normal((r_t, r_n, r_h), f32, 1.0 / math.sqrt(r_h)),
      _Normal((r_t, r_kv, r_h), f32), _Normal((r_t, r_kv, r_h)),
      -0.01 * jax.nn.softplus(_Normal((r_t, r_kv), f32)),
      _Normal((r_slots, r_kv, r_h, r_d), f32),
      1.0 + jnp.abs(_Normal((r_slots, r_kv, r_d // r_h, r_h), f32)),
      _Normal(r_pool), _Normal(r_pool),
      jnp.cumsum(-0.01 * jax.nn.softplus(_Normal((r_pages, r_kv, page), f32)),
                 axis=-1),
      jnp.arange(r_slots * r_span, dtype=i32).reshape(r_slots, r_span),
      r_rows)

  # SSD scan: a cotangent, log-decay <= 0, write keys, read keys, values
  lead = (s.b, s.t, s.ssd_heads)
  ssd = (_Normal(lead + (s.h,), f32),
         -0.1 * jax.nn.softplus(_Normal(lead, f32)),
         _Normal(lead + (s.ssd_state,), f32) / math.sqrt(s.ssd_state),
         _Normal(lead + (s.ssd_state,), f32), _Normal(lead + (s.h,), f32))

  return {
      "flash": flash,
      "block_decode_bf16": decode[:1] + pools[False] + decode[1:],
      "block_decode_int8": decode[:1] + pools[True] + decode[1:],
      "ragged_plain": ragged[:1] + pools[False] + ragged[1:] + chain,
      "ragged_tree": ragged[:1] + pools[False] + ragged[1:] + tree,
      "ragged_int8": ragged[:1] + pools[True] + ragged[1:] + chain,
      "ragged_grouped": grouped,
      "diff_attend": diff,
      "diff_write": diff_write,
      "run_write": run_write,
      "selective_scan": scan,
      "retention": retention,
      "flash_decode": (
          _Normal((s.b, 1, s.n, s.h), scale=q_scale),
          _Normal((s.b, s.cache_len, s.n, s.h)),
          _Normal((s.b, s.cache_len, s.n, s.h)),
          jnp.asarray(s.cache_len // 2 + 3, i32)),
      # bf16 activations against the f32 master table: the widest weight
      # tile the kernel is handed, and the one the default VMEM limit refused
      "xent": (_Normal((s.b, s.t), f32), _Normal((s.b, s.t, s.d)),
               _Normal((s.vocab, s.d), f32) / math.sqrt(s.d),
               jax.random.randint(next(keys), (s.b, s.t), 0, s.vocab, i32)),
      "ssd": ssd,
  }


def KernelCases(size: Size) -> list[KernelCase]:
  """Every Pallas kernel of the train and serve paths at `size` widths."""
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.ops import block_decode
  from lingvo_tpu.ops import diff_attend
  from lingvo_tpu.ops import flash_attention
  from lingvo_tpu.ops import flash_decode
  from lingvo_tpu.ops import fused_xent
  from lingvo_tpu.ops import ragged_block_attend
  from lingvo_tpu.ops import run_write
  from lingvo_tpu.ops import selective_scan
  from lingvo_tpu.ops import ssd_scan

  s = size
  page = s.page_size

  def _Lowering(pallas, twin="xla"):
    # the ops take `lowering`; interpret mode only where the kernel runs
    return (dict(lowering="pallas", interpret=s.interpret) if pallas
            else dict(lowering=twin))

  def _Weighted(fn, argnums):
    """fn's scalar product with a fixed cotangent, and its gradients."""
    def _Run(w, *args):
      def _Loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32) * w)
      return jax.value_and_grad(_Loss, argnums=argnums)(*args)
    return _Run

  def _Flash(pallas, seg_ids):
    if pallas:
      return lambda q, k, v: flash_attention.FlashAttention(
          q, k, v, causal=True, segment_ids=seg_ids, interpret=s.interpret)
    return lambda q, k, v: flash_attention._XlaAttention(
        q, k, v, seg_ids, True)

  def _FlashFwd(pallas):
    return lambda w, q, k, v, seg: _Flash(pallas, None)(q, k, v)

  def _FlashFwdBwdSeg(pallas):
    return lambda w, q, k, v, seg: _Weighted(
        _Flash(pallas, seg), (0, 1, 2))(w, q, k, v)

  def _BlockDecode(pallas):
    return lambda q, k, v, ks, vs, tables, lens: block_decode.BlockDecode(
        q, k, v, tables, lens, page_size=page, k_scale=ks, v_scale=vs,
        **_Lowering(pallas))

  def _Ragged(pallas):
    def _Run(q, k, v, ks, vs, tables, row_of, q_end, q_start, lo, hi, **kw):
      return ragged_block_attend.RaggedAttend(
          q, k, v, tables, row_of, q_end, page_size=page, k_scale=ks,
          v_scale=vs, q_start=q_start, anc_lo=lo, anc_hi=hi,
          **_Lowering(pallas), **kw)
    return _Run

  def _RaggedNoRows(pallas):
    # a step that holds no row: the kernel's grid is its live (block, page)
    # pairs, here none, and the output is the zeros it starts from
    return lambda q, k, v, ks, vs, tables, row_of, q_end, *tree: (
        _Ragged(pallas)(q, k, v, ks, vs, tables, row_of,
                        jnp.zeros_like(q_end), *tree))

  def _RaggedGrouped(window):
    return lambda pallas: lambda q, k, v, tables, row_of, q_end, **kw: (
        ragged_block_attend.RaggedAttend(
            q, k, v, tables, row_of, q_end, page_size=page, window=window,
            **_Lowering(pallas), **kw))

  def _DiffAttend(window):
    return lambda pallas: lambda q, k, v, tables, row_of, q_end, **kw: (
        diff_attend.DiffAttend(
            q, k, v, tables, row_of, q_end, 0.35, page_size=page,
            window=window, **_Lowering(pallas), **kw))

  def _DiffWrite(pallas):
    # all pages but the last: only the scatter's padding writes the trash page
    return lambda k, v, k_new, v_new, tables, rows, **kw: tuple(
        pool[:-1] for pool in diff_attend.WritePages(
            k, v, k_new, v_new, tables, rows, **_Lowering(pallas), **kw))

  def _RunWrite(pallas):
    # all pages but the last: nothing writes the trash page
    def _Run(k, v, k_new, v_new, tables, rows):
      runs = run_write.BuildWriteRuns(rows, *tables.shape, page)
      return tuple(pool[:-1] for pool in run_write.WriteRuns(
          k, v, k_new, v_new, tables[runs.row, runs.logical], runs,
          **_Lowering(pallas)))
    return _Run

  # The step's plan (core/attention.BuildRaggedPlan) against the call that
  # builds its own: the kernel on BOTH sides, handed its descriptors
  # (`planned`) or not, which must agree to the bit.
  def _Blocks(key, tables, row_of, q_end, *tree):
    return {key: ragged_block_attend.BuildAttendPlan(
        key, row_of, q_end, *tree, b=tables.shape[0],
        t_pages=tables.shape[1])}

  def _RaggedPlan(planned):
    def _Run(q, k, v, ks, vs, tables, row_of, q_end, q_start, lo, hi):
      key = ragged_block_attend.AttendPlanKey(
          q.shape[1], k.shape[2], q.shape[2], page, q.dtype, k.dtype,
          lowering="pallas")
      return _Ragged(True)(
          q, k, v, ks, vs, tables, row_of, q_end, q_start, lo, hi,
          plan=_Blocks(key, tables, row_of, q_end, q_start, lo, hi)
          if planned else None)
    return _Run

  def _GroupedPlan(planned):
    def _Run(q, k, v, tables, row_of, q_end):
      key = ragged_block_attend.AttendPlanKey(
          q.shape[1], k.shape[2], q.shape[2], page, q.dtype, k.dtype,
          window=s.grouped_window, tree=False, lowering="pallas")
      return _RaggedGrouped(s.grouped_window)(True)(
          q, k, v, tables, row_of, q_end,
          plan=_Blocks(key, tables, row_of, q_end) if planned else None)
    return _Run

  def _DiffPlan(planned):
    def _Run(q, k, v, tables, row_of, q_end):
      key = diff_attend.DiffPlanKey(
          q.shape[1], k.shape[2], q.shape[2], page, q.dtype, k.dtype,
          window=s.diff_window, lowering="pallas")
      return _DiffAttend(s.diff_window)(True)(
          q, k, v, tables, row_of, q_end,
          plan=_Blocks(key, tables, row_of, q_end) if planned else None)
    return _Run

  def _DiffWritePlan(planned):
    return lambda k, v, k_new, v_new, tables, rows: _DiffWrite(True)(
        k, v, k_new, v_new, tables, rows,
        plan=diff_attend.BuildWritePlan(rows, *tables.shape, page)
        if planned else None)

  def _SelectiveScan(pallas):
    return lambda delta, x, b, c, a, d, state, rows: (
        selective_scan.SelectiveScan(delta, x, b, c, a, d, state, rows,
                                     **_Lowering(pallas)))

  def _FlashDecode(pallas):
    return lambda q, k, v, step: flash_decode.FlashDecode(
        q, k, v, step, page_size=page, **_Lowering(pallas))

  def _Xent(pallas):
    return lambda x, w, ids: fused_xent.FusedXent(
        x, w, ids, block_size=s.xent_block,
        **_Lowering(pallas)).per_example_xent

  def _XentFwd(pallas):
    return lambda c, x, w, ids: _Xent(pallas)(x, w, ids)

  def _XentFwdBwd(pallas):
    return lambda c, x, w, ids: _Weighted(
        lambda x_, w_: _Xent(pallas)(x_, w_, ids), (0, 1))(c, x, w)

  def _Ssd(pallas):
    return lambda dl, b, c, v: ssd_scan.SsdScan(
        dl, b, c, v, chunk_size=s.ssd_chunk, **_Lowering(pallas, "chunked"))

  def _SsdFwd(pallas):
    return lambda w, dl, b, c, v: _Ssd(pallas)(dl, b, c, v)

  def _SsdFwdBwd(pallas):
    # the final state joins the loss so the backward reaches both outputs
    def _Y(dl, b, c, v):
      y, s_fin = _Ssd(pallas)(dl, b, c, v)
      return y + jnp.mean(s_fin)
    return lambda w, dl, b, c, v: _Weighted(_Y, (0, 1, 2, 3))(w, dl, b, c, v)

  def _Retention(pallas):
    from lingvo_tpu.core.nested_map import NestedMap
    from lingvo_tpu.ops import power_retention

    def _Run(q, k, v, log_g, state, norm, k_pool, v_pool, gates, tables, rows):
      y, state, norm, pool = power_retention.PackedRetention(
          q, k, v, log_g, state, norm,
          NestedMap(key=k_pool, value=v_pool, gate=gates), tables, rows,
          eps=1e-6, **_Lowering(pallas))
      return y, state, norm, pool.key, pool.gate

    return _Run

  return [
      KernelCase("flash_fwd", "flash", _FlashFwd),
      KernelCase("flash_fwd_bwd_segments", "flash", _FlashFwdBwdSeg),
      KernelCase("block_decode_bf16", "block_decode_bf16", _BlockDecode),
      KernelCase("block_decode_int8", "block_decode_int8", _BlockDecode),
      KernelCase("ragged_attend_plain", "ragged_plain", _Ragged),
      KernelCase("ragged_attend_tree", "ragged_tree", _Ragged),
      KernelCase("ragged_attend_int8", "ragged_int8", _Ragged),
      KernelCase("ragged_attend_no_rows", "ragged_plain", _RaggedNoRows),
      KernelCase("ragged_attend_grouped", "ragged_grouped", _RaggedGrouped(0)),
      KernelCase("ragged_attend_grouped_window", "ragged_grouped",
                 _RaggedGrouped(s.grouped_window)),
      KernelCase("diff_attend_full", "diff_attend", _DiffAttend(0)),
      KernelCase("diff_attend_window", "diff_attend",
                 _DiffAttend(s.diff_window)),
      KernelCase("diff_write_pages", "diff_write", _DiffWrite),
      KernelCase("run_write", "run_write", _RunWrite),
      KernelCase("ragged_attend_tree_plan", "ragged_tree", _RaggedPlan),
      KernelCase("ragged_attend_grouped_window_plan", "ragged_grouped",
                 _GroupedPlan),
      KernelCase("diff_attend_window_plan", "diff_attend", _DiffPlan),
      KernelCase("diff_write_pages_plan", "diff_write", _DiffWritePlan),
      KernelCase("selective_scan_packed", "selective_scan", _SelectiveScan),
      KernelCase("power_retention_packed", "retention", _Retention),
      KernelCase("flash_decode", "flash_decode", _FlashDecode),
      KernelCase("fused_xent_fwd", "xent", _XentFwd),
      KernelCase("fused_xent_fwd_bwd", "xent", _XentFwdBwd),
      KernelCase("ssd_scan_fwd", "ssd", _SsdFwd),
      KernelCase("ssd_scan_fwd_bwd", "ssd", _SsdFwdBwd),
  ]


def _WorstLeaf(got, want) -> tuple[float, float]:
  """(max abs error, tolerance held) of the leaf closest to its tolerance;
  a leaf's tolerance is REL_TOL times its largest reference magnitude."""
  import jax
  import numpy as np
  worst = (0.0, 0.0, -1.0)
  g_leaves = jax.tree_util.tree_leaves(got)
  w_leaves = jax.tree_util.tree_leaves(want)
  assert len(g_leaves) == len(w_leaves), (len(g_leaves), len(w_leaves))
  for g, w in zip(g_leaves, w_leaves):
    g = np.asarray(g, np.float32)
    w = np.asarray(w, np.float32)
    assert g.shape == w.shape, (g.shape, w.shape)
    if not (np.all(np.isfinite(g)) and np.all(np.isfinite(w))):
      return float("inf"), 0.0
    err = float(np.max(np.abs(g - w))) if g.size else 0.0
    tol = REL_TOL * max(float(np.max(np.abs(w))) if w.size else 0.0, 1e-6)
    if err / tol > worst[2]:
      worst = (err, tol, err / tol)
  return worst[0], worst[1]


def KernelsPhase(size: Size, seed: int) -> dict:
  import jax
  all_inputs = jax.jit(lambda key: KernelInputs(size, key))(
      jax.random.PRNGKey(seed))
  out = []
  for case in KernelCases(size):
    inputs = all_inputs[case.inputs]
    # kernel and twin in one program: one compile, the same operands
    compiled = jax.jit(
        lambda *a, case=case: (case.fn(True)(*a), case.fn(False)(*a))
    ).lower(*inputs).compile()
    if not size.interpret and "tpu_custom_call" not in compiled.as_text():
      raise AssertionError(f"{case.name}: no tpu_custom_call in the program")
    err, tol = _WorstLeaf(*compiled(*inputs))
    out.append({"name": case.name, "max_abs_err": err, "tolerance": tol})
    if not err <= tol:
      raise AssertionError(
          f"{case.name}: max abs error {err:.4g} over tolerance {tol:.4g}; "
          f"done so far: {out}")
  return {"interpret": size.interpret, "cases": out}


# -- the model ---------------------------------------------------------------


def _ModelParams(size: Size, *, flash: bool, layers: int | None):
  import jax.numpy as jnp
  from lingvo_tpu import model_registry
  from lingvo_tpu.core import attention as attention_lib
  import lingvo_tpu.models.all_params  # noqa: F401  (populates the registry)
  mp = model_registry.GetParams(size.model, "Train")
  mp.task.input = mp.input
  mp.task.fprop_dtype = jnp.bfloat16
  if layers is not None:
    mp.task.num_layers = layers
  if flash:
    # remat on dots + flash attention: the settings of the dense train cells
    mp.task.remat_policy = "dots"
    mp.task.atten_tpl = attention_lib.MultiHeadedAttention.Params().Set(
        use_flash_attention=True)
  return mp


def _Instantiate(task_p):
  task = task_p.Instantiate()
  task.FinalizePaths()
  return task


def _TreesEqual(a, b) -> bool:
  import jax
  import numpy as np
  la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
  return len(la) == len(lb) and all(
      np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _PeakBytes() -> dict:
  import jax
  stats = jax.devices()[0].memory_stats() or {}   # None on the CPU
  return {k: stats[k] for k in ("peak_bytes_in_use", "bytes_limit")
          if k in stats}


# -- train -------------------------------------------------------------------


def TrainPhase(size: Size, seed: int) -> dict:
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.core import checkpointer as checkpointer_lib
  from lingvo_tpu.core import input_policy
  from lingvo_tpu.runners import executor as executor_lib
  from lingvo_tpu.runners import program as program_lib

  mp = _ModelParams(size, flash=True, layers=size.train_layers)
  steps_per_loop = size.steps_per_loop or mp.task.train.tpu_steps_per_loop
  max_steps = 2 * steps_per_loop
  mp.task.train.max_steps = max_steps
  task = _Instantiate(mp.task)
  logdir = tempfile.mkdtemp(prefix="chip_smoke_train_")
  try:
    train_p = program_lib.TrainProgram.Params().Set(
        task=mp.task, logdir=logdir, steps_per_loop=steps_per_loop)
    schedule = program_lib.SimpleProgramSchedule(
        program_lib.SimpleProgramSchedule.Params().Set(train_program=train_p),
        task=task)
    executor = executor_lib.ExecutorTpu(
        mp, logdir, schedule=schedule, task=task, init_seed=seed,
        precompile=True)
    # Start() saves step 0 through SaveAsync while the first loop trains on
    # donated buffers, runs the two loops, and force-saves the last step.
    final = executor.Start()
    step_rec = schedule.train_program.compile_records["step"]
    if not size.interpret and not step_rec["tpu_custom_calls"]:
      raise AssertionError(f"no flash kernel in the train step: {step_rec}")

    with open(os.path.join(logdir, "metrics.jsonl")) as f:
      rows = [json.loads(line) for line in f]
    losses = [r["train"]["loss"] for r in rows if "train" in r]
    if len(losses) != 2 or not all(math.isfinite(x) for x in losses):
      raise AssertionError(f"want two finite loop losses, got {losses}")

    ckpt = checkpointer_lib.Checkpointer(os.path.join(logdir, "train"))
    try:
      fresh = task.CreateTrainState(jax.random.PRNGKey(seed))
      # Reference: the loss of the initial weights on the first batch through
      # a plain forward (no flash kernel, no remat). The tied, tanh-capped
      # head starts far from uniform (21.4 at depth 6 against ln V = 10.4),
      # so ln V is no yardstick. The learning rate is still in its warm-up:
      # the first loop's mean sat 2 to 3% under where the weights started on
      # the chip, and must sit within 10%; no fall is asked of the second.
      plain = _Instantiate(
          _ModelParams(size, flash=False, layers=size.train_layers).task)
      batch = input_policy.Instantiate(
          mp.input).GetPreprocessedInputBatch().Transform(jnp.asarray)
      init_loss = float(jax.jit(plain.EvalStep)(fresh.theta, batch)[0].loss[0])
      if not abs(losses[0] - init_loss) <= 0.1 * init_loss:
        raise AssertionError(
            f"first loop loss {losses[0]:.4f}, initial weights give "
            f"{init_loss:.4f} through the plain forward")
      # The async snapshot of step 0 must hold the initial state, whatever
      # the loops did to the donated buffers it was copied from; the last
      # checkpoint must hold what Start() returned.
      first, first_step = ckpt.Restore(fresh, step=0)
      if first_step != 0 or not _TreesEqual(first, fresh):
        raise AssertionError("step-0 (SaveAsync) checkpoint != initial state")
      del first, fresh
      last, last_step = ckpt.Restore(final)
      if last_step != max_steps or not _TreesEqual(last, final):
        raise AssertionError(
            f"step-{last_step} checkpoint != final state at {max_steps}")
    finally:
      ckpt.Close()
    return {
        "layers": mp.task.num_layers, "steps": max_steps, "losses": losses,
        "plain_forward_initial_loss": init_loss, "compile": step_rec,
        "checkpoints_restored": [0, last_step], **_PeakBytes(),
    }
  finally:
    shutil.rmtree(logdir, ignore_errors=True)


# -- serve -------------------------------------------------------------------


def ServePhase(size: Size, seed: int) -> dict:
  import jax
  import jax.numpy as jnp
  import numpy as np
  from lingvo_tpu.core import py_utils
  from lingvo_tpu.core.nested_map import NestedMap
  from lingvo_tpu.observe import schema as observe_schema
  from lingvo_tpu.serving import engine as engine_lib

  mp = _ModelParams(size, flash=False, layers=None)
  task = _Instantiate(mp.task)

  def _Init(key):
    # served weights live in the fprop dtype: 2.6 GB for the 1.3B model
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        task.InstantiateVariables(key))

  theta = jax.jit(_Init)(jax.random.PRNGKey(seed))
  rng = np.random.RandomState(seed)
  lo, hi = size.prompt_lens
  lens = rng.randint(lo, hi + 1, size=size.max_batch)
  prompts = [rng.randint(1, mp.task.vocab_size, size=n).astype(np.int32)
             for n in lens]

  # reference: one plain unpaged forward over the padded prompts
  width = -(-int(lens.max()) // 8) * 8
  ids = np.zeros((len(prompts), width), np.int32)
  for i, p in enumerate(prompts):
    ids[i, :len(p)] = p
  paddings = (np.arange(width)[None] >= lens[:, None]).astype(np.float32)

  def _LastLogits(theta, ids, paddings, last):
    with py_utils.EvalContext():
      preds = task.ComputePredictions(
          theta, NestedMap(ids=ids, paddings=paddings))
    rows = jnp.arange(ids.shape[0])
    return jax.lax.top_k(preds.logits[rows, last].astype(jnp.float32), 2)

  top2, top2_ids = jax.device_get(jax.jit(_LastLogits)(
      theta, jnp.asarray(ids), jnp.asarray(paddings), jnp.asarray(lens - 1)))

  engine = engine_lib.ServingLoop(
      task, theta, page_size=size.page_size, num_pages=size.num_pages,
      max_batch=size.max_batch, max_seq_len=size.max_seq_len)
  want_path = "xla" if size.interpret else "pallas"
  if engine.paged_path != want_path:
    raise AssertionError(
        f"paged_path {engine.paged_path!r}, want {want_path!r}")
  # the loop runs on its own thread: catch what kills it, not a timeout
  died = []
  prev_hook = threading.excepthook
  threading.excepthook = lambda a: (died.append(a), prev_hook(a))
  t0 = time.perf_counter()
  try:
    engine.Start()
    handles = [engine.Submit(p, size.new_tokens) for p in prompts]
    while not all(h.done for h in handles):
      if died:
        raise RuntimeError(
            f"serving loop died: {died[0].exc_type.__name__}: "
            f"{died[0].exc_value}")
      time.sleep(0.01)
    streams = [h.Result(timeout=0) for h in handles]
  finally:
    engine.Stop(drain=False)
    threading.excepthook = prev_hook
  serve_s = time.perf_counter() - t0

  if [len(s) for s in streams] != [size.new_tokens] * len(prompts):
    raise AssertionError(f"stream lengths {[len(s) for s in streams]}")
  stats = engine.Stats()
  records = stats["compile"]
  programs = records.pop(observe_schema.COMPILE_CENSUS_KEY)
  ragged = records.get("ragged", {})
  if programs != 1 or "fallback" in ragged or (
      not size.interpret and not ragged.get("tpu_custom_calls")):
    raise AssertionError(f"want one compiled Pallas step program: {records}")
  # first tokens against the reference; a prompt whose two best logits lie
  # within bf16 rounding of each other has no stable argmax to compare
  checked, skipped = 0, 0
  for i, stream in enumerate(streams):
    if top2[i, 0] - top2[i, 1] <= 2.0 ** -6 * max(abs(top2[i, 0]), 1.0):
      skipped += 1
    elif stream[0] != top2_ids[i, 0]:
      raise AssertionError(
          f"prompt {i} (len {lens[i]}): first token {stream[0]}, plain "
          f"forward argmax {top2_ids[i, 0]} (top-2 logits {top2[i]})")
    else:
      checked += 1
  if not checked:
    raise AssertionError("every prompt was skipped: nothing was compared")
  return {
      "layers": mp.task.num_layers, "paged_path": engine.paged_path,
      "kv_cache_dtype": stats["kv_cache_dtype"],
      "prompt_lens": lens.tolist(),
      "tokens_out": sum(len(s) for s in streams), "steps": stats["steps"],
      "first_tokens_checked": checked, "first_tokens_skipped": skipped,
      "step_programs": programs, "compile": records,
      "serve_seconds": serve_s, **_PeakBytes(),
  }


def HybridPhase(seed: int) -> dict:
  """The tiny Nemotron-H sibling's layers at the smallest widths the chip's
  kernels take (heads and state indices of 128, pages of 128, a step of 128
  packed tokens), served on whatever device is there."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  from benchmarks.references import nemotron_h as reference
  from lingvo_tpu import model_registry
  from lingvo_tpu.serving import engine as engine_lib
  import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

  mp = model_registry.GetParams("lm.nemotron_h.Nemotron3NanoTiny", "Train")
  tp = mp.task
  tp.input = mp.input
  tp.Set(fprop_dtype=jnp.bfloat16, model_dim=256, vocab_size=512)
  tp.atten_tpl.Set(dim_per_head=128)
  tp.mixer_tpl.Set(head_dim=64, state_dim=128)
  tp.expert_ffn_tpl.Set(hidden_dim=128, shared_hidden_dim=256)
  task = _Instantiate(tp)
  theta = jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16), reference.SeededWeights(
          task.InstantiateVariables(jax.random.PRNGKey(seed)),
          router_reads_share=0.25, router_scale=32.0,
          groups=tp.mixer_tpl.num_groups, state_dim=tp.mixer_tpl.state_dim,
          experts_per_token=tp.expert_ffn_tpl.num_experts_per_token))
  prompt = np.random.RandomState(seed).randint(1, tp.vocab_size, 300).astype(
      np.int32)
  new_tokens = 6
  engine = engine_lib.ServingLoop(task, theta, page_size=128, num_pages=8,
                                  max_batch=2, max_seq_len=512,
                                  prefill_token_budget=126)
  on_chip = jax.default_backend() == "tpu"
  if engine.paged_path != ("pallas" if on_chip else "xla"):
    raise AssertionError(f"paged_path {engine.paged_path!r}")
  handle = engine.Submit(prompt, new_tokens)
  while not handle.done:
    engine.StepOnce()
  stream = np.asarray(handle.Result(), np.int32)
  seq = np.concatenate([prompt, stream])
  ids = np.zeros((new_tokens, 512), np.int32)
  ids[:, :len(seq)] = seq
  at = len(prompt) - 1 + np.arange(new_tokens, dtype=np.int32)
  with jax.default_matmul_precision("highest"):
    want = np.asarray(jax.jit(lambda th, i, a: reference.LogitsAt(
        th, i, a, 0.0))(theta, jnp.asarray(ids), jnp.asarray(at)))
  checked = 0
  for i, tok in enumerate(stream):
    best, second = np.sort(want[i])[::-1][:2]
    if best - second <= 0.1:     # inside bf16's rounding: no stable argmax
      continue
    if tok != want[i].argmax():
      raise AssertionError(
          f"streamed token {i} is {tok}, the reference's {want[i].argmax()} "
          f"(its two best logits {best}, {second})")
    checked += 1
  if not checked:
    raise AssertionError("every token was skipped: nothing was compared")
  stats = engine.Stats()
  records = stats["compile"]
  if on_chip and not records.get("ragged", {}).get("tpu_custom_calls"):
    raise AssertionError(f"want a Pallas step program: {records}")
  return {"layer_kinds": stats["layer_kinds"], "steps": stats["steps"],
          "paged_path": engine.paged_path,
          "tpu_custom_calls": records.get("ragged", {}).get(
              "tpu_custom_calls"),
          "ssm_tokens": stats["ssm_tokens"],
          "moe_tokens_routed": stats["moe_tokens_routed"],
          "tokens_checked": checked, "tokens_out": len(stream)}


# -- four chips --------------------------------------------------------------


def MultichipPhase(size: Size, seed: int) -> dict:
  import jax
  from jax.sharding import PartitionSpec
  from lingvo_tpu.parallel import mesh as mesh_lib
  from lingvo_tpu.runners import program as program_lib

  devices = jax.devices()[:4]
  mesh = mesh_lib.MakeMesh({"data": 2, "model": 2}, devices=devices)
  steps = 2

  def _TwoSteps(mesh):
    mp = _ModelParams(size, flash=True, layers=size.train_layers)
    task = _Instantiate(mp.task)
    logdir = tempfile.mkdtemp(prefix="chip_smoke_multichip_")
    train_p = program_lib.TrainProgram.Params().Set(
        task=mp.task, logdir=logdir, steps_per_loop=steps)
    if mesh is not None:
      train_p.Set(
          mesh=mesh, input_sharding=PartitionSpec("data"),
          state_sharding_fn=lambda state: mesh_lib.TrainStateShardings(
              mesh, task, state))
    prog = program_lib.TrainProgram(train_p, task=task)
    try:
      state = program_lib.PlaceStateForPrograms(
          [prog], task.CreateTrainState(jax.random.PRNGKey(seed)))
      spans = {len(x.sharding.device_set)
               for x in jax.tree_util.tree_leaves(state.theta)}
      split = sum(x.sharding.shard_shape(x.shape) != x.shape
                  for x in jax.tree_util.tree_leaves(state.theta))
      # one batch through the program's own placement (both runs draw it,
      # so they still train on the same ones)
      batch = prog._PutBatch(prog.input_generator.GetPreprocessedInputBatch())
      batch_span = len(batch.ids.sharding.device_set)
      prog.Compile(state)
      state, result = prog.Run(state)   # the first Run waits for its own loop
      jax.block_until_ready(state)
      return {"loss": result["loss"], "theta_device_spans": sorted(spans),
              "theta_leaves_split": split, "batch_device_span": batch_span,
              "compile": prog.compile_records["step"]}
    finally:
      prog.Shutdown()
      shutil.rmtree(logdir, ignore_errors=True)

  sharded = _TwoSteps(mesh)
  single = _TwoSteps(None)
  all_reduces = sharded["compile"]["collectives"].get("all-reduce", 0)
  if (sharded["theta_device_spans"] != [4] or not sharded["theta_leaves_split"]
      or sharded["batch_device_span"] != 4
      or single["theta_device_spans"] != [1]):
    raise AssertionError(f"placement: {sharded} against {single}")
  if not all_reduces:
    raise AssertionError(f"no all-reduce in the sharded step: {sharded}")
  if not size.interpret and not sharded["compile"]["tpu_custom_calls"]:
    raise AssertionError(f"no flash kernel in the sharded step: {sharded}")
  rel = abs(sharded["loss"] - single["loss"]) / abs(single["loss"])
  if not rel <= 1e-2:
    raise AssertionError(
        f"loss {sharded['loss']} on the mesh, {single['loss']} on one device")
  return {"mesh": {"data": 2, "model": 2}, "steps": steps,
          "loss_rel_diff": rel, "sharded": sharded, "single_device": single,
          **_PeakBytes()}


# -- driver ------------------------------------------------------------------


def DevicePhase(cache_dir: str, cache_was_empty: bool) -> dict:
  import importlib.metadata
  import jax
  try:
    libtpu = importlib.metadata.version("libtpu")
  except importlib.metadata.PackageNotFoundError:
    libtpu = None
  return {"devices": [str(d) for d in jax.devices()], "jax": jax.__version__,
          "libtpu": libtpu, "cache_dir": cache_dir,
          "cache_was_empty": cache_was_empty}


def _Emit(obj: dict) -> None:
  print(json.dumps(obj, default=str), flush=True)


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--seed", type=int, default=0,
                      help="weights, prompts and kernel inputs.")
  parser.add_argument("--tiny", action="store_true",
                      help="DenseLmTiny, interpret-mode kernels: the CPU "
                      "rehearsal, never reported as a chip run.")
  parser.add_argument("--multichip", action="store_true",
                      help="only the four-chip GSPMD train phase and the "
                      "one-device steps it is compared with.")
  args = parser.parse_args(argv)
  size = TINY if args.tiny else REAL
  need = 4 if args.multichip else 1

  device = {"platform": None, "kind": None, "count": 0}
  cache = {"hits": 0, "misses": 0}

  def _Fail(phase: str, error: str) -> int:
    _Emit({"phase": phase, "ok": False, "error": error})
    _Emit({"ok": False, "device": device})
    return 1

  try:
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    from lingvo_tpu.core import compile_cache
  except Exception as e:  # noqa: BLE001 - no backend or no repo: report, exit
    return _Fail("device", f"{type(e).__name__}: {e}")
  if not args.tiny and device["platform"] != "tpu":
    return _Fail("device", f"no TPU: JAX found {device}")
  if device["count"] < need:
    return _Fail("device", f"need {need} devices: JAX found {device}")

  cache_dir = compile_cache.Configure()
  cache_was_empty = not (os.path.isdir(cache_dir) and os.listdir(cache_dir))

  def _CountCache(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
      cache["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
      cache["misses"] += 1

  jax.monitoring.register_event_listener(_CountCache)

  if args.multichip:
    phases = [("multichip", lambda: MultichipPhase(size, args.seed))]
  else:
    phases = [
        ("device", lambda: DevicePhase(cache_dir, cache_was_empty)),
        ("kernels", lambda: KernelsPhase(size, args.seed)),
        ("train", lambda: TrainPhase(size, args.seed)),
        ("serve", lambda: ServePhase(size, args.seed)),
        ("hybrid", lambda: HybridPhase(args.seed)),
    ]
  for name, run in phases:
    before = dict(cache)
    t0 = time.perf_counter()
    try:
      with contextlib.redirect_stdout(sys.stderr):   # library chatter
        out = run()
    except Exception as e:  # noqa: BLE001 - the one place a failure lands
      traceback.print_exc()
      return _Fail(name, f"{type(e).__name__}: {e}")
    _Emit({"phase": name, "ok": True,
           "seconds": round(time.perf_counter() - t0, 3),
           "cache_hits": cache["hits"] - before["hits"],
           "cache_misses": cache["misses"] - before["misses"], **out})
  _Emit({"ok": True, "device": device})
  return 0


if __name__ == "__main__":
  sys.exit(main())
