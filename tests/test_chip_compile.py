"""What the chip's compiler must accept, checked without a chip.

The TPU compiler is installed wherever JAX is and compiles for a chip that
is described and not attached (the `on-chip-measurement` guide, section 2).
Every Pallas kernel of the train and serve paths is compiled here for one
v5e chip at the widths `chip_smoke.py` runs them at: kernels that passed
every interpret-mode test were refused by Mosaic for a dot it does not have,
a primitive it does not lower and more VMEM than a kernel may take, and only
a compile shows that. A compile that passes is not a chip run.

Also reads the rehearsal of `chip_smoke.py --tiny` on the CPU, which
`conftest.py` runs as a child beside the first test files (half a minute of
tracing that tier-1's time cap has no room for in this process). The file's
name sorts early on purpose: tier-1 stops at its cap, and a file past that
point guards nothing.
"""

import concurrent.futures
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp

import jax
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

import chip_smoke

_CASES = {case.name: case for case in chip_smoke.KernelCases(chip_smoke.REAL)}

# The ragged attend kernel at the shapes the serving cells of `dense1b` run
# it at (BENCHMARK.json: 32 slots + a 512-token budget = 544 packed tokens,
# 16 heads of 128, pages of 128, 16 pages a row, 193 pool pages + trash).
_SERVING = dict(t=544, n=16, h=128, page=128, table_pages=16, pool_pages=194,
                rows=32)
_SERVING_CASES = {"bf16": "ragged_attend_plain", "int8": "ragged_attend_int8",
                  "tree": "ragged_attend_tree"}


# The grouped kernel at the shapes `smallthinker21b_serve_mixed` runs it at:
# 64 slots + a 1,024-token budget, 28 query heads over 4 KV heads of 128, the
# block's one pool of 2 x 7,601 pages, 128 pages a row.
_GROUPED_SERVING = dict(t=1088, n=28, n_kv=4, h=128, page=128,
                        table_pages=128, pool_pages=15202, rows=64)
_GROUPED_CASES = {"full": "ragged_attend_grouped",
                  "window": "ragged_attend_grouped_window"}


# The longest lists of (block, page) pairs any cell's plan rides scalar
# prefetch with: `nemotron3nano_serve_agent`'s one attention layer, 32 query
# heads over 2 KV heads, 98 blocks x 128 pages = 12,544 entries twice.
_AGENT_SERVING = dict(t=1088, n=32, n_kv=2, h=128, page=128, table_pages=128,
                      pool_pages=2049, rows=64)


# The four power-retention kernels at the shapes `brumby14b_serve_longwrite`
# runs them at: 16 slots + a 512-token budget, 40 query heads over 8 KV heads
# of 128, states of 8,320 features, the one pool of 8 x 104 pages and the
# trash page, 128 pages a row.
_RETENTION_SERVING = dict(t=528, n=40, n_kv=8, h=128, page=128,
                          table_pages=128, pool_pages=833, rows=16, wmax=512)


# The whole-page write at the shapes `phi4flash_serve_reason` runs it at: 64
# slots + a 512-token budget, 20 KV heads of 64 (a token is a LANE of its
# page), the nine owners' one pool of 9 x 729 pages, 64 pages a row.
_REASON_SERVING = dict(t=576, n=40, n_kv=20, h=64, page=128, table_pages=64,
                       pool_pages=6561, rows=64)


# The grouped kernel and the runs' write at the shapes
# `trinitymini_serve_repo_agent` runs them at: 64 slots + a 1,024-token
# budget, 32 query heads over 4 KV heads of 128 (a group of EIGHT query heads
# a KV head), the five layers' one pool of 5 x 2,000 pages and the trash
# page, 272 pages a row (34,816 tokens): the longest block tables a cell has.
_REPO_AGENT_SERVING = dict(t=1088, n=32, n_kv=4, h=128, page=128,
                           table_pages=272, pool_pages=10001, rows=64)


# The grouped kernel and the runs' write at the shapes
# `lfm2_24b_serve_chat_wide` runs them at: 256 slots + a 1,024-token budget
# (1,280 packed rows: the widest pack a cell has), 32 query heads over 8 KV
# heads of 64, TWO KV heads side by side on a token's row of the pool (`row`:
# the pool is [pages, 128, 4, 128], ops/ragged_block_attend.TileHeads), the
# two layers' one pool of 2 x 4,600 pages and the trash page, 76 pages a row.
_CHAT_WIDE_SERVING = dict(t=1280, n=32, n_kv=8, h=64, row=2, page=128,
                          table_pages=76, pool_pages=9201, rows=256)


def _RetentionServingArgs(d=_RETENTION_SERVING):
  import jax.numpy as jnp
  from lingvo_tpu.core import ragged
  from lingvo_tpu.ops import power_retention
  sds = jax.ShapeDtypeStruct
  f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
  t, b, nk, h = d["t"], d["rows"], d["n_kv"], d["h"]
  pool = sds((d["pool_pages"], d["page"], nk, h), bf16)
  tok, row = sds((t,), i32), sds((b,), i32)
  cols = sds((b, d["wmax"]), i32)
  rows = ragged.RaggedRows(
      row_of=tok, col_of=tok, pos=tok, valid=sds((t,), jnp.bool_),
      row_q_pos=row, row_len=row, row_cols=cols, pos_ids=tok, anc_lo=tok,
      anc_hi=tok, col_parent=cols)
  return (sds((t, d["n"], h), f32), sds((t, nk, h), f32), sds((t, nk, h), bf16),
          sds((t, nk), f32),
          sds((b, nk, h, power_retention.StoredDim(h)), f32),
          sds((b, nk, power_retention.Offsets(h), h), f32), pool, pool,
          sds((d["pool_pages"], nk, d["page"]), f32),
          sds((b, d["table_pages"]), i32), rows)


def _RunWriteServingArgs(d, kv_dtype=None):
  """The operands of chip_smoke's `_RunWrite` case at a cell's shapes: its
  pool, a step's packed K and V, its block tables and rows."""
  import jax.numpy as jnp
  from lingvo_tpu.core import ragged
  sds = jax.ShapeDtypeStruct
  i32 = jnp.int32
  # a token's row of the pool: a KV head, or `row` of them side by side
  n = d.get("n_kv", d["n"]) // d.get("row", 1)
  h = d["h"] * d.get("row", 1)
  dtype = kv_dtype or jnp.bfloat16
  pool = sds((d["pool_pages"], d["page"], n, h), dtype)
  new = sds((d["t"], n, h), dtype)
  tok, row = sds((d["t"],), i32), sds((d["rows"],), i32)
  cols = sds((d["rows"], d["t"] - d["rows"] + 1), i32)
  rows = ragged.RaggedRows(
      row_of=tok, col_of=tok, pos=tok, valid=sds((d["t"],), jnp.bool_),
      row_q_pos=row, row_len=row, row_cols=cols, pos_ids=tok, anc_lo=tok,
      anc_hi=tok, col_parent=cols)
  return (pool, pool, new, new, sds((d["rows"], d["table_pages"]), i32), rows)


def _GroupedServingArgs(d=_GROUPED_SERVING):
  import jax.numpy as jnp
  sds = jax.ShapeDtypeStruct
  row = d.get("row", 1)
  pool = sds((d["pool_pages"], d["page"], d["n_kv"] // row, d["h"] * row),
             jnp.bfloat16)
  tok = sds((d["t"],), jnp.int32)
  return (sds((d["t"], d["n"], d["h"]), jnp.bfloat16), pool, pool,
          sds((d["rows"], d["table_pages"]), jnp.int32), tok, tok)


def _ServingArgs(variant):
  """The operands of chip_smoke's `_Ragged` case at `_SERVING` shapes."""
  import jax.numpy as jnp
  d = _SERVING
  i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
  sds = jax.ShapeDtypeStruct
  kv = jnp.int8 if variant == "int8" else bf16
  pool = sds((d["pool_pages"], d["page"], d["n"], d["h"]), kv)
  scale = (sds((d["pool_pages"], d["n"], d["page"]), f32)
           if variant == "int8" else None)
  tok = sds((d["t"],), i32)
  tree = tok if variant == "tree" else None
  return (sds((d["t"], d["n"], d["h"]), bf16), pool, pool, scale, scale,
          sds((d["rows"], d["table_pages"]), i32), tok, tok, tree, tree, tree)


@pytest.fixture(scope="module")
def compiles():
  """{case name: Future of the compiled program's text}, all cases at once:
  the compiler runs outside the interpreter lock, so eight threads finish in
  a third of the time one would take."""
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # noqa: BLE001 - no TPU compiler, or another holds it
    pytest.skip(f"cannot describe a v5e topology: {e}")
  one_chip = SingleDeviceSharding(topo.devices[0])
  shapes = jax.eval_shape(
      lambda key: chip_smoke.KernelInputs(chip_smoke.REAL, key),
      jax.random.PRNGKey(0))

  def _Compile(case, args=None):
    if args is None:
      args = shapes[case.inputs]
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        args)
    return jax.jit(case.fn(True)).lower(*args).compile().as_text()

  # such a compile is written to the persistent cache but cannot be read
  # back without a chip: the next run would warn and compile again
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
      futures = {name: pool.submit(_Compile, case)
                 for name, case in _CASES.items()}
      futures.update({
          f"serving_{variant}": pool.submit(
              _Compile, _CASES[name], _ServingArgs(variant))
          for variant, name in _SERVING_CASES.items()})
      futures.update({
          f"grouped_serving_{variant}": pool.submit(
              _Compile, _CASES[name], _GroupedServingArgs())
          for variant, name in _GROUPED_CASES.items()})
      futures["retention_serving"] = pool.submit(
          _Compile, _CASES["power_retention_packed"], _RetentionServingArgs())
      import jax.numpy as jnp
      futures.update({
          f"run_write_serving_{cell}": pool.submit(
              _Compile, _CASES["run_write"], _RunWriteServingArgs(*args))
          for cell, args in {
              "docs": (_SERVING,), "docs_int8": (_SERVING, jnp.int8),
              "mixed": (_GROUPED_SERVING,), "agent": (_AGENT_SERVING,),
          }.items()})
      futures["diff_write_serving_reason"] = pool.submit(
          _Compile, _CASES["diff_write_pages_plan"],
          _RunWriteServingArgs(_REASON_SERVING))
      futures["grouped_serving_agent"] = pool.submit(
          _Compile, _CASES["ragged_attend_grouped"],
          _GroupedServingArgs(_AGENT_SERVING))
      futures.update({
          f"grouped_serving_repo_agent_{variant}": pool.submit(
              _Compile, _CASES[name], _GroupedServingArgs(_REPO_AGENT_SERVING))
          for variant, name in _GROUPED_CASES.items()})
      futures["run_write_serving_repo_agent"] = pool.submit(
          _Compile, _CASES["run_write"],
          _RunWriteServingArgs(_REPO_AGENT_SERVING))
      futures["grouped_serving_chat_wide"] = pool.submit(
          _Compile, _CASES["ragged_attend_grouped"],
          _GroupedServingArgs(_CHAT_WIDE_SERVING))
      futures["run_write_serving_chat_wide"] = pool.submit(
          _Compile, _CASES["run_write"],
          _RunWriteServingArgs(_CHAT_WIDE_SERVING))
      yield futures
  finally:
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


# The tiled row pass of the packed Mamba-2 scan at the two shapes the cells
# run it at: `nemotron3nano_serve_agent` (64 heads of 64 in 8 groups: a group
# of 512 channels is ONE tile) and `granite4hsmall_serve_tools` (128 heads of
# 64 in ONE group: 16 tiles of 512), each with one layer's state alone (a
# stack of one) and with the state read and written in its scanned block's
# stack of layers, as the cells run it; 64 slots + a 1,024-token budget.
_ROW_PASS = {"agent": dict(hm=64, g=8, stack=0),
             "agent_in_stack": dict(hm=64, g=8, stack=5),
             "tools": dict(hm=128, g=1, stack=0),
             "tools_in_stack": dict(hm=128, g=1, stack=5)}


@pytest.mark.parametrize("shape", sorted(_ROW_PASS))
def test_the_row_pass_compiles_at_serving_shapes(shape):
  import jax.numpy as jnp
  from lingvo_tpu.core import ragged
  from lingvo_tpu.ops import packed_ssd_scan
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # noqa: BLE001 - no TPU compiler
    pytest.skip(f"cannot describe a v5e topology: {e}")
  one_chip = SingleDeviceSharding(topo.devices[0])
  d = _ROW_PASS[shape]
  t, slots, wmax, p, n = 1088, 64, 1024, 64, 128
  f32, i32 = jnp.float32, jnp.int32
  sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
  tok = sds((t,), i32)
  rows = ragged.RaggedRows(
      row_of=tok, col_of=tok, pos=tok, valid=sds((t,), jnp.bool_),
      row_q_pos=sds((slots,), i32), row_len=sds((slots,), i32),
      row_cols=sds((slots, wmax), i32), pos_ids=tok, anc_lo=tok, anc_hi=tok,
      col_parent=sds((slots, wmax), i32))
  state = (slots, d["hm"], p, n)
  args = [sds((t, d["hm"], p), f32), sds((t, d["hm"]), f32),
          sds((d["hm"],), f32), sds((t, d["g"], n), f32),
          sds((t, d["g"], n), f32), sds((d["hm"],), f32),
          sds(((d["stack"],) if d["stack"] else ()) + state, f32), rows]
  layer = {"layer": 3} if d["stack"] else {}
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    text = jax.jit(lambda *a: packed_ssd_scan.PackedSsdScan(
        *a, chunk_size=64, lowering="pallas", interpret=False, **layer),
                   donate_argnums=(6,)).lower(*args).compile().as_text()
  finally:
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
  assert "tpu_custom_call" in text and "%ssd_row_pass" in text


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_compiles_for_v5e(name, compiles):
  # raises what the chip's compiler would raise
  assert "tpu_custom_call" in compiles[name].result(timeout=300)


@pytest.mark.parametrize("variant", sorted(_SERVING_CASES))
def test_ragged_attend_compiles_at_serving_shapes(variant, compiles):
  assert "tpu_custom_call" in compiles[f"serving_{variant}"].result(
      timeout=300)


@pytest.mark.parametrize("variant", sorted(_GROUPED_CASES) + [
    "agent", "repo_agent_full", "repo_agent_window", "chat_wide"])
def test_grouped_attend_compiles_at_serving_shapes(variant, compiles):
  # every rung of the ladder is a branch of the one program Mosaic lowers:
  # a decode row's 8 rows and a chunk's 512 with its clear and masked bodies
  # (`chat_wide`: heads of 64, a pair a row of the pool)
  assert "tpu_custom_call" in compiles[f"grouped_serving_{variant}"].result(
      timeout=300)


@pytest.mark.parametrize("cell", ["docs", "docs_int8", "mixed", "agent",
                                  "repo_agent", "chat_wide"])
def test_run_write_compiles_at_serving_shapes(cell, compiles):
  # token rows of 16, 4 and 2 KV heads (4 KB, 1 KB and 512 B of bf16) and of
  # int8: each a whole number of the tiles Mosaic lays that pool out in
  assert "tpu_custom_call" in compiles[f"run_write_serving_{cell}"].result(
      timeout=300)


def test_diff_page_write_compiles_at_serving_shapes(compiles):
  # the step's new K and V whole in VMEM beside the lanes they are laid out
  # on and the pages' double buffers; a bf16 page rolled as 32-bit words
  assert "tpu_custom_call" in compiles["diff_write_serving_reason"].result(
      timeout=300)


def test_power_retention_compiles_at_serving_shapes(compiles):
  # the open chunk's kernel, the state's two queries and the fold
  assert compiles["retention_serving"].result(timeout=300).count(
      "tpu_custom_call") >= 4


@pytest.fixture(scope="module")
def tiny_lines(tiny_smoke):
  returncode, out, err = tiny_smoke
  assert returncode == 0, f"chip_smoke.py --tiny failed:\n{out}\n{err[-3000:]}"
  return [json.loads(line) for line in out.strip().splitlines()]


@pytest.mark.parametrize("phase", ["device", "kernels", "train", "serve",
                                   "hybrid"])
def test_tiny_smoke_phase(phase, tiny_lines):
  rows = [row for row in tiny_lines[:-1] if row["phase"] == phase]
  assert len(rows) == 1 and rows[0]["ok"], rows
  row = rows[0]
  if phase == "kernels":
    assert [c["name"] for c in row["cases"]] == list(_CASES)
    assert all(c["max_abs_err"] <= c["tolerance"] for c in row["cases"])
  elif phase == "train":
    assert len(row["losses"]) == 2 and row["checkpoints_restored"] == [0, 4]
  elif phase == "serve":
    assert row["step_programs"] == 1 and row["first_tokens_checked"] >= 1
    assert row["tokens_out"] == len(row["prompt_lens"]) * 4


def test_tiny_smoke_last_line(tiny_lines):
  assert [row["phase"] for row in tiny_lines[:-1]] == [
      "device", "kernels", "train", "serve", "hybrid"]
  last = tiny_lines[-1]
  assert set(last) == {"ok", "device"} and last["ok"] is True
  # the platform it really saw: a rehearsal never reads as a chip run
  assert set(last["device"]) == {"platform", "kind", "count"}
  assert last["device"]["platform"] == "cpu"


def test_no_tpu_runs_no_phase(capsys):
  """Without --tiny a CPU is a failure, at once: nothing is measured here."""
  assert chip_smoke.main([]) != 0
  lines = [json.loads(line)
           for line in capsys.readouterr().out.strip().splitlines()]
  assert [row.get("phase") for row in lines] == ["device", None]
  assert not lines[0]["ok"] and "no TPU" in lines[0]["error"]
  assert lines[-1] == {"ok": False, "device": {
      "platform": "cpu", "kind": jax.devices()[0].device_kind,
      "count": len(jax.devices())}}
