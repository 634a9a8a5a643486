"""BENCHMARK.json against the contract's rules, and the proof that a cell is
added from new files alone: one of the dense family, and one that is not
(a head size that is not model_dim / heads, KV heads, `task_params`, no
`hidden_dim`). The rules are functions of a benchmark and a checkout, so the
throw-away cells are held to the same ones as the tree's."""

import json
import os
import re
import shutil

import pytest

from benchmarks.harness import model as model_lib
from benchmarks.harness import spec
from benchmarks.harness import traffic as traffic_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
  return spec.LoadBenchmark()


def test_top_level_keys_and_limits(bench):
  assert set(bench) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
  assert bench["command"] == ["python3", "benchmarks/run.py"]
  assert bench["paths"] == ["benchmarks", "tests/benchmark"]
  assert isinstance(bench["run_seconds"], int)
  assert 1 <= bench["run_seconds"] <= 51
  assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
  cells = len(bench["workloads"])
  # (2 + 14 * cells) runs of run_seconds + 60, 180 s a cell to compile, 1200
  # spare, inside 43200 s, at the full 24 cells a later PR may reach
  assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
  assert 1 <= cells <= 24


def _Json(*path):
  with open(os.path.join(*path)) as f:
    return json.load(f)


def _CheckConfigs(bench, root=ROOT):
  names = [c["name"] for c in bench["configs"]]
  assert len(set(names)) == len(names)
  files = [c["file"] for c in bench["configs"]]
  assert len(set(files)) == len(files)
  used = {w["config"] for w in bench["workloads"]}
  for c in bench["configs"]:
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and c["name"] in used
    assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    assert c["file"].startswith("benchmarks/")
    cfg = _Json(root, c["file"])
    for key in c["reduced"]:
      assert NAME.match(key) and key in cfg
      # a width is never reduced
      assert not re.search(r"(_dim|_rank|hidden|head)", key), key
    assert len(c["reduced"]) <= 16
    assert os.path.exists(os.path.join(
        root, "benchmarks", "references", cfg["reference"] + ".py"))
    # the head arithmetic where it is true, at the sizes that run and at the
    # rehearsal's: d / heads where the program derives it, the written head
    # size where the file writes one; KV heads divide the heads
    model_lib.CheckHeads(cfg)
    model_lib.CheckHeads(model_lib.Sizes(cfg, rehearse=True))
    # a reason for each kind of cell that uses the configuration
    kinds = {_Json(root, "benchmarks", "traffic", w["traffic"] + ".json")["kind"]
             for w in bench["workloads"] if w["config"] == c["name"]}
    assert kinds and kinds <= {"train", "serve"}
    for kind in kinds:
      assert cfg["correct"][kind + "_reason"], (c["name"], kind)


def test_configs(bench):
  _CheckConfigs(bench)


def _CheckWorkloads(bench, root=ROOT):
  names = [w["name"] for w in bench["workloads"]]
  assert len(set(names)) == len(names)
  pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
  assert len(set(pairs)) == len(pairs)
  four = sum(w["chips"] == 4 for w in bench["workloads"])
  assert four <= max(1, len(names) // 4)
  for w in bench["workloads"]:
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert os.path.exists(os.path.join(
        root, "benchmarks", "traffic", w["traffic"] + ".json"))


def test_workloads(bench):
  _CheckWorkloads(bench)


def _CheckMetrics(bench, root=ROOT):
  cells = [w["name"] for w in bench["workloads"]]
  e2e = {m["name"]: m for m in bench["end_to_end"]}
  names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
  assert len(set(names)) == len(names)
  assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
  assert 1 <= len(bench["end_to_end"]) <= 16
  assert 1 <= len(bench["per_layer"]) <= 128

  def _Cells(m):
    return set(m.get("workloads", cells))

  for m in bench["end_to_end"]:
    assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert 0.01 <= m["bound"] <= 0.1
    assert m["source"] in ("host_clock", "device_trace")
    assert _Cells(m) <= set(cells)
  for m in bench["per_layer"]:
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert "bound" not in m
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
    assert 1 <= len(m["layer"]) <= 200
    # the metric it moves is reported in every cell where this one is
    assert m["moves"] in e2e
    assert _Cells(m) <= _Cells(e2e[m["moves"]]), m["name"]
    assert spec.LayerMetricReader(m["name"], root) is not None, m["name"]
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
      assert m["unit"] == "%"
  for c in cells:
    mine = [m for m in bench["end_to_end"] if c in _Cells(m)]
    assert len(mine) >= 2, c           # setup_s and at least one other
    assert any(c in _Cells(m) for m in bench["per_layer"]), c


def test_metrics(bench):
  _CheckMetrics(bench)


def test_a_metric_that_moves_setup_lists_its_cells(bench):
  """`setup_s` is every cell's, those of later PRs too: a per-layer metric
  that moves it and lists no cells would have to be reported by each of
  them, so a PR that adds a cell would be refused for a reader it never
  saw. Each such metric lists the cells that report it."""
  cells = {w["name"] for w in bench["workloads"]}
  mine = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
  assert [m["name"] for m in mine] == ["compile_s"]
  for m in mine:
    assert set(m["workloads"]) <= cells and m["workloads"]


def test_every_cell_loads_its_files(bench):
  for w in bench["workloads"]:
    cell = spec.Cell(bench, w["name"])
    assert cell["traffic"]["kind"] in ("train", "serve")
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
  with pytest.raises(KeyError):
    spec.Cell(bench, "no_such_cell")


def _Copy(tmp_path):
  """A copy of benchmarks/ to add a throw-away cell to, and every file in it
  as it was: ({path: bytes})."""
  root = str(tmp_path)
  shutil.copytree(os.path.join(ROOT, "benchmarks"),
                  os.path.join(root, "benchmarks"),
                  ignore=shutil.ignore_patterns("__pycache__", "data"))
  before = {}
  for d, _, files in os.walk(os.path.join(root, "benchmarks")):
    for f in files:
      before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
  return root, before


def test_a_cell_is_added_from_new_files_alone(bench, tmp_path):
  """A throw-away cell: one new configuration file, one new traffic file,
  one new reader, three new entries, and no file that was there is edited.
  The general generator reads the new mix and the harness finds the reader
  by name."""
  root, before = _Copy(tmp_path)
  with open(os.path.join(root, "benchmarks/configs/dense1b.json")) as f:
    cfg = json.load(f)
  cfg["serving"]["num_pages"] = 97
  with open(os.path.join(root, "benchmarks/configs/throwaway.json"), "w") as f:
    json.dump(cfg, f)
  mix = {"kind": "serve", "loop": "open", "rate_per_s": 4.0, "lead_in_s": 1.0,
         "prompt_len": {"dist": "fixed", "value": 48},
         "new_tokens": {"dist": "uniform", "min": 8, "max": 16}}
  with open(os.path.join(root, "benchmarks/traffic/bursty.json"), "w") as f:
    json.dump(mix, f)
  with open(os.path.join(root, "benchmarks/layer_metrics/steps_seen.lat.py"),
            "w") as f:
    f.write("def Read(run):\n  return run['window_steps']\n")

  grown = json.loads(json.dumps(bench))
  grown["configs"].append({"name": "throwaway", "source": "a test",
                           "file": "benchmarks/configs/throwaway.json",
                           "reduced": [], "why": "a test"})
  grown["workloads"].append({"name": "throwaway_bursty", "config": "throwaway",
                             "traffic": "bursty", "chips": 1, "why": "a test"})
  for m in grown["end_to_end"] + grown["per_layer"]:
    if m["name"] in ("itl_p95_ms", "compile_s"):
      m["workloads"] = m["workloads"] + ["throwaway_bursty"]
  grown["per_layer"].append({
      "name": "steps_seen.lat", "unit": "steps", "better": "higher",
      "source": "program_counter", "layer": "serving engine",
      "moves": "itl_p95_ms", "workloads": ["throwaway_bursty"]})

  _CheckConfigs(grown, root), _CheckWorkloads(grown, root)
  _CheckMetrics(grown, root)
  cell = spec.Cell(grown, "throwaway_bursty", root=root)
  assert cell["config"]["serving"]["num_pages"] == 97
  assert [m["name"] for m in cell["end_to_end"]] == ["itl_p95_ms", "setup_s"]
  reqs = traffic_lib.Generate(cell["traffic"], 10, 3000000019)
  assert len(reqs) == 4 + 40 and {r.prompt_len for r in reqs} == {48}
  got = spec.ReadLayerMetrics(cell, {"window_steps": 7, "compile_s": 1.5})
  assert got["steps_seen.lat"] == {"value": 7.0, "unit": "steps"}
  assert got["compile_s"] == {"value": 1.5, "unit": "s"}
  # readers with nothing to read leave their metric out
  assert set(got) == {"steps_seen.lat", "compile_s"}
  for p, data in before.items():
    assert open(p, "rb").read() == data, f"{p} was edited"


# -- a configuration that is not of the dense family --------------------------

_HAVE = {"num_experts": 4, "moe_hidden_dim": 48}      # the program's, today
_LATER = {"dim_per_head": 32, "num_kv_heads": 1,      # a later PR's keys
          "atten.window": 16, "atten.layout": [1, 1, 1, 0]}
_SPARSE = {
    "family": "sparse_lm", "reference": "sparse_lm",
    "registry_model": "lm.synthetic_packed_input.MoELmTiny",
    "model_dim": 80, "num_heads": 5, "dim_per_head": 32, "num_kv_heads": 1,
    "vocab_size": 128, "seq_len": 64, "batch_size": 4, "num_layers": 4,
    "attention_windows": [16, 16, 16, 0], "logit_cap": 0.0,
    "task_params": {**_HAVE, **_LATER},
    "weights": {}, "assumed": [], "reduced_notes": "num_layers 4 of 52",
    "serving": {"page_size": 8, "num_pages": 33, "max_batch": 4,
                "max_seq_len": 64, "prefill_token_budget": 16},
    "correct": {"serve_logit_tol": 0.2, "serve_sample_rows": 4,
                "serve_reason": "a test"},
    "rehearsal": {"num_layers": 2, "attention_windows": [16, 0],
                  "task_params": {**_HAVE, **_LATER, "atten.layout": [1, 0]}},
}


def _LaterProgram(monkeypatch):
  """The registered model as a later PR's program will have it: the tree's
  own params (so `num_experts` and `moe_hidden_dim` are the program's, as
  they are today) with the keys it does not have yet defined on them, one
  of them a nested group."""
  from lingvo_tpu import model_registry
  from lingvo_tpu.core import hyperparams
  real = model_registry.GetParams

  def _Get(name, dataset):
    mp = real(name, dataset)
    atten = hyperparams.Params()
    atten.Define("window", 0, "Sliding window, 0 = full.")
    atten.Define("layout", None, "Which layers slide.")
    mp.task.Define("dim_per_head", 0, "Head size, 0 = model_dim / heads.")
    mp.task.Define("num_kv_heads", 0, "KV heads, 0 = num_heads.")
    mp.task.Define("atten", atten, "Attention pattern.")
    return mp

  monkeypatch.setattr(model_registry, "GetParams", _Get)


def _ModelParams(cfg, rehearse=False):
  sizes = model_lib.Sizes(cfg, rehearse)
  return model_lib.ModelParams(sizes, num_layers=sizes["num_layers"],
                               flash=False, remat_policy=None, input_seed=7)


def test_a_cell_of_another_family_is_added_from_new_files_alone(
    bench, tmp_path, monkeypatch):
  """A throw-away serve cell whose configuration is not dense: 5 heads of 32
  on a model_dim of 80, 1 KV head, a `task_params` object, no `hidden_dim`,
  no `train_reason`. New files (configuration, reference, traffic), one new
  configuration and one new cell entry, the cell's name appended to
  `serve_tok_s`, to every `.tput` reader's list and to `compile_s`'s. It
  passes the rules, loads, and every `task_params` key lands on the params."""
  root, before = _Copy(tmp_path)
  with open(os.path.join(root, "benchmarks/configs/sparse.json"), "w") as f:
    json.dump(_SPARSE, f)
  with open(os.path.join(root, "benchmarks/references/sparse_lm.py"),
            "w") as f:
    f.write("def SeededWeights(theta):\n  return theta\n\n\n"
            "def LogitsAt(theta, ids, at, logit_cap):\n  raise "
            "NotImplementedError\n")
  mix = dict(_Json(ROOT, "benchmarks/traffic/docs.json"),
             requests_per_s_hint=2.0, notes="a test")
  with open(os.path.join(root, "benchmarks/traffic/docs_short.json"),
            "w") as f:
    json.dump(mix, f)

  name = "sparse_serve_docs_short"
  grown = json.loads(json.dumps(bench))
  grown["configs"].append({"name": "sparse", "source": "a test",
                           "file": "benchmarks/configs/sparse.json",
                           "reduced": ["num_layers"], "why": "a test"})
  grown["workloads"].append({"name": name, "config": "sparse",
                             "traffic": "docs_short", "chips": 1,
                             "why": "a test"})
  joined = []
  for m in grown["end_to_end"] + grown["per_layer"]:
    if m["name"] in ("serve_tok_s", "compile_s") or m["name"].endswith(".tput"):
      m["workloads"] = m["workloads"] + [name]
      joined.append(m["name"])
  # every `.tput` metric the file under test has, however many that is
  assert sum(n.endswith(".tput") for n in joined) == sum(
      m["name"].endswith(".tput") for m in bench["per_layer"]) >= 14

  _CheckConfigs(grown, root), _CheckWorkloads(grown, root)
  _CheckMetrics(grown, root)
  cell = spec.Cell(grown, name, root=root)
  assert "hidden_dim" not in cell["config"]
  assert "train_reason" not in cell["config"]["correct"]
  assert [m["name"] for m in cell["end_to_end"]] == ["serve_tok_s", "setup_s"]
  assert sorted(m["name"] for m in cell["per_layer"]) == sorted(
      n for n in joined if n != "serve_tok_s")
  # every reader the cell joined is found by its name
  for m in cell["per_layer"]:
    assert spec.LayerMetricReader(m["name"], root) is not None

  # on the program as it is, the first key it does not have fails the run
  # and is named
  with pytest.raises(KeyError, match="task_params key 'dim_per_head'"):
    _ModelParams(cell["config"])
  # on the program as a later PR will have it, every key is written: the
  # tree's own (num_experts, moe_hidden_dim), the later ones, the nested
  _LaterProgram(monkeypatch)
  for rehearse in (False, True):
    tp = _ModelParams(cell["config"], rehearse).task
    want = model_lib.Sizes(cell["config"], rehearse)
    for key, value in want["task_params"].items():
      assert tp.Get(key) == value, key
    assert (tp.model_dim, tp.num_heads, tp.vocab_size) == (80, 5, 128)
    assert tp.num_layers == want["num_layers"]
    assert tp.softmax_logits_soft_max == 0.0
    assert tp.hidden_dim == 128, "the registered model's: the file has none"
  for p, data in before.items():
    assert open(p, "rb").read() == data, f"{p} was edited"


# -- a configuration whose cache is a latent row a token -----------------------

_MLA = {"kv_lora_rank": 256, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "q_lora_rank": 1024}
_LATENT_CFG = {
    "family": "latent_lm", "reference": "latent_lm",
    "registry_model": "lm.synthetic_packed_input.MoELmTiny",
    # one chip's share of a model of 32 heads on 4096: the query-key head
    # size (64 + 64) is model_dim / num_heads, so no head size is written
    "model_dim": 4096, "num_heads": 32, "dim_per_head": 128,
    "vocab_size": 32768, "seq_len": 64, "batch_size": 4, "num_layers": 6,
    "logit_cap": 0.0, **_MLA,
    "task_params": {"atten_tpl." + k: v for k, v in _MLA.items()},
    # what the pool keeps a token: kv_lora_rank + qk_rope_head_dim, not heads
    "pool_rows": {"latent": [320]},
    "weights": {}, "assumed": [], "reduced_notes": "num_layers 6 of 36",
    "serving": {"page_size": 128, "num_pages": 96, "max_batch": 128,
                "max_seq_len": 8192, "prefill_token_budget": 512},
    "correct": {"serve_logit_tol": 0.2, "serve_sample_rows": 4,
                "serve_reason": "a test"},
    "rehearsal": {"model_dim": 64, "num_heads": 4, "dim_per_head": 16,
                  "num_layers": 2, "pool_rows": {"latent": [24]},
                  "serving": {"page_size": 8, "num_pages": 12, "max_batch": 8,
                              "max_seq_len": 64, "prefill_token_budget": 16}},
}
_LATENT_READER = '''"""A latent attend kernel against its roofline over the traced steps: a
live row's latent rows read once a layer, 2 x heads x (rank + rope + rank)
operations an attended token."""
from benchmarks.harness import hybrid_cost


def _Cost(s):
  row = s["pool_rows"]["latent"][0]

  def _StepCost(rows):
    attended = sum(new * (ctx - (new - 1) / 2.0) for new, ctx in rows)
    kept = sum(ctx for new, ctx in rows if new > 0)
    return (s["num_layers"] * 2.0 * s["num_heads"]
            * (row + s["kv_lora_rank"]) * attended,
            s["num_layers"] * 2.0 * row * kept)
  return _StepCost


def Read(run):
  return hybrid_cost.KernelRoofline(run, "mla_attend", _Cost(run["sizes"]))
'''


def test_a_cell_with_a_latent_pool_is_added_from_new_files_alone(
    bench, tmp_path, capsys):
  """The sibling of the test above for a stack whose cache is not K and V by
  heads: the file states `pool_rows: {"latent": [kv_lora_rank +
  qk_rope_head_dim]}`, the cell joins the lists of the readers that are not
  one kernel's, and brings its own kernel's roofline reader over the traced
  steps' rows. New files and appended entries only; every rule of this file
  holds; the read-back takes the program's latent leaf at the full and at
  the rehearsal sizes, never its slot state, and the same file with 576
  stated against 320 kept fails and names the leaf."""
  root, before = _Copy(tmp_path)
  with open(os.path.join(root, "benchmarks/configs/latent.json"), "w") as f:
    json.dump(_LATENT_CFG, f)
  with open(os.path.join(root, "benchmarks/references/latent_lm.py"),
            "w") as f:
    f.write("def SeededWeights(theta):\n  return theta\n\n\n"
            "def LogitsAt(theta, ids, at, logit_cap):\n  raise "
            "NotImplementedError\n")
  mix = dict(_Json(ROOT, "benchmarks/traffic/docs.json"), notes="a test")
  with open(os.path.join(root, "benchmarks/traffic/docs_long.json"),
            "w") as f:
    json.dump(mix, f)
  with open(os.path.join(
      root, "benchmarks/layer_metrics/mla_attend_roofline.py"), "w") as f:
    f.write(_LATENT_READER)

  name = "latent_serve_docs_long"
  grown = json.loads(json.dumps(bench))
  grown["configs"].append({"name": "latent", "source": "a test",
                           "file": "benchmarks/configs/latent.json",
                           "reduced": ["num_layers"], "why": "a test"})
  grown["workloads"].append({"name": name, "config": "latent",
                             "traffic": "docs_long", "chips": 1,
                             "why": "a test"})
  joined = []
  for m in grown["end_to_end"] + grown["per_layer"]:
    # the readers of the engine, the step and the device; not another
    # kernel's (the driver refuses a traced line that lacks a listed metric)
    if m["name"] in ("serve_tok_s", "compile_s") or (
        m["name"].endswith(".tput") and m["layer"] != "kernels"):
      m["workloads"] = m["workloads"] + [name]
      joined.append(m["name"])
  grown["per_layer"].append({
      "name": "mla_attend_roofline", "unit": "%", "better": "higher",
      "source": "device_trace", "layer": "kernels", "moves": "serve_tok_s",
      "workloads": [name]})

  _CheckConfigs(grown, root), _CheckWorkloads(grown, root)
  _CheckMetrics(grown, root)
  cell = spec.Cell(grown, name, root=root)
  assert [m["name"] for m in cell["end_to_end"]] == ["serve_tok_s", "setup_s"]
  assert sorted(m["name"] for m in cell["per_layer"]) == sorted(
      [n for n in joined if n != "serve_tok_s"] + ["mla_attend_roofline"])
  assert not any(m["name"].startswith("ragged_attend")
                 for m in cell["per_layer"])

  # the program's word and the file's: a latent leaf a layer stack, a slot
  # state with as many slots as a page has tokens
  def _Program(row):
    return lambda pages, page, slots: {"stack": {
        "mla": {"latent": (6, pages, page, row)},
        "conv": {"tail": (6, slots, 3, 4096)}}}

  for rehearse, row in ((False, 320), (True, 24)):
    sizes = model_lib.Sizes(cell["config"], rehearse)
    model_lib.CheckHeads(sizes)
    geo = sizes["serving"]
    assert _ReadBackPools(sizes, _Program(row)) == {
        "stack/mla/latent": [6, geo["num_pages"] + 1, geo["page_size"], row]}
  with pytest.raises(ValueError, match=r"pool leaf stack/mla/latent has "
                     r"shape \(6, 97, 128, 320\); the configuration file "
                     r"states \(576,\)"):
    _ReadBackPools(dict(cell["config"], pool_rows={"latent": [576]}),
                   _Program(320))

  # its own reader, found by name, over the rows of the steps the trace holds
  from benchmarks.harness import peaks
  run = {"sizes": cell["config"], "peak": peaks.PeakOf("TPU v5 lite"),
         "trace": {"kernel_s_by_scope": {"mla_attend": 0.004}},
         "trace_step": {"count": 2}, "window": (0.0, 10.25),
         "step_records": [(10.0 + 0.1 * i, 0.09, i + 1, 0) for i in range(6)],
         "step_rows": [[(1, 4000)] * 64] * 3 + [[(1, 9)]] * 3}
  got = spec.LayerMetricReader("mla_attend_roofline", root)(spec.RunData(run))
  note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert note["note"] == "mla_attend_roofline" and note["value"]["steps"] == 2
  nbytes = 2 * 6 * 2.0 * 320 * 64 * 4000     # steps 2 and 3, not 5 and 6
  assert got == pytest.approx(100.0 * nbytes / 819e9 / 0.004)
  # a run whose trace holds no such kernel: nothing to read, not 0
  assert spec.LayerMetricReader("mla_attend_roofline", root)(spec.RunData(
      dict(run, trace={"kernel_s_by_scope": {"ragged_attend": 0.1}}))) is None
  for p, data in before.items():
    assert open(p, "rb").read() == data, f"{p} was edited"


@pytest.mark.parametrize("broken, error, names", [
    ({"dim_per_head": 16}, ValueError, "dim_per_head"),
    ({"task_params": {**_HAVE, **_LATER, "num_expertz": 4}}, KeyError,
     "num_expertz"),
    ({"task_params": {**_HAVE, **_LATER, "atten.windw": 16}}, KeyError,
     "atten.windw"),
    ({"task_params": _HAVE}, ValueError, "dim_per_head"),
    ({"num_kv_heads": 2}, ValueError, "num_kv_heads"),
])
def test_a_broken_twin_fails_and_names_the_key(broken, error, names,
                                               monkeypatch):
  """The same file with another `dim_per_head` beside the written one, with a
  misspelt key (plain and nested), with no head size written (80 is not
  5 x 32), with KV heads that do not divide the heads."""
  _LaterProgram(monkeypatch)
  _ModelParams(_SPARSE)           # the file itself is sound
  with pytest.raises(error, match=names):
    _ModelParams({**_SPARSE, **broken})


# -- the accepted configurations get the params they had ----------------------


def _ParentModelParams(sizes, *, num_layers, flash, remat_policy, input_seed):
  """benchmarks/harness/model.ModelParams as it stood at fd6857a (PR 33): the
  six keys and nothing else."""
  import jax.numpy as jnp
  from lingvo_tpu import model_registry
  from lingvo_tpu.core import attention as attention_lib
  import lingvo_tpu.models.all_params  # noqa: F401
  mp = model_registry.GetParams(sizes["registry_model"], "Train")
  mp.input.Set(batch_size=sizes["batch_size"], seq_len=sizes["seq_len"],
               vocab_size=sizes["vocab_size"], seed=input_seed % (2**31))
  tp = mp.task
  tp.input = mp.input
  tp.Set(model_dim=sizes["model_dim"], num_heads=sizes["num_heads"],
         hidden_dim=sizes["hidden_dim"], vocab_size=sizes["vocab_size"],
         num_layers=num_layers,
         softmax_logits_soft_max=sizes.get("logit_cap", 30.0))
  assert sizes["model_dim"] == sizes["num_heads"] * sizes["dim_per_head"]
  tp.fprop_dtype = jnp.bfloat16
  if remat_policy is not None:
    tp.remat_policy = remat_policy
  if flash:
    tp.atten_tpl = attention_lib.MultiHeadedAttention.Params().Set(
        use_flash_attention=True)
  return mp


@pytest.mark.parametrize("rehearse", [False, True])
@pytest.mark.parametrize("name", [
    "dense1b_train_packed", "dense1b_serve_docs", "dense1b_serve_chat",
    "dense8b_train_2x2"])
def test_an_accepted_cell_gets_the_params_it_had(bench, name, rehearse):
  """No number of an accepted cell moves: at its full and at its rehearsal
  sizes, the params the harness hands the program are, key for key, what
  the six-key form handed it (`dense1b` and `dense8b`, as a train cell and
  as a serve cell build them)."""
  cell = spec.Cell(bench, name)
  sizes = model_lib.Sizes(cell["config"], rehearse)
  assert "task_params" not in sizes
  if cell["traffic"]["kind"] == "train":
    how = dict(num_layers=sizes["train_num_layers"],
               flash=cell["traffic"]["flash_attention"],
               remat_policy=cell["traffic"]["remat_policy"])
  else:
    how = dict(num_layers=sizes["num_layers"], flash=False, remat_policy=None)
  got = model_lib.ModelParams(sizes, input_seed=3400000011, **how)
  want = _ParentModelParams(sizes, input_seed=3400000011, **how)
  assert got.ToText() == want.ToText()
  text = got.ToText()
  assert f"task.model_dim : {sizes['model_dim']}" in text
  assert f"task.hidden_dim : {sizes['hidden_dim']}" in text


# -- the file's word against the program's ------------------------------------


def _Leaf(*shape):
  import numpy as np
  return np.zeros(shape, np.float32)


class _StubTask:
  """A program as the read-back sees it: `InitPagedDecodeState` alone, over
  `leaves(num_pages, page_size, num_slots) -> {path: shape}` (nested)."""

  def __init__(self, leaves):
    self._leaves = leaves

  def InitPagedDecodeState(self, theta, num_pages, page_size, num_slots,
                           kv_cache_dtype=None):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda shape: jnp.zeros(shape, jnp.float32),
        self._leaves(num_pages, page_size, num_slots),
        is_leaf=lambda x: isinstance(x, tuple))


def _ReadBackPools(sizes, leaves):
  """The read-back as a serve cell calls it: the engine's states (abstract
  here: shapes are all it reads) hold one page more than the file's
  `num_pages`, as `ServingLoop`'s do."""
  import jax
  task, geo = _StubTask(leaves), sizes["serving"]
  states = jax.eval_shape(lambda: task.InitPagedDecodeState(
      None, geo["num_pages"] + 1, geo["page_size"], geo["max_batch"]))
  return model_lib.ReadBackPools(sizes, task, None, states)


def test_read_back_holds_the_pool_to_the_files_kv_heads_and_head_size():
  sizes = {"num_heads": 16, "dim_per_head": 128,
           "serving": {"page_size": 128, "num_pages": 2, "max_batch": 4}}

  def _Dense(pages, page, slots, heads=16):
    return {"body": {"self_atten": {"key": (24, pages, page, heads, 128),
                                    "value": (24, pages, page, heads, 128)},
                     "ssm": {"state": (24, slots, 16)}}}

  assert _ReadBackPools(sizes, _Dense) == {
      "body/self_atten/key": [24, 3, 128, 16, 128],
      "body/self_atten/value": [24, 3, 128, 16, 128]}
  # grouped heads: the pool is as wide as the KV heads
  grouped = dict(sizes, num_heads=28, num_kv_heads=4,
                 serving=dict(sizes["serving"], num_pages=35))
  flat = lambda heads: lambda pages, page, slots: {
      "key": (pages, page, heads, 128), "value": (pages, page, heads, 128)}
  assert len(_ReadBackPools(grouped, flat(4))) == 2
  # the file says 4 KV heads, the program keeps 28: the roofline would count
  # a seventh of the bytes the kernel reads
  with pytest.raises(ValueError, match=r"key has shape \(36, 128, 28, 128\); "
                     r"the configuration file states \(4, 128\)"):
    _ReadBackPools(grouped, flat(28))
  with pytest.raises(ValueError, match="no pool leaf"):
    _ReadBackPools(sizes, lambda pages, page, slots: {
        "ssm": {"state": (24, slots, 16)}})


# a stack whose cache is not K and V by heads: a latent row a token, a gate a
# (head, token) with its offsets on the LAST axis, and a slot state whose
# slots are as many as a page has tokens (`max_batch` == `page_size`)
_LATENT = {"num_heads": 32, "dim_per_head": 128, "pool_rows": {
    "latent": [320], "gate": [8]},
           "serving": {"page_size": 128, "num_pages": 40, "max_batch": 128}}


def _LatentLeaves(latent=320, more=None):
  def _Leaves(pages, page, slots):
    return {"stack": {"mla": {"latent": (pages, page, latent)},
                      "retention": {"gate": (pages, 8, page)},
                      "ssm": {"state": (2, slots, 16, 5120)},
                      **(more(pages, page, slots) if more else {})}}
  return _Leaves


def test_read_back_holds_a_latent_pool_to_the_row_the_file_states():
  """Which leaves are pools is the program's word, what each keeps a token
  the file's: the latent leaf ends in `[320]`, the gate leaf keeps `[8]`
  behind a page axis and an offset axis that are not neighbours, and the
  slot state `[2, 128, 16, 5120]`, which the rule by shape took for a pool
  (third from last = page size), is not read."""
  assert _ReadBackPools(_LATENT, _LatentLeaves()) == {
      "stack/mla/latent": [41, 128, 320],
      "stack/retention/gate": [41, 8, 128]}
  # a file with K and V by heads beside them states nothing for those two
  both = _LatentLeaves(more=lambda pages, page, slots: {
      "atten": {"key": (pages, page, 32, 128), "value": (pages, page, 32, 128)}})
  assert sorted(_ReadBackPools(_LATENT, both)) == [
      "stack/atten/key", "stack/atten/value", "stack/mla/latent",
      "stack/retention/gate"]


@pytest.mark.parametrize("sizes, leaves, says", [
    # the file states kv_lora_rank + qk_rope_head_dim of another model
    (dict(_LATENT, pool_rows={"latent": [576], "gate": [8]}), _LatentLeaves(),
     r"pool leaf stack/mla/latent has shape \(41, 128, 320\); the "
     r"configuration file states \(576,\) for the last 1 of the \(320,\)"),
    # the program keeps another row than the file's
    (_LATENT, _LatentLeaves(latent=576),
     r"stack/mla/latent has shape \(41, 128, 576\); .* states \(320,\)"),
    # a pool leaf the file does not cover
    (dict(_LATENT, pool_rows={"latent": [320]}), _LatentLeaves(),
     r"pool leaf stack/retention/gate has shape \(41, 8, 128\) and the "
     r"configuration file states no row for 'gate': pool_rows covers "
     r"\['latent'\]"),
    # a row stated for a leaf the program does not have
    (dict(_LATENT, pool_rows={"latent": [320], "gate": [8], "scale": [1]}),
     _LatentLeaves(),
     r"pool_rows states \{'scale': \[1\]\} and the program declares no such "
     r"pool leaf"),
    # ... and for one that is a slot state, not a pool
    (dict(_LATENT, pool_rows={"latent": [320], "gate": [8],
                              "state": [16, 5120]}), _LatentLeaves(),
     r"pool_rows states \{'state': \[16, 5120\]\}"),
    # a row that is no row
    (dict(_LATENT, pool_rows={"latent": [], "gate": [8]}), _LatentLeaves(),
     r"pool_rows 'latent' is \[\]"),
    # K and V restated by the file, and wrongly
    (dict(_LATENT, pool_rows={"latent": [320], "gate": [8], "key": [320]}),
     _LatentLeaves(more=lambda pages, page, slots: {
         "atten": {"key": (pages, page, 32, 128)}}),
     r"stack/atten/key has shape \(41, 128, 32, 128\); .* states \(320,\)"),
    # no pool at all, whatever the slot state's shape
    (dict(_LATENT, pool_rows={}), lambda pages, page, slots: {
        "ssm": {"state": (2, slots, 16, 5120)}}, "no pool leaf"),
])
def test_read_back_fails_and_names_the_leaf(sizes, leaves, says):
  with pytest.raises(ValueError, match=says):
    _ReadBackPools(sizes, leaves)


def test_read_back_never_takes_a_slot_state_for_a_pool():
  """`slots == page_size` and the shape is a pool's by the old rule: the
  layout's word decides, so a stack of K, V and such a state reads K and V
  alone, and the state's last dimensions are held to nothing."""
  sizes = {"num_heads": 40, "num_kv_heads": 20, "dim_per_head": 64,
           "serving": {"page_size": 128, "num_pages": 8, "max_batch": 128}}
  got = _ReadBackPools(sizes, lambda pages, page, slots: {
      "pool": {"key": (pages, page, 20, 64), "value": (pages, page, 20, 64)},
      "mamba": {"state": (9, slots, 16, 5120), "tail": (9, slots, 3, 5120)}})
  assert got == {"pool/key": [9, 128, 20, 64], "pool/value": [9, 128, 20, 64]}


def test_read_back_holds_the_query_projection_to_the_files_heads():
  sizes = {"num_heads": 128, "dim_per_head": 64}
  theta = {"stack": {"body": {"self_atten": {"atten": {
      "w_query": _Leaf(2, 8192, 128, 64), "w_key": _Leaf(2, 8192, 16, 64),
      "b_query": _Leaf(2, 128, 64)}}}}}
  assert model_lib.ReadBackQueryProjection(sizes, theta) == {
      "stack/body/self_atten/atten/w_query": [2, 8192, 128, 64]}
  # the file states a head size the program does not derive
  with pytest.raises(ValueError, match="w_query has shape"):
    model_lib.ReadBackQueryProjection(dict(sizes, dim_per_head=128), theta)
  with pytest.raises(ValueError, match="no query projection"):
    model_lib.ReadBackQueryProjection(sizes, {"emb": {"emb": _Leaf(8, 4)}})
