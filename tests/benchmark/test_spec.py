"""BENCHMARK.json against the contract's rules, and the proof that a cell is
added from new files alone."""

import json
import os
import re
import shutil

import pytest

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


def test_configs(bench):
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
    with open(os.path.join(ROOT, c["file"])) as f:
      cfg = json.load(f)
    for key in c["reduced"]:
      assert NAME.match(key) and key in cfg
      # a width is never reduced
      assert not re.search(r"(_dim|_rank|hidden|head)", key), key
    assert len(c["reduced"]) <= 16
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "references", cfg["reference"] + ".py"))
    assert cfg["model_dim"] == cfg["num_heads"] * cfg["dim_per_head"]
    assert cfg["correct"]["train_reason"]


def test_workloads(bench):
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
        ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))


def test_metrics(bench):
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
    assert spec.LayerMetricReader(m["name"]) is not None, m["name"]
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
      assert m["unit"] == "%"
  for c in cells:
    mine = [m for m in bench["end_to_end"] if c in _Cells(m)]
    assert len(mine) >= 2, c           # setup_s and at least one other
    assert any(c in _Cells(m) for m in bench["per_layer"]), c


def test_every_cell_loads_its_files(bench):
  for w in bench["workloads"]:
    cell = spec.Cell(bench, w["name"])
    assert cell["traffic"]["kind"] in ("train", "serve")
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
  with pytest.raises(KeyError):
    spec.Cell(bench, "no_such_cell")


def test_a_cell_is_added_from_new_files_alone(bench, tmp_path):
  """A throw-away cell: one new configuration file, one new traffic file,
  one new reader, three new entries, and no file that was there is edited.
  The general generator reads the new mix and the harness finds the reader
  by name."""
  root = str(tmp_path)
  shutil.copytree(os.path.join(ROOT, "benchmarks"),
                  os.path.join(root, "benchmarks"),
                  ignore=shutil.ignore_patterns("__pycache__", "data"))
  before = {}
  for d, _, files in os.walk(os.path.join(root, "benchmarks")):
    for f in files:
      p = os.path.join(d, f)
      before[p] = open(p, "rb").read()

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
  for m in grown["end_to_end"]:
    if m["name"] == "itl_p95_ms":
      m["workloads"] = m["workloads"] + ["throwaway_bursty"]
  grown["per_layer"].append({
      "name": "steps_seen.lat", "unit": "steps", "better": "higher",
      "source": "program_counter", "layer": "serving engine",
      "moves": "itl_p95_ms", "workloads": ["throwaway_bursty"]})

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
