"""BENCHMARK.json and the files it names. The harness is driven by data: a
cell's configuration is benchmarks/configs/<config>.json (or the `file` its
entry gives), its traffic benchmarks/traffic/<traffic>.json, and each
per-layer metric benchmarks/layer_metrics/<name>.py with one `Read(run)`.
Adding a cell adds files and entries and edits none.

A configuration of another family than the dense one is added the same way
(PERF.md section 4, "Adding a configuration of another family"). Files: the
configuration (the sizes every file has, `task_params` for what only its
family has, `num_kv_heads` and `attention_windows` where the roofline
readers need them, `pool_rows` where the decode state's page pools hold
more than K and V by heads: `{a pool leaf's last path key: the dimensions it
ends in behind its page and token-offset axes}`, `{"gate": [8]}` for a
retention layer's gates, `{"latent": [320]}` for a latent row a token; the
read-back (`model.ReadBackPools`) asks the program which leaves are pools
and holds each to its row, `key` and `value` to `[num_kv_heads,
dim_per_head]` with no entry, and fails the run on a pool leaf the file does
not cover and on an entry the program has no leaf for; the `rehearsal` group
carries its own), `reduced` and `assumed`, a `correct` group with a
`train_reason` where a train cell uses it and a `serve_reason` where a serve
cell does, a `rehearsal` group), its plain reference under
benchmarks/references/ (`SeededWeights`, `LogitsAt`, and `Logits` for a
train cell), a traffic file where the mix is new, and a reader for each
metric that is new. Entries: the configuration, the cell, the cell's name
appended to one end-to-end metric's `workloads` and to the lists of the
per-layer metrics that move it (and to `compile_s`'s). Nothing that is
there is edited; tests/benchmark/test_spec.py adds such a cell from new
files alone and holds it to the same rules as the tree's."""

from __future__ import annotations

import importlib.util
import json
import os

from benchmarks.harness import device


def LoadBenchmark(path: str | None = None) -> dict:
  with open(path or os.path.join(device.ROOT, "BENCHMARK.json")) as f:
    return json.load(f)


def _LoadJson(path: str) -> dict:
  with open(path) as f:
    return json.load(f)


def Cell(bench: dict, name: str, root: str | None = None) -> dict:
  """The cell with its configuration and traffic files read in. root: the
  checkout (tests point it at a copy that holds a throw-away cell)."""
  root = root or device.ROOT
  here = os.path.join(root, "benchmarks")
  for w in bench["workloads"]:
    if w["name"] == name:
      break
  else:
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")
  cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
  config = _LoadJson(os.path.join(root, cfg_entry["file"]))
  traffic = _LoadJson(os.path.join(here, "traffic", w["traffic"] + ".json"))

  def _Mine(metrics):
    return [m for m in metrics
            if "workloads" not in m or name in m["workloads"]]

  return {"name": name, "root": root, "chips": w["chips"], "config_name": w["config"],
          "traffic_name": w["traffic"], "config": config, "traffic": traffic,
          "end_to_end": _Mine(bench["end_to_end"]),
          "per_layer": _Mine(bench["per_layer"])}


def LayerMetricReader(name: str, root: str | None = None):
  """benchmarks/layer_metrics/<name>.py's Read, or None if there is no such
  file (the metric is then left out of the line)."""
  path = os.path.join(root or device.ROOT, "benchmarks", "layer_metrics",
                      name + ".py")
  if not os.path.exists(path):
    return None
  spec = importlib.util.spec_from_file_location(
      "benchmarks.layer_metrics." + name.replace(".", "_"), path)
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod.Read


class NothingToRead(KeyError):
  """The run holds no such span, counter or trace."""


class RunData(dict):
  """What a run leaves for the readers. Asking it for a key it does not hold
  is how a reader finds nothing to read; any other failure is a fault."""

  def __missing__(self, key):
    raise NothingToRead(key)


def ReadLayerMetrics(cell: dict, run: dict) -> dict:
  """{name: {"value", "unit"}} for every per-layer metric of the cell whose
  reader finds something to read. A reader that returns None, or that asks
  the run for a span, counter or trace it does not hold, leaves its metric
  out. A reader that fails in any other way fails the run: a broken reader
  must not look like a metric with nothing to read."""
  run = RunData(run)
  out = {}
  for m in cell["per_layer"]:
    read = LayerMetricReader(m["name"], cell.get("root"))
    if read is None:
      continue
    try:
      value = read(run)
    except NothingToRead:
      value = None
    if value is not None:
      out[m["name"]] = {"value": float(value), "unit": m["unit"]}
  return out
