"""The CPU rehearsal of every cell: the whole command line at the
configuration's tiny sizes, kernels in interpret mode, virtual devices for
the mesh. It prints counts, never a device metric; without --rehearse a
missing TPU is an error."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
  CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _Run(args, tmp_path):
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
             BENCH_RUN="ignored")
  return subprocess.run(
      [sys.executable, RUN, *args, "--out", str(tmp_path)], cwd=ROOT, env=env,
      capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_whole_command_line(cell, tmp_path):
  done = _Run(["--workload", cell, "--seed", "3000000019", "--seconds", "2",
               "--trace", "0", "--rehearse"], tmp_path)
  assert done.returncode == 0, done.stderr[-2000:]
  line = json.loads(done.stdout.strip().splitlines()[-1])
  assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
  assert line["correct"] is True and line["failed"] == 0
  assert line["attempted"] > 0
  assert line["metrics"] == {}, "a CPU run reports no device metric"
  assert line["rehearsal"] is True
  assert line["device"]["platform"] == "cpu"
  detail = line["counts"]["correct_detail"]
  assert "tolerance" in detail
  notes = [json.loads(x) for x in done.stdout.strip().splitlines()[:-1]]
  assert all("note" in n for n in notes), "only notes before the last line"
  by_name = {n["note"]: n["value"] for n in notes}
  if "client" in by_name:       # a serving cell says where its window went
    stalls = by_name["step_stalls"]
    assert stalls["steps"] > 2 and stalls["period_ms_median"] > 0
    assert {"lock_wait", "h2d", "device_wait", "commit", "loop"} <= set(
        stalls["phases_ms"])
    assert stalls["stalls"] >= len(stalls["stalled_steps"])
    assert by_name["client_gaps"]["passes"] > 100
    assert set(by_name["gc"]) == {"by_generation", "long_at_s_generation_ms"}
    assert by_name["compiles_in_window"] == [], "nothing compiles in a window"
    assert "step_records" not in by_name, "too long for a log"
    with open(os.path.join(str(tmp_path), cell + ".notes.jsonl")) as f:
      kept = json.loads(f.readlines()[-1])["notes"]
    assert len(kept["step_records"]) == stalls["steps"]


def test_without_a_tpu_a_measured_run_is_an_error(tmp_path):
  done = _Run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0"], tmp_path)
  assert done.returncode != 0
  assert '"correct"' not in done.stdout
  assert "no TPU" in done.stderr
