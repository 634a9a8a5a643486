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
  # every number compared beside its limit: the line's last key, and the
  # last lines of standard error
  assert list(line)[-1] == "compared" and line["compared"]
  said = done.stderr.strip().splitlines()[-len(line["compared"]):]
  for (name, c), ln in zip(line["compared"].items(), said):
    assert c["value"] <= c["limit"]
    assert ln == f"compared {name}: {c['value']!r} limit {c['limit']!r}"
  notes = [json.loads(x) for x in done.stdout.strip().splitlines()[:-1]]
  assert all("note" in n for n in notes), "only notes before the last line"
  by_name = {n["note"]: n["value"] for n in notes}
  # set-up: the wall time less the chip's bring-up (the first jax.devices(),
  # which on the CPU takes next to nothing) and less nothing else: JAX's
  # import is over before that call, in run.py itself, so it is counted
  # whether or not the program's own import loads JAX; the line says what
  # was left out
  setup = by_name["setup"]
  assert setup["setup_s"] == pytest.approx(
      setup["setup_wall_s"] - setup["runtime_start_s"])
  assert 0 < setup["runtime_start_at_s"] < setup["setup_wall_s"]
  assert setup["runtime_start_s"] < setup["runtime_start_at_s"], (
      "jax.devices() took longer than every import before it: JAX's import "
      "has moved into the stretch that setup_s leaves out")
  assert line["setup"] == {k: setup[k] for k in ("setup_wall_s",
                                                 "runtime_start_s")}
  if "client" in by_name:       # a serving cell says where its window went
    stalls = by_name["step_stalls"]
    assert stalls["steps"] > 2 and stalls["period_ms_median"] > 0
    assert {"lock_wait", "h2d", "device_wait", "commit", "loop"} <= set(
        stalls["phases_ms"])
    assert stalls["stalls"] >= len(stalls["stalled_steps"])
    # (a window in work ends when its requests are done, which the tiny
    # engine does in a tenth of a second; a window in seconds lasts them)
    assert by_name["client_gaps"]["passes"] > 50
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
