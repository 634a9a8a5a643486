"""Tokens of the fullest expert over tokens of the mean expert, summed over
layers and the window's steps (engine counters `serving/moe_expert_load_max`
and `serving/moe_expert_load_mean`): 1 is an even spread."""
from benchmarks.harness import moe_cost


def Read(run):
  grew = moe_cost.CounterDeltas(
      run, ("moe_expert_load_max", "moe_expert_load_mean"))
  if grew is None or grew["moe_expert_load_mean"] <= 0:
    return None
  return grew["moe_expert_load_max"] / grew["moe_expert_load_mean"]
