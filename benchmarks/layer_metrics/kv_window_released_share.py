"""Pages of the window layers that a live row let go of, over the pages
of those layers ever backed, between the window's first and last step
(`kv_cache.KindPages.pages_released` / `pages_allocated` in the step
records)."""
import json

from benchmarks.harness import moe_cost


def Read(run):
  # beside it, what each kind of layer held of the one pool at most
  print(json.dumps({"note": "kv_pages_by_kind", "value": {
      "kinds": run["kv_pages"].get("kinds"),
      "num_pages": run["kv_pages"].get("num_pages"),
      "peak_in_use": run["kv_pages"].get("peak_in_use")}}), flush=True)
  grew = moe_cost.CounterDeltas(
      run, ("window_pages_released", "window_pages_allocated"))
  if grew is None or grew["window_pages_allocated"] <= 0:
    return None
  return 100.0 * grew["window_pages_released"] / grew["window_pages_allocated"]
