"""Collective events on the device's op line (ops there run one at a time, so
this is the part of them with no compute running on that device), over the
traced steps, mean over the chips. Async collectives show their -start and
-done halves; what runs between the two is hidden behind compute and is not
counted."""


def Read(run):
  if run["chips"] < 2:
    return None
  return 100.0 * run["trace"]["collective_exposed_s"] / run["trace"]["window_s"]
