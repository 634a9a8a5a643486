"""Tokens per loop over the median loop interval of the window, per chip: the
steady state's rate, which one stall does not move. train_tok_s (all tokens
over all the time) falls below it by what stalls cost."""


def Read(run):
  return run["loop_median_tok_s"]
