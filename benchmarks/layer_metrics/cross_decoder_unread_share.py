"""Valid packed tokens that are neither a row's decode token nor the last
token of its prompt (`cross_tokens_unread`), over all valid packed tokens
(`ssm_tokens`), between the window's first and last step: the share of the
tokens for which the layers after the last page-owning one compute what
nothing reads."""
from benchmarks.harness import moe_cost


def Read(run):
  grew = moe_cost.CounterDeltas(run, ("cross_tokens_unread", "ssm_tokens"))
  if grew is None or grew["ssm_tokens"] <= 0:
    return None
  return 100.0 * grew["cross_tokens_unread"] / grew["ssm_tokens"]
