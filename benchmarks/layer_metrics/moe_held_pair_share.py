"""The (token, expert) pairs whose expert this chip holds over all the pairs
its routers chose (engine counters `serving/moe_tokens_routed` and
`serving/moe_pairs_elsewhere`): 25% where four chips share a layer and
routing is even."""
from benchmarks.harness import mla_cost

Read = mla_cost.HeldPairShare
