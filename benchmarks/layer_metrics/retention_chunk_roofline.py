"""The attention form over each row's open chunk and the step's own tokens
(the program's `retention_chunk` scope) against the larger of its HBM and
MXU times over the traced steps (harness/retention_cost.py)."""
from benchmarks.harness import retention_cost

Read = retention_cost.ChunkRoofline
