"""Valid queries over the ragged attend kernel's query blocks times the
block size `Bq`, over the window's steps (engine counters
`serving/attend_block_queries`, `serving/attend_query_blocks`)."""
from benchmarks.harness import layer_lib

Read = layer_lib.AttendBlockFill
