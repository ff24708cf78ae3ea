"""The decode tick's share of its memory roofline where grouped-query
heads attend the rows an indexer picks: `dsa_tick_bytes_roofline.tput`'s
own reader, as it stands (its log line carries that reader's name), under
a name of its own because a test of the benchmark holds the `dsa_*`
metrics' lists of cells to their first cell. The least time the bytes of a
tick need at the chip's peak bandwidth (`harness/dsa_cost.tick_bytes` from
the family's `shape()`: the weights every token reads once, an expert's
three matrices for each expert that got a row, and in each layer the index
key of every VISIBLE row and the keys and values, of every head of them,
of every SELECTED row; no ring: `ring_rows_read` is 0), over the mean
device time of the `_tick` program in the traced window; what the tick met
comes from the engine's loop ring (`moe_experts_hit`, `dsa_rows_visible`,
`dsa_rows_selected`). The program scores every row of the slab whatever is
visible, and dead slots and unread experts count nothing: a lower reading.
None against a program, or in a cell, whose ring lacks the counters."""
from benchmarks.harness.readers import load_reader

read = load_reader("dsa_tick_bytes_roofline.tput")
