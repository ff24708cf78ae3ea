"""The indexer's share of its roofline over the prompts of the window,
where the heads it selects for are grouped-query ones:
`dsa_index_roofline.tput`'s own reader, as it stands (the visible pairs
alone through `harness/dsa_cost.index_flops`, `index_bytes` and
`index_calls` from the family's `shape()`, a block of queries a call of
`dsa_index_t<T>`; its log line carries that reader's name), under a name
of its own because a test of the benchmark holds the `dsa_*` metrics'
lists of cells to their first cell. 2 x 16 x 64 operations a pair on the
matrix unit here, and on the vector unit a ReLU, a product and a sum for
each of the 16 heads, which the harness has no peak for and does not
count: a lower reading. None against a program without the kernel, or a
backend without Mosaic."""
from benchmarks.harness.readers import load_reader

read = load_reader("dsa_index_roofline.tput")
