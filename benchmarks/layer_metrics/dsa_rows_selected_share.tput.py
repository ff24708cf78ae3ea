"""What of the rows a query could see it attends: the rows the full
layers' queries attended (`dsa_rows_selected`: `min(t + 1, index_topk)` a
query) over the rows they could see (`dsa_rows_visible`: `t + 1`), summed
over the admissions of the window of offered load and the decode passes
in it (the prefill's counters in the admission's record, the tick's in
the pass's), in per cent. Near `index_topk` over the mean visible rows;
100 for prompts no longer than `index_topk`. None against a program whose
records lack the two counters."""
from benchmarks.harness.loop_records import admissions, decoding


def read(obs):
    met = [r for r in list(admissions(obs)) + list(decoding(obs))
           if r.get("dsa_rows_visible")]
    if not met:
        return None
    return 100.0 * sum(r["dsa_rows_selected"] for r in met) \
        / sum(r["dsa_rows_visible"] for r in met)
