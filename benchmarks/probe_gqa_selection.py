#!/usr/bin/env python3
"""What do a serving cell's limits tell apart that belongs to a model
whose GROUPED-QUERY heads attend the rows a learned indexer picks? Once,
on the chip, outside any measured window: the serving cell's own reference
check with the ENGINE as configured and the float32 reference given an
attention that does something else:

    --selection dense   every query attends every row it can see
    --selection first   every query attends the FIRST `sa_config.topk`
                        rows, not the best (a wrong selection)
    --selection topk    the reference as the cell runs it (the control)

    python3 benchmarks/probe_gqa_selection.py --workload keye-vl2-videoqa-32k --seed 1300000003 --selection dense first

The procedure is `probe_dsa_selection.py`'s, which names no model
(`references/<family>.py` reads `conf["reference_selection"]`, which no
configuration file holds) and is the accepted benchmark's, so it is run
from here as it stands. The gaps it prints stand beside the configured
ones of the same seeds in `traffic/videoqa-32k.json`'s `tolerances.why`;
the 8-bit reading of the same cell is `probe_state_precision.py --what
weights`. One engine a process: one call per seed."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.probe_dsa_selection import main  # noqa: E402

if __name__ == "__main__":
    main()
