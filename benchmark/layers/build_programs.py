"""Programs built (compiled, or loaded from the persistent cache) before the window: the program's own count of `backend_compile_duration` events at the window's opening, which is `run.py`'s "programs built so far" (`window_compiles.flood` keeps the window's). Also writes the build journal (the totals, then one row a program: the span it was built under, its name, seconds by part, hit or miss) to `.bench_trace/build_journal.json`, beside the xplane: `benchmark/tools/setup_by_program.py` prints it. A program without the totals has nothing to read and writes nothing."""

import json
import os

from benchmark import setup_parts


def read(w):
    journal = setup_parts.journal(w)
    if journal is None:
        return None
    os.makedirs(os.path.dirname(setup_parts.JOURNAL), exist_ok=True)
    with open(setup_parts.JOURNAL, "w") as f:
        json.dump(journal, f, indent=1)
    return float(journal["at_opening"]["builds"])
