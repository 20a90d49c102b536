#!/usr/bin/env python3
"""Make `benchmark/data/b4_flags.txt` from the repo's copy of the B4 trace.

    python3 benchmark/tools/b4_flags.py [--ops 32768]

`benches/data/b4_log.pkl.gz` is crdt-benchmarks B4 ("real-world editing
dataset": 182,315 single-character insertions and 77,463 single-character
deletions) as the updates one Yjs client sends, an update a keystroke. The
benchmark reads nothing outside its directory, so what
`generators/keystroke_mix.py` needs of the trace is kept beside it: one
letter an op, in order,

    c  an insert that continues the run: its origin is the character the
       client inserted last
    j  an insert anywhere else (a jump: a new run starts)
    b  a delete of the character the client inserted last (a backspace)
    d  a delete of another character

and nothing else (no positions, no characters: the rooms of the benchmark
hold other documents than B4's). `--ops` letters are kept: 1,024 sessions
x 24 keystrokes, and as many again for warm-up sessions and tests.
`recount()` is what `benchmark/tests/test_keystroke_mix.py` holds the file
to wherever the log is present.
"""

from __future__ import annotations

import argparse
import gzip
import os
import pickle
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LOG = os.path.join(ROOT, "benches", "data", "b4_log.pkl.gz")
OUT = os.path.join(os.path.dirname(HERE), "data", "b4_flags.txt")


def recount(n_ops: int, log_path: str = LOG) -> str:
    """The first `n_ops` letters, from the log itself."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from ytpu.core import Update

    with gzip.open(log_path, "rb") as f:
        log = pickle.load(f)["log"]
    out = []
    last = None  # id of the character inserted last
    for payload in log[:n_ops]:
        update = Update.decode_v1(payload)
        blocks = [b for q in update.blocks.values() for b in q]
        if blocks:
            (b,) = blocks
            if b.len != 1:
                raise ValueError(f"op {len(out)} inserts {b.len} characters")
            origin = getattr(b, "origin", None)
            same = last is not None and origin is not None and (origin.client, origin.clock) == last
            out.append("c" if same else "j")
            last = (b.id.client, b.id.clock)
        else:
            ((client, ranges),) = update.delete_set.clients.items()
            ((start, end),) = list(ranges)
            if end - start != 1:
                raise ValueError(f"op {len(out)} deletes {end - start} characters")
            out.append("b" if (client, start) == last else "d")
    return "".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ops", type=int, default=32768)
    args = ap.parse_args(argv)
    flags = recount(args.ops)
    with open(OUT, "w") as f:
        f.write(flags + "\n")
    print(f"{OUT}: {len(flags)} ops: " + ", ".join(f"{k} {flags.count(k)}" for k in "cjbd"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
