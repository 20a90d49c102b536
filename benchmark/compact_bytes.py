"""What one call of the served compaction program must move, for its
roofline (`layers/compact_roofline.py`; beside `peaks.py`, which holds the
chip's published peaks and the integrate step's bytes).

The program gathers K rooms of the state (the served path: two), squashes,
collects and defragments them, and scatters them back: every plane of those
K rooms read once and written once is the least it can do. (As built it is not donated,
like the integrate step, so the chip also copies every other room's planes:
that is time the share counts against it.)
"""

from __future__ import annotations

import json
import os

from benchmark.peaks import peak

#: rooms a call gathers: `ytpu.models.ingest.COMPACT_ROOMS_PER_CALL`
ROOMS_PER_CALL = 2
_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs", "yws-rooms-1k-typed.json")


def compact_bytes(resident_bytes: int, n_rooms: int, rooms_per_call: int = ROOMS_PER_CALL) -> float:
    """Bytes of `rooms_per_call` rooms' planes, read once and written once."""
    return 2.0 * resident_bytes * rooms_per_call / n_rooms


def compact_min_seconds(resident_bytes: int, device_kind: str) -> float:
    """The least time one call can take on this chip in the typed cell's
    deployment (its room count from its configuration file)."""
    with open(_CONFIG) as f:
        n_rooms = json.load(f)["n_docs"]
    return compact_bytes(resident_bytes, n_rooms) / peak(device_kind, "hbm_bytes_per_s")
