"""What a generator hands the server loop: ops with due times, and a plan.

An op is one thing a client does. `kind` is one of

- ``update``: one wire update frame from a session (its next edit);
- ``awareness``: one awareness frame;
- ``sync1``: a SyncStep1 carrying the client's own state vector;
- ``reconnect``: the provider's reconnect handshake: disconnect, connect
  (greeting), SyncStep1 with the state vector in `frame`, reply collected.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence


@dataclass
class Op:
    kind: str
    session: int  # index into Plan.sessions (traffic) or warm sessions
    room: int
    frame: bytes  # the wire frame handed to `receive_frames`
    update: Optional[bytes] = None  # raw update payload, for the oracle
    due: float = 0.0  # seconds after the window opens
    stale: int = -1  # reconnect: room updates the carried state vector lacks (-1 = empty)


@dataclass
class Plan:
    """Everything one run sends, made from the seed before the server exists."""

    clients: List[int]  # every client id, preregistered before the first dispatch
    session_rooms: List[int]  # room of every traffic session (index = session)
    preload: List[Op]  # update ops put through the served path during set-up
    warm: List[List[Op]]  # warm-up ticks: each list is handed over as one tick
    warm_session_rooms: List[int]
    ops: List[Op]  # the window's ops, by due time
    saturated: bool  # True: everything is due at 0 and the inbox is never empty
    repeat: bool  # True: when `ops` is exhausted start again (state unchanged)
    tick_max_frames: int
    # sessions as the grammar built them, for the grammar-only checks
    sessions: Sequence = field(default_factory=list)
    notes: Dict[str, object] = field(default_factory=dict)
