"""How long an acknowledged update is not yet readable, in served steps of its room: `ingest.stash_wait_steps` (summed over the updates released from a stash: the steps of their room from the one that stashed them to the one that brought what they waited for) / `ingest.stash_released` (the phase recorder's copies, the window's deltas). 1 where the awaited update is the room's next. Nothing to read in a window that released nothing, or from a program without the counters."""


def read(w):
    def delta(name):
        return (w.phases.get(name) or {}).get("value") or 0.0

    n = delta("ingest.stash_released")
    return delta("ingest.stash_wait_steps") / n if n else None
