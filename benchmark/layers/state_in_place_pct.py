"""Share of the window's calls that replaced the served state (an integrate step, a room compaction) after which the buffers handed in were consumed: `ingest.state_in_place` / `ingest.state_steps` x 100 (`BatchIngestor._count_state_step`: the program donates the state, XLA aliases each buffer to its output, and a compact step writes K rooms where they are instead of copying 26 planes of 16 MB first). 100 where the donation engaged in every call; less where jax found a donated buffer no output and copied as before (it only warns). The window's counter deltas where they carry the names, else the phase recorder's copies of the same counts (stage values). A program without the counters has nothing to read."""


def read(w):
    def count(name):
        return w.counters.get(name) or (w.phases.get(name) or {}).get("value")

    steps = count("ingest.state_steps")
    return 100.0 * (count("ingest.state_in_place") or 0.0) / steps if steps else None
