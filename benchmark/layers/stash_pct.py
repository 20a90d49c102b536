"""Share of the window's updates of which a block or a delete range went into a room's stash (it reached the server before an update it depends on): `ingest.stash_updates` (the phase recorder's copy of the count, the window's delta) / the window's update frames x 100. About 5 in the co-edit cell (598 of 12,288), or the traffic is not this cell's; 0 where no update is early. A program without the counter has nothing to read."""


def read(w):
    st = w.phases.get("ingest.stash_updates")
    n = len(w.indices("update"))
    return 100.0 * (st.get("value") or 0.0) / n if st is not None and n else None
