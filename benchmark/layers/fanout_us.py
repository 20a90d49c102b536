"""Mean host time of the program's `sync.receive.fanout` stage per update frame: the frame encode and the pushes to the room's other sessions (phases recorder; a host stage)."""


def read(w):
    st = w.phases.get("sync.receive.fanout")
    return st["execute_s"] / st["calls"] * 1e6 if st and st.get("calls") else None
