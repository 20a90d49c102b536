"""Mean host time of the program's `sync.receive.roots` stage per update frame: `_note_roots`, a native prescan of the update for its root names before it is queued (the step's planning prescans it again) (phases recorder; a host stage)."""


def read(w):
    st = w.phases.get("sync.receive.roots")
    return st["execute_s"] / st["calls"] * 1e6 if st and st.get("calls") else None
