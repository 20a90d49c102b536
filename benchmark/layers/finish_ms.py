"""Host time of the native finisher per handshake: phases `encode.finish` / handshakes."""


def read(w):
    st, n = w.phases.get("encode.finish"), len(w.indices("reconnect"))
    return st["execute_s"] / n * 1e3 if st and n else None
