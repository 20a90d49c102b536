"""Packed bytes copied to the host per handshake: phases `encode.d2h_bytes` / handshakes."""


def read(w):
    st, n = w.phases.get("encode.d2h_bytes"), len(w.indices("reconnect"))
    return st["value"] / n if st and n and "value" in st else None
