"""Mean host time of `receive_frames` per update frame (benchmark span)."""


def read(w):
    r = w.rec
    idx = w.indices("update")
    return sum(r.recv1[i] - r.recv0[i] for i in idx) / len(idx) * 1e6 if idx else None
