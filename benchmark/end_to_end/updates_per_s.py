"""Update frames integrated on the device (their dispatch ended in
`block_until_ready`) per second of window, all of it."""


def read(w):
    n = len(w.indices("update"))
    return n / w.elapsed if n else None
