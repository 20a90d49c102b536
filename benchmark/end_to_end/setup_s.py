"""Process start to the first timed instant: native build, program build
or load, prefill, preload, warm-up."""


def read(w):
    return w.setup_s
