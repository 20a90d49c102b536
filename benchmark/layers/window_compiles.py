"""Program builds (compiled or loaded from the cache) inside the window: `jax.monitoring` backend_compile events. Expected 0."""


def read(w):
    return float(w.compiles)
