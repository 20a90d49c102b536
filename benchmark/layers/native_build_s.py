"""Seconds the native library's start took: `ytpu.native.startup`, the on-demand g++ build (0 where the library was there) plus the `dlopen`. It happens at the first `native.load()`, before any recorder can be on. A program without the record has nothing to read."""

from benchmark import setup_parts


def read(w):
    s = setup_parts.native_startup()
    return None if s is None else s["build_s"] + s["load_s"]
