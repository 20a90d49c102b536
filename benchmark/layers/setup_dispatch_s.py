"""Set-up's seconds inside the program's `sync.dispatch` spans (the served prefill, load and warm-up, up to the enqueue of their last program; the wait in `block_until_ready` is the harness's): the phase recorder's total of the stage less the window's delta. The builds made inside those spans are in it; the build journal says which, so that with `build_trace_s`, `build_lower_s`, `build_compile_s` and `build_cache_load_s` it says what the dispatches cost once their programs exist. A program without the build parts has nothing to read."""

from benchmark import setup_parts


def read(w):
    return setup_parts.setup_dispatch_s(w)
