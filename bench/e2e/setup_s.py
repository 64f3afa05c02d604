"""Process start to the window's start: JAX's start, the inputs made
from the seed, and one whole warm-up operation (compiling or finding
the cache, filling the page cache)."""


def read(w):
    return w.setup_s
