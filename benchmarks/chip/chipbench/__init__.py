"""The chip benchmark's yardstick: traffic, drivers, reference, reductions.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration in ``configs/<config>.json``, the configuration's model kind
in ``kinds/<kind>.py`` (the weights, the system under test, the payloads,
the comparison with the plain reference, the work counter), its traffic
mix in ``traffic/<mix>.json`` and each per-layer metric's reader in
``metrics/<metric>.py``.  From the program under test the harness takes
only its public serving entry points and the spans and counters they keep.
"""
import os
from pathlib import Path

#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, so only a cell's first run in a checkout compiles (the path is
#: part of the cache key, so it never moves)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def pin_compile_cache() -> None:
    """Give the program the benchmark's cache directory, with no size limit;
    call before JAX is imported (the program keeps its cache where this
    variable says).  A size limit below what one cell compiles evicts every
    entry before its next use: the engine's programs carry the weights as
    constants, megabytes each."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
