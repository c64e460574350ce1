"""Suite-wide config: device pinning, tier markers, bitwise conventions.

Tier markers
------------
``tier1`` (default) vs ``slow`` (hypothesis property sweeps, end-to-end
system tests) — see ``pytest.ini``.  Unmarked tests are auto-marked
``tier1`` below, so ``-m tier1`` and the default ``-m "not slow"``
selection agree.

Bitwise-comparison convention (jit vs eager)
--------------------------------------------
Bit-exact assertions compare SAME-PROGRAM outputs only:

* jitted-vs-jitted of the same function: bitwise equality is required —
  XLA programs are deterministic for fixed inputs on one host.
* jitted-vs-eager (or two differently fused float programs): compare with
  a small tolerance (f32: ~1e-6); XLA fuses the eager op-by-op chain
  differently, shifting f32 results by ~1 ulp.
* the int16 fixed-point kernels are EXEMPT from the float caveat —
  integer arithmetic has no fusion sensitivity, so jit-vs-eager is also
  bitwise (``tests/test_kernels_fxp.py`` asserts both, keeping the eager
  comparison tolerance-based per this convention anyway).
"""
import os

# Tests run on the CPU, split into four virtual devices so mesh-sharded
# engines (``mesh:<profile>:4``) run a real 4-way mesh here; unsharded code
# uses device 0.  The 512-device override is strictly dry-run-only (see
# launch/dryrun.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""),
     "--xla_force_host_platform_device_count=4"]).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


def pytest_collection_modifyitems(items):
    """Every test not explicitly marked ``slow`` is tier1 by default."""
    for item in items:
        if item.get_closest_marker("slow") is None:
            item.add_marker(pytest.mark.tier1)
