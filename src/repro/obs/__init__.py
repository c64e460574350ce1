"""repro.obs — unified observability: metrics, tracing, the clock.

The paper's central claim — feature attribution at *minimal overhead over
inference* — is an observability claim.  This package is the single place
where that claim is measured:

  * :mod:`repro.obs.registry` — typed counters / gauges / histograms with
    label sets, a strict-JSON snapshot, and Prometheus-style text
    exposition.  ``repro.serve`` stats, admission shed/degrade counters,
    the ``repro.plan`` tuning-cache hit/miss counters, and the
    ``repro.engine`` build cache all record into ONE default registry, so
    :func:`snapshot` describes the whole process.
  * :mod:`repro.obs.trace` — per-request spans with parent/child links,
    minted at admission and carried through batcher enqueue -> bucket
    dispatch -> engine -> residual-cache lookup, exported as Chrome
    trace-event JSON (Perfetto-loadable).  Each launch's ``batch/*`` span
    and its phases (stack, forward, targets, replay, cache store/gather,
    respond) are scoped spans, mirrored on JAX's profiler, so under
    ``jax.profiler`` they share the device trace's clock; there the Pallas
    kernels carry their family names (``conv2d_fwd``, ``conv2d_bwd``,
    ``vmm_bwd``, ...), and the device trace is the kernel profiler.
    ``python -m repro.obs trace`` replays a synthetic load trace and
    writes the span file.
  * :mod:`repro.obs.clock` — the single injectable monotonic clock every
    serving timestamp reads (``VirtualClock`` conforms), so traces and
    deadlines can never disagree about "now"; and the serving thread's
    CPU and run-queue seconds, which a traced launch records.

ZERO-COST WHEN DISABLED: a server without a tracer uses the shared no-op
span and scope (no allocation, no clock reads).
``benchmarks/attribution_serving`` carries rows enforcing this, gated by
``benchmarks/report.py --check``.
"""
from repro.obs.clock import VirtualClock, monotonic, perf
from repro.obs.jsonsafe import dump_strict, dumps_strict, sanitize
from repro.obs.registry import (Counter, Gauge, Histogram, Registry,
                                default_registry, render_prometheus, reset,
                                snapshot)
from repro.obs.trace import (NULL_SCOPE, NULL_SPAN, NULL_TRACER,
                             RequestTrace, Span, Tracer, integrity_errors,
                             validate_chrome)

__all__ = [
    "VirtualClock", "monotonic", "perf",
    "dump_strict", "dumps_strict", "sanitize",
    "Counter", "Gauge", "Histogram", "Registry", "default_registry",
    "render_prometheus", "reset", "snapshot",
    "NULL_SCOPE", "NULL_SPAN", "NULL_TRACER", "RequestTrace", "Span", "Tracer",
    "integrity_errors", "validate_chrome",
]
