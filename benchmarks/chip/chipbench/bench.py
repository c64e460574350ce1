"""One run of a cell: prepare, warm up, measure a window, compare.

``run.py`` runs this once per process; ``calibrate.py`` and ``sweep.py``
prepare once and measure many windows in one process.
"""
from __future__ import annotations

import functools
import gc
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, List, Optional, Tuple

from chipbench import cell as cell_lib
from chipbench import compare, drive, tracing, traffic
from chipbench.context import RunContext, launches_from_spans
from chipbench.stats import latencies_with_misses, percentile

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]

#: JAX monitoring event of a program lowered (a jit cache miss)
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: JAX monitoring event of a program compiled by the backend (cache miss)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _import_program():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise cell_lib.RefusedError(f"the program is not at {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(1, str(src))


class _Lowerings:
    """Counts the programs JAX lowers (an in-memory jit miss) and compiles
    (a persistent-cache miss), and names those lowered while ``on``: a
    program lowered inside the window is a compile inside the window."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        self.names = []
        self.lower_s = self.compile_s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if event == _LOWERING_EVENT:
            self.lower_s += duration
            if self.on:
                self.count += 1
                self.names.append(str(kw.get("fun_name", "?")))
        elif event == _COMPILE_EVENT:
            self.compile_s += duration
            self.compiles += 1


def end_to_end(cell, window: drive.Window, setup_s: float):
    """The cell's end-to-end metrics from the window's records."""
    miss = window.miss_latency_s()

    def pct(kind, q):
        recs = window.records(kind)
        lat = latencies_with_misses([r.latency_s for r in recs if r.ok],
                                    sum(not r.ok for r in recs), miss)
        v = percentile(lat, q)
        return None if v is None else 1e3 * v

    # The rate is every explain the window's sessions asked for (none is
    # started after the close) over the time from the window's start to
    # the last answer.  A closed loop's clients move in rounds of a few
    # seconds, and a count cut at the close would take the last round
    # whole or not at all.
    done = [r.done_t for r in window.records(drive.EXPLAIN) if r.ok]
    values = {
        "explain_p50_ms": lambda: pct(drive.EXPLAIN, 50),
        "predict_p50_ms": lambda: pct(drive.PREDICT, 50),
        "explains_per_s": lambda: (len(done) / (max(done) - window.t0)
                                   if done else None),
        "setup_s": lambda: setup_s,
    }
    out = {}
    for m in cell.end_to_end:
        v = values[m["name"]]()
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def per_layer(cell, ctx: RunContext, harness_dir: Path):
    out = {}
    for m in cell.per_layer:
        v = cell_lib.metric_reader(m["name"], harness_dir)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            log(f"per-layer metric {m['name']}: nothing to read")
    return out


def _tails(window: drive.Window) -> str:
    out = []
    for kind in (drive.EXPLAIN, drive.PREDICT):
        lat = sorted(r.latency_s for r in window.records(kind))
        if lat:
            out.append(f"{kind} p95 {1e3 * percentile(lat, 95):.3f} ms, "
                       f"p99 {1e3 * percentile(lat, 99):.3f} ms")
    return "; ".join(out)


def _lateness(window: drive.Window) -> str:
    late = sorted(r.late_s for r in window.records())
    if not late:
        return "no requests"
    return (f"p50 {1e3 * percentile(late, 50):.3f} ms, p99 "
            f"{1e3 * percentile(late, 99):.3f} ms, max {1e3 * late[-1]:.3f} ms")


@dataclass
class Prepared:
    """A cell ready to measure: the device checked, the weights made, the
    system under test built through the cell's model kind."""
    cell: cell_lib.Cell
    harness_dir: Path
    device: dict
    peak: dict
    params: Any
    adapter: Any
    devices: list

    @property
    def model(self) -> dict:
        return self.cell.config["model"]

    @property
    def kind(self):
        return self.cell.kind

    def plan(self, seed: int, seconds: float, mix: Optional[dict] = None):
        return traffic.make_plan(mix or self.cell.mix, seed, seconds,
                                 functools.partial(self.kind.payloads,
                                                   self.model))


_LOWERED: Optional[_Lowerings] = None


def lowerings() -> _Lowerings:
    global _LOWERED
    if _LOWERED is None:
        _LOWERED = _Lowerings()
    return _LOWERED


def prepare(workload: str, *, bench_file: Path = ROOT / "BENCHMARK.json",
            harness_dir: Path = HERE,
            require_accelerator: bool = True) -> Prepared:
    """Refuse a machine the cell cannot stand on (RefusedError), point
    JAX's compile cache inside the checkout, make the weights, build."""
    _import_program()
    cell = cell_lib.load(workload, bench_file, harness_dir)
    import jax
    devices = jax.devices()
    peaks = cell_lib.load_peaks(Path(harness_dir) / "peaks.json")
    if require_accelerator:
        device = cell_lib.check_devices(devices, cell.chips, peaks)
        from repro.launch import compile_cache
        compile_cache.enable()
    else:
        d0 = devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devices)}
    lowerings()
    config = cell.config
    t = time.monotonic()
    params = jax.block_until_ready(
        cell.kind.init_params(config["model"], config["weight_seed"]))
    t_params = time.monotonic() - t
    adapter = cell.kind.build_adapter(config, params, cell.chips)
    log(f"weights made in {t_params:.3f} s, engine built in "
        f"{time.monotonic() - t - t_params:.3f} s")
    return Prepared(cell=cell, harness_dir=Path(harness_dir), device=device,
                    peak=peaks.get(device["kind"], {}), params=params,
                    adapter=adapter, devices=devices[:max(cell.chips, 1)])


def _cache_stats() -> str:
    import jax
    from repro.launch import compile_cache
    st = compile_cache.stats()
    return (f"{st['hits']} hits, {st['misses']} misses at "
            f"{jax.config.jax_compilation_cache_dir}")


def warm(prep: Prepared, plan: traffic.Plan) -> int:
    from repro.serve import ExplanationServer
    n = drive.warm_up(ExplanationServer(prep.adapter), prep.cell.mix, plan,
                      prep.kind.warm_payloads)
    gc.collect()
    gc.freeze()
    return n


class _GcPauses:
    """Python's garbage-collector pauses while ``on``: (generation,
    seconds) per collection."""

    def __init__(self):
        self.pauses: List[Tuple[int, float]] = []
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def summary(self) -> str:
        if not self.pauses:
            return "no collections"
        full = sum(g == 2 for g, _ in self.pauses)
        return (f"{len(self.pauses)} collections ({full} full), "
                f"{sum(d for _, d in self.pauses):.4f} s in all, longest "
                f"{max(d for _, d in self.pauses):.4f} s")


@dataclass
class Measured:
    window: drive.Window
    server: Any
    window_s: float
    lowered: int
    trace_dir: Optional[str] = None


def measure(prep: Prepared, plan: traffic.Plan, seconds: float,
            trace: bool) -> Measured:
    """One window through a fresh server (with the program's tracer and
    the profiler on when ``trace``)."""
    import jax
    from repro.obs.trace import Tracer
    from repro.serve import ExplanationServer
    server = ExplanationServer(prep.adapter,
                               tracer=Tracer() if trace else None)
    window = drive.Window(server, plan, seconds, annotate=trace)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tracing.capture_options())
    low = lowerings()
    before = low.count
    t0 = time.monotonic()
    low.on = True
    with _GcPauses() as pauses:
        window.run()
    low.on = False
    window_s = time.monotonic() - t0
    if trace:
        jax.profiler.stop_trace()
    if low.count > before:
        log(f"programs lowered inside the window: "
            f"{sorted(set(low.names[before:]))}")
    log(f"garbage collection in the window: {pauses.summary()}; longest "
        f"poll {window.longest_poll_s:.4f} s")
    return Measured(window=window, server=server, window_s=window_s,
                    lowered=low.count - before, trace_dir=trace_dir)


def memory_peak_bytes(prep: Prepared) -> int:
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in prep.devices))


def reduce_trace(prep: Prepared, m: Measured, dump_dir: Optional[Path] = None):
    """-> (per-layer metrics, busy_s, breakdown) of a traced window."""
    xplane = tracing.xplane_file(m.trace_dir)
    if dump_dir is not None:
        Path(dump_dir).mkdir(parents=True, exist_ok=True)
        (Path(dump_dir) / f"{prep.cell.name}.trace_summary.json").write_text(
            json.dumps(tracing.summarize(xplane), indent=1))
    dtrace = tracing.load(xplane, m.window_s)
    shutil.rmtree(m.trace_dir, ignore_errors=True)
    ctx = RunContext(model=prep.model, precision=prep.cell.config["precision"],
                     chips=prep.cell.chips, shards=prep.adapter.n_shards,
                     peak=prep.peak, window=m.window, server=m.server,
                     spans=list(m.server.tracer.spans), trace=dtrace,
                     flops=prep.kind.flops)
    ctx.launches = launches_from_spans(ctx.spans, m.window,
                                       m.server.batcher.fill_target)
    metrics = per_layer(prep.cell, ctx, prep.harness_dir)
    breakdown = {"device_ops": tracing.top_ops(dtrace),
                 "idle_gaps": tracing.idle_gaps(dtrace)}
    return metrics, tracing.busy_s(dtrace), breakdown


def served_answers(prep: Prepared, m: Measured, plan: traffic.Plan):
    return [prep.kind.served(r.kind, plan.payload(r.session), r.resp)
            for r in m.window.records() if r.ok]


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, bench_file: Path = ROOT / "BENCHMARK.json",
        harness_dir: Path = HERE, require_accelerator: bool = True,
        dump_dir: Optional[Path] = None) -> dict:
    """The whole run ``run.py`` makes: see its docstring."""
    prep = prepare(workload, bench_file=bench_file, harness_dir=harness_dir,
                   require_accelerator=require_accelerator)
    plan = prep.plan(seed, seconds)
    t_warm = time.monotonic()
    warmed = warm(prep, plan)
    setup_s = time.monotonic() - t_start
    log(f"prepared in {t_warm - t_start:.3f} s, warmed up in "
        f"{setup_s - (t_warm - t_start):.3f} s")
    device = dict(prep.device)
    log(f"{workload}: set-up {setup_s:.3f} s ({warmed} warm-up requests); "
        f"{device['count']} x {device['platform']} ({device['kind']})")
    low = lowerings()
    log(f"set-up compiles: {low.lower_s:.3f} s lowering, {low.compiles} "
        f"programs compiled in {low.compile_s:.3f} s, persistent cache "
        f"{_cache_stats()}")

    m = measure(prep, plan, seconds, trace)
    device["memory_peak_bytes"] = memory_peak_bytes(prep)
    recs = m.window.records()
    failed = sum(not r.ok for r in recs)
    log(f"window: {len(recs)} requests sent, {failed} failed, "
        f"{len(m.window.records(drive.EXPLAIN))} explains; generator "
        f"lateness {_lateness(m.window)}; programs lowered inside the "
        f"window: {m.lowered}")
    log(f"tails: {_tails(m.window)}")
    for r in recs:
        if not r.ok:
            log(f"first failed request {r.uid}/{r.kind}: {r.error}")
            break

    result = {"correct": False, "attempted": len(recs), "failed": failed}
    if trace:
        metrics, busy, breakdown = reduce_trace(prep, m, dump_dir)
        result["metrics"] = metrics
        result["breakdown"] = breakdown
        device["busy_s"] = busy
        device["window_s"] = m.window_s
    else:
        result["metrics"] = end_to_end(prep.cell, m.window, setup_s)
    result["device"] = device
    del recs

    # every answer of the window against the reference, once the
    # program's state is gone
    t_ref = time.monotonic()
    low_s, compiled = low.lower_s + low.compile_s, low.compiles
    ok, checks, values, n = assess(prep, m, plan)
    result["correct"] = ok
    result["checks"] = checks
    log(f"reference compared {n} answers in {time.monotonic() - t_ref:.3f}"
        f" s ({low.compiles - compiled} programs compiled, "
        f"{low.lower_s + low.compile_s - low_s:.3f} s lowering and "
        f"compiling); all readings: "
        + ", ".join(f"{k}={v:.6g}" for k, v in values.items()))
    for name, c in checks.items():
        log(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    return result


def assess(prep: Prepared, m: Measured, plan: traffic.Plan):
    """Compare every answer of a window with the reference, after dropping
    the server: -> (correct, checks, all readings, answers compared).  A
    failed request, or a window with no answer, is not correct."""
    failed = sum(not r.ok for r in m.window.records())
    served = served_answers(prep, m, plan)
    m.server = m.window = None
    gc.unfreeze()
    gc.collect()
    values = (prep.kind.numbers(prep.params, prep.model, served)
              if served else {})
    ok, checks = compare.judge(values, prep.cell.config.get("limits", {}))
    checks["failed_requests"] = {"value": failed, "limit": 0}
    return bool(ok and failed == 0 and served), checks, values, len(served)
