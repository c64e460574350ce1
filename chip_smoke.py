#!/usr/bin/env python3
"""Chip smoke test: the paper CNN's explanation service on a TPU.

Drives the main path once, through the entry points a user calls:

    EngineSpec -> repro.engine.build -> CNNAdapter.from_engine
               -> ExplanationServer

at the full Table III width (``CNNConfig()``: 32x32x3 input, convs
32/32/64/64, FC 4096->128->10) with random weights from ``--seed``.  Every
Pallas kernel runs compiled for the chip; the script refuses to run
anywhere else.

One chip (no arguments), per precision f32, bf16, fxp16:

* predict-then-explain sessions through the server, saliency / deconvnet /
  guided, top-3 panels on half of them; every response must be ``ok``,
  nothing shed, and explains after a predict must hit the residual cache;
* the engine's explain (BP replay) program must contain ``tpu_custom_call``;
* logits and per-method heatmaps are compared with a plain reference: the
  ``lax`` CNN (``use_pallas=False``, ``backward="vjp"``) under
  ``jax.default_matmul_precision("highest")``.  f32: normalised-heatmap
  max abs error <= 1e-3.  bf16 / fxp16: per method, the mean Spearman of
  the served heatmaps against it >= 0.95 (the worst one is printed).

``--chips 4``: only the mesh-sharded engine (``mesh:tpu-v5e:4``) served
through the server, and the same requests on a one-device engine, per
precision f32, bf16, fxp16; the mesh must span 4 devices and the heatmaps
must agree.

Printed times are smoke timings from the host clock, not benchmark
figures.  The last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Any failing phase exits non-zero without that line.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the four-chip sharded path
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

METHODS = ("saliency", "deconvnet", "guided")
PAIRS = 16                        # predict-then-explain sessions per run
MAX_BATCH = 8                     # server micro-batch
TOPK = 3
F32_HEATMAP_TOL = 1e-3
LOW_PRECISION_SPEARMAN = 0.95
F32_LOGIT_RTOL = 1e-3
LOW_PRECISION_LOGIT_RTOL = 5e-2
MESH_HEATMAP_TOL = 1e-5


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _import_repo():
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        raise SmokeFailure(f"{src / 'repro'} not found: run chip_smoke.py "
                           f"from a checkout of the repository")
    sys.path.insert(0, str(src))


def _requests(xs, tag: str):
    """Predicts for every example, then explains of the same uids:
    methods round-robin, a top-3 panel on every odd pair."""
    from repro.serve import Request
    predicts = [Request(uid=f"{tag}{i}", kind="predict", x=xs[i])
                for i in range(len(xs))]
    explains = [Request(uid=f"{tag}{i}", kind="explain", x=xs[i],
                        method=METHODS[i % len(METHODS)],
                        topk=TOPK if i % 2 else None)
                for i in range(len(xs))]
    return predicts, explains


def _serve(server, reqs):
    """Submit a wave and drain it: every submit must be admitted."""
    from repro.serve import ShedError
    for r in reqs:
        try:
            server.submit(r)
        except ShedError as e:
            raise SmokeFailure(f"request {r.uid} shed at submit: {e}")
    return server.drain()


def _session(server, xs, tag: str):
    """Predict wave then explain wave; returns (predicts, explains) by uid."""
    predicts, explains = _requests(xs, tag)
    out_p = _serve(server, predicts)
    out_e = _serve(server, explains)
    bad = [r for r in out_p + out_e if not r.ok]
    if bad:
        raise SmokeFailure(f"{len(bad)} failed responses, first: "
                           f"{bad[0].uid} {bad[0].error_type}: {bad[0].error}")
    check(len(out_p) == len(predicts) and len(out_e) == len(explains),
          "missing responses")
    return ({r.uid: r for r in out_p}, {r.uid: r for r in out_e})


def _panel_heatmaps(resp):
    """Response relevance -> [K, H, W] normalised heatmaps (K = targets)."""
    import numpy as np
    from repro.core import attribution
    rel = np.asarray(resp.relevance, np.float32)
    if rel.ndim == 3:
        rel = rel[None]
    return np.asarray(attribution.heatmap(rel))


class Reference:
    """The plain f32 reference: lax CNN, jax.vjp, highest matmul precision.
    Results are memoised per (example, method, target)."""

    def __init__(self, params, cfg, xs):
        import jax
        from repro import engine as engine_lib
        self.jax = jax
        self.xs = xs
        model = engine_lib.CNNModel(params, cfg, use_pallas=False)
        self.engines = {m: engine_lib.build(engine_lib.EngineSpec(
            model=model, method=m, backward="vjp")) for m in METHODS}
        self.memo = {}

    def explain(self, i: int, method: str, target: int):
        import numpy as np
        from repro.core import attribution
        key = (i, method, target)
        if key not in self.memo:
            jax = self.jax
            with jax.default_matmul_precision("highest"):
                logits, rel = self.engines[method].explain(
                    self.xs[i:i + 1], target=jax.numpy.asarray([target]))
            self.memo[key] = (np.asarray(logits[0]),
                              np.asarray(attribution.heatmap(rel))[0])
        return self.memo[key]

    def logits(self, i: int):
        return self.explain(i, "saliency", 0)[0]


def _compare(precision, predicts, explains, ref, tag):
    """Max heatmap error (f32) or mean Spearman (bf16/fxp16) vs reference."""
    import numpy as np
    from repro.core import fidelity
    worst_logit = 0.0
    for i in range(len(ref.xs)):
        got = np.asarray(predicts[f"{tag}{i}"].logits, np.float32)
        want = ref.logits(i)
        worst_logit = max(worst_logit, float(
            np.abs(got - want).max() / max(np.abs(want).max(), 1.0)))
    per_method = {m: [] for m in METHODS}
    for i in range(len(ref.xs)):
        resp = explains[f"{tag}{i}"]
        maps = _panel_heatmaps(resp)
        check(len(resp.targets) == maps.shape[0],
              f"{resp.uid}: {len(resp.targets)} targets, {maps.shape} maps")
        for k, t in enumerate(resp.targets):
            want = ref.explain(i, resp.method, int(t))[1]
            if precision == "f32":
                per_method[resp.method].append(
                    float(np.abs(maps[k] - want).max()))
            else:
                per_method[resp.method].append(
                    fidelity.spearman(maps[k], want))
    if precision == "f32":
        errs = {m: max(v) for m, v in per_method.items()}
        log(f"{precision}: logits max rel err {worst_logit:.3e}; "
            f"heatmap max abs err vs reference "
            + ", ".join(f"{m}={e:.3e}" for m, e in errs.items()))
        check(worst_logit <= F32_LOGIT_RTOL,
              f"f32 logits rel err {worst_logit} > {F32_LOGIT_RTOL}")
        check(all(e <= F32_HEATMAP_TOL for e in errs.values()),
              f"f32 heatmap error above {F32_HEATMAP_TOL}: {errs}")
    else:
        mean = {m: sum(v) / len(v) for m, v in per_method.items()}
        log(f"{precision}: logits max rel err {worst_logit:.3e}; "
            f"heatmap Spearman vs f32 reference (mean / worst) "
            + ", ".join(f"{m}={mean[m]:.4f}/{min(v):.4f}"
                        for m, v in per_method.items()))
        check(worst_logit <= LOW_PRECISION_LOGIT_RTOL,
              f"{precision} logits rel err {worst_logit} > "
              f"{LOW_PRECISION_LOGIT_RTOL}")
        check(all(r >= LOW_PRECISION_SPEARMAN for r in mean.values()),
              f"{precision} mean Spearman below {LOW_PRECISION_SPEARMAN}: "
              f"{mean}")


def _explain_program_has_kernels(eng, x):
    """The engine's compiled BP replay program runs the Pallas kernels."""
    import jax
    logits, residuals = eng.forward(x)
    seeds = jax.numpy.zeros((TOPK,) + logits.shape, jax.numpy.float32)
    text = eng.backend.backward.lower(residuals, seeds).as_text()
    n = text.count("tpu_custom_call")
    log(f"explain program: {n} tpu_custom_call sites")
    check(n > 0, "explain program holds no tpu_custom_call")


def run_one_chip(params, cfg, xs):
    from repro import engine as engine_lib
    from repro.serve import CNNAdapter, ExplanationServer
    ref = Reference(params, cfg, xs)
    for precision in ("f32", "bf16", "fxp16"):
        t0 = time.perf_counter()
        eng = engine_lib.build(engine_lib.EngineSpec(
            model=engine_lib.CNNModel(params, cfg), method="saliency",
            precision=precision, device="detected"))
        server = ExplanationServer(CNNAdapter.from_engine(eng),
                                   max_batch=MAX_BATCH)
        predicts, explains = _session(server, xs, "a")
        cold_s = time.perf_counter() - t0
        hits = sum(r.cache_hit for r in explains.values())
        log(f"{precision}: {len(predicts)} predicts + {len(explains)} "
            f"explains served, 0 errors, 0 shed, explain cache hits "
            f"{hits}/{len(explains)} (plan device {eng.plan.device})")
        check(hits > 0, f"{precision}: no explain hit the residual cache")
        _explain_program_has_kernels(eng, xs[:MAX_BATCH])
        _compare(precision, predicts, explains, ref, "a")
        t0 = time.perf_counter()
        _session(server, xs, "b")
        warm_s = time.perf_counter() - t0
        n = 2 * len(xs)
        log(f"{precision}: smoke timing (host clock, not a benchmark "
            f"figure): first session {cold_s:.2f}s incl. compile; repeat "
            f"session {warm_s:.3f}s = {1e3 * warm_s / n:.2f} ms/request")


def run_four_chips(params, cfg, xs):
    for precision in ("f32", "bf16", "fxp16"):
        _four_chips_at(params, cfg, xs, precision)


def _four_chips_at(params, cfg, xs, precision):
    import numpy as np
    from repro import engine as engine_lib
    from repro.serve import CNNAdapter, ExplanationServer
    model = engine_lib.CNNModel(params, cfg)
    out = {}
    for device in ("mesh:tpu-v5e:4", "tpu-v5e"):
        t0 = time.perf_counter()
        eng = engine_lib.build(engine_lib.EngineSpec(
            model=model, method="saliency", precision=precision,
            device=device))
        if device.startswith("mesh:"):
            size = 0 if eng.mesh is None else eng.mesh.devices.size
            check(size == 4, f"mesh engine spans {size} devices")
            logits, _ = eng.forward(xs[:4 * MAX_BATCH])
            placed = len(logits.sharding.device_set)
            log(f"{precision} {device}: mesh over {eng.mesh.devices.size} "
                f"devices {eng.mesh.axis_names}, forward logits "
                f"{logits.dtype} placed on {placed} devices")
            check(placed == 4, f"sharded logits on {placed} devices")
        server = ExplanationServer(CNNAdapter.from_engine(eng),
                                   max_batch=MAX_BATCH)
        predicts, explains = _session(server, xs, "m")
        hits = sum(r.cache_hit for r in explains.values())
        check(hits > 0, f"{device}: no explain hit the residual cache")
        log(f"{precision} {device}: {len(predicts)} predicts + "
            f"{len(explains)} explains, 0 errors, cache hits "
            f"{hits}/{len(explains)}; smoke timing "
            f"{time.perf_counter() - t0:.2f}s incl. compile")
        out[device] = (predicts, explains)
    (mp, me), (sp, se) = out["mesh:tpu-v5e:4"], out["tpu-v5e"]
    worst_h = dict.fromkeys(METHODS, 0.0)
    worst_l = 0.0
    for uid in se:
        check(me[uid].targets == se[uid].targets,
              f"{uid}: targets {me[uid].targets} vs {se[uid].targets}")
        m = se[uid].method
        worst_h[m] = max(worst_h[m], float(np.abs(
            _panel_heatmaps(me[uid]) - _panel_heatmaps(se[uid])).max()))
        worst_l = max(worst_l, float(np.abs(
            np.asarray(mp[uid].logits, np.float32)
            - np.asarray(sp[uid].logits, np.float32)).max()))
    log(f"{precision} 4-shard vs 1-device: logits max abs diff "
        f"{worst_l:.3e}, heatmap max abs diff "
        + ", ".join(f"{m}={e:.3e}" for m, e in worst_h.items()))
    check(max(worst_h.values()) <= MESH_HEATMAP_TOL,
          f"{precision} mesh heatmaps differ by more than "
          f"{MESH_HEATMAP_TOL}: {worst_h}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args()
    try:
        _import_repo()
        from repro.launch import compile_cache
        cache_dir = compile_cache.enable()
        import jax
        devices = jax.devices()
        platform, kind = devices[0].platform, devices[0].device_kind
        log(f"devices: {len(devices)} x {platform} ({kind})")
        check(platform == "tpu", f"no TPU: JAX found {platform}")
        check(len(devices) >= args.chips,
              f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}")
        from repro.models import cnn
        from repro.plan import detect
        log(f"device profile: {detect()}")
        log(f"compile cache: {cache_dir}")
        cfg = cnn.CNNConfig()
        params = cnn.init(jax.random.PRNGKey(args.seed), cfg)
        xs = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                               (PAIRS,) + cfg.in_hw + (cfg.in_ch,))
        t0 = time.perf_counter()
        if args.chips == 4:
            run_four_chips(params, cfg, xs)
        else:
            run_one_chip(params, cfg, xs)
        stats = compile_cache.stats()
        log(f"compile cache: {stats['hits']} hits, {stats['misses']} misses; "
            f"all phases {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        log(f"FAIL: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
