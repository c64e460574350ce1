"""Serving driver on the :mod:`repro.serve` subsystem.

The paper's end goal — "real-time XAI on the edge" — as a service: requests
can ask not just for the next tokens (or class) but for WHY, served from the
same weights with the same sharding.  Two workloads:

  * ``--workload lm``  — token-level LM attribution as a served workload
    (:mod:`repro.lm`): step-wise decode with per-generated-token contrastive
    attribution, then a mixed predict/explain stream through the
    ``ExplanationServer`` on an ``LMAdapter`` — sequence-length-bucketed
    batching, the same admission/deadline knobs as the CNN path, and the
    ``ssm_scan`` chunking plan resolved from ``--device-profile`` before
    anything compiles.  Method choices come from the registry's
    token-capable explainers.
  * ``--workload cnn`` — a mixed predict/explain stream through the
    ``ExplanationServer`` (micro-batching + residual-mask cache): every
    explain that follows a predict for the same request id skips the
    forward pass and replays only the BP phase over the stored 1-/2-bit
    masks (paper §III.F).

``generate`` / ``explain`` stay importable helpers for the LM path.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro import engine as engine_lib
from repro.launch import compile_cache, steps as steps_lib
from repro.models import cnn as cnn_lib, transformer as tf
from repro.obs import Tracer, dumps_strict, snapshot as obs_snapshot
from repro.serve import (AdmissionConfig, CNNAdapter, DegradePolicy,
                         ExplanationServer, Request, ShedError, registry)


def generate(cfg, params, prompt_tokens, *, max_new: int = 16):
    """Greedy decode: prefill + decode_step loop. Returns [B, max_new]."""
    b, s = prompt_tokens.shape
    cache = tf.init_cache(cfg, b, s + max_new + 8)
    prefill = jax.jit(steps_lib.make_prefill_step(cfg))
    decode = jax.jit(steps_lib.make_decode_step(cfg))
    nxt, cache = prefill(params, {"tokens": prompt_tokens}, cache)
    outs = [nxt]
    for i in range(max_new - 1):
        nxt, cache = decode(params, cache, nxt, jnp.asarray(s + i, jnp.int32))
        outs.append(nxt)
    return jnp.concatenate(outs, axis=1)


def explain(cfg, params, prompt_tokens, *, method: str = "saliency"):
    """Per-prompt-token relevance for the model's next-token prediction.

    Built once through the engine (build-cached: repeated calls for the
    same params/method reuse the compiled FP+BP token step).
    """
    eng = engine_lib.build(engine_lib.EngineSpec(
        model=engine_lib.LMModel(params, cfg), method=method))
    logits, scores = eng.explain_tokens({"tokens": prompt_tokens})
    return logits, scores


def check_outcome(tag: str, responses, sheds_at_submit: int,
                  admission) -> None:
    """Fail the run (non-zero exit) when any request errored, or was shed
    without admission control configured to shed it."""
    failed = [r for r in responses if not r.ok and r.error_type != "ShedError"]
    shed = sheds_at_submit + sum(r.error_type == "ShedError"
                                 for r in responses)
    print(f"[serve/{tag}] {len(failed)} errors, {shed} shed")
    if failed:
        r = failed[0]
        raise SystemExit(f"[serve/{tag}] {len(failed)} requests failed; "
                         f"first: {r.uid} {r.error_type}: {r.error}")
    if shed and admission is None:
        raise SystemExit(f"[serve/{tag}] {shed} requests shed with no "
                         f"admission control configured")


def run_lm(args) -> None:
    from repro import lm as lm_lib

    cfg = configs.get_smoke(args.arch)
    params = tf.init(jax.random.PRNGKey(0), cfg)
    # Bare rule-set names (saliency/deconvnet/guided) predate the served
    # token explainers; they map to token_ixg — the historical ixg score
    # reduction — so old invocations keep working through the server path.
    method = (args.method if args.method.startswith("token_")
              else "token_ixg")
    if method != args.method:
        print(f"[serve/lm] --method {args.method} -> {method} "
              f"(LM serving dispatches the registry token explainers)")
    # configure-once, same as the CNN path: the spec resolves the ssm_scan
    # chunking plan for the device profile before anything compiles.
    adapter = lm_lib.LMAdapter(params, cfg, precision=args.precision,
                               device=args.device_profile,
                               autotune=args.autotune)
    eng = adapter.engine
    if eng.plan is not None:
        print(f"[serve/lm] planned ssm_scan tiles for device profile "
              f"{args.device_profile!r}:")
        for line in eng.plan.summary().splitlines()[1:]:
            print(f"  {line.strip()}")

    # step-wise generation + per-generated-token contrastive attribution
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)
    t0 = time.time()
    result = lm_lib.decode(params, cfg, prompts, max_new=args.max_new)
    print(f"[serve/lm] decoded {tuple(result.generated.shape)} in "
          f"{time.time() - t0:.2f}s")
    t0 = time.time()
    per_tok = lm_lib.explain_generated(params, cfg, result, plan=eng.plan)
    print(f"[serve/lm] contrastive per-generated-token attribution "
          f"{tuple(per_tok.shape)} in {time.time() - t0:.2f}s")

    admission = None
    if args.capacity is not None or args.deadline_ms is not None:
        admission = AdmissionConfig(
            capacity=args.capacity if args.capacity is not None else 1024,
            default_deadline_s=(args.deadline_ms / 1e3
                                if args.deadline_ms is not None else None))
    tracer = Tracer() if args.trace_out else None
    server = ExplanationServer(adapter, max_batch=args.batch,
                               max_delay_s=args.max_delay_ms / 1e3,
                               admission=admission, tracer=tracer)
    # mixed predict/explain traffic over ragged prompt lengths: pow2
    # padding buckets equal-length requests into shared launches (the
    # batcher's shape-keyed buckets ARE the sequence buckets)
    rng = np.random.RandomState(2)
    n = args.requests
    reqs = []
    for i in range(n):
        s = int(rng.randint(max(2, args.prompt_len // 2),
                            args.prompt_len + 1))
        toks = np.asarray(lm_lib.pad_tokens(
            rng.randint(0, cfg.vocab, size=(s,)).astype(np.int32)))
        reqs.append(Request(uid=f"q{i}", kind="predict", x=toks))
        reqs.append(Request(uid=f"q{i}", kind="explain", x=toks,
                            method=method))
    buckets = sorted({req.x.shape[-1] for req in reqs})
    t0 = time.time()
    responses = []
    sheds = 0
    for req in reqs:                  # serve()'s dict collapses uids; keep all
        try:
            server.submit(req)
        except ShedError:             # admission refusal: typed, never a stall
            sheds += 1
            continue
        responses.extend(server.poll())
    responses.extend(server.drain())
    dt = time.time() - t0
    print(f"[serve/lm] {len(responses)} responses in {dt:.2f}s "
          f"({len(responses) / dt:.1f} req/s); sequence buckets {buckets}")
    if admission is not None:
        snap = server.stats.snapshot()
        print(f"[serve/lm] admission: {sheds} shed at submit "
              f"(by reason {snap['sheds']}), "
              f"peak queue {snap['peak_queue_depth']}")
    for resp in responses:
        if resp.kind == "explain" and resp.ok:
            top = np.argsort(-np.abs(np.asarray(resp.relevance)))[:5]
            print(f"  {resp.uid}: most relevant prompt positions "
                  f"{top.tolist()}")
            break
    for name, snap in server.stats.snapshot()["methods"].items():
        print(f"  {name:28s} n={snap['count']:3d} p50={snap['p50_us']:.0f}us "
              f"p99={snap['p99_us']:.0f}us hit_rate={snap['hit_rate']:.2f}")
    if tracer is not None:
        tracer.finish()
        tracer.save(args.trace_out)
        print(f"[serve/lm] trace: {len(tracer.spans)} spans -> "
              f"{args.trace_out} (load in https://ui.perfetto.dev)")
    if args.metrics:
        print("[serve/lm] unified metrics snapshot:")
        print(dumps_strict(obs_snapshot(), indent=2))
    check_outcome("lm", responses, sheds, admission)


def run_cnn(args) -> None:
    cfg = cnn_lib.CNNConfig()
    params = cnn_lib.init(jax.random.PRNGKey(0), cfg)
    # configure-once: the spec decides precision x store-rules x backend x
    # device tile plan; the server/adapter only ever execute the built
    # engine.
    eng = engine_lib.build(engine_lib.EngineSpec(
        model=engine_lib.CNNModel(params, cfg), method="saliency",
        precision=args.precision, device=args.device_profile,
        autotune=args.autotune))
    if eng.n_shards > 1:
        print(f"[serve/cnn] mesh-sharded engine: {eng.n_shards} shards, "
              f"batcher fills {args.batch * eng.n_shards} seats/launch")
    if eng.plan is not None:
        print(f"[serve/cnn] planned tiles for device profile "
              f"{args.device_profile!r}:")
        for line in eng.plan.summary().splitlines()[1:]:
            print(f"  {line.strip()}")
    admission = None
    if args.capacity is not None or args.deadline_ms is not None:
        degrade = None
        if args.degrade_pressure is not None:
            # above the occupancy threshold: collapse top-K panels to argmax
            # and reroute float explains to the int16 sibling engine
            degrade = DegradePolicy(
                pressure_threshold=args.degrade_pressure,
                reroute_precision=("fxp16" if args.precision == "f32"
                                   else None))
        admission = AdmissionConfig(
            capacity=args.capacity if args.capacity is not None else 1024,
            default_deadline_s=(args.deadline_ms / 1e3
                                if args.deadline_ms is not None else None),
            degrade=degrade)
    tracer = Tracer() if args.trace_out else None
    # perturbation fan-out knob: lime/rise sample counts ride method_opts
    # (occlusion's fan-out is geometric — window/stride opts instead)
    method_opts = {}
    if args.perturb_samples is not None:
        method_opts = {m: {"n_samples": args.perturb_samples}
                       for m in ("lime", "rise")}
    server = ExplanationServer(CNNAdapter.from_engine(eng),
                               max_batch=args.batch,
                               max_delay_s=args.max_delay_ms / 1e3,
                               method_opts=method_opts,
                               admission=admission, tracer=tracer)
    n = args.requests
    xs = jax.random.normal(jax.random.PRNGKey(1), (n,) + cfg.in_hw
                           + (cfg.in_ch,))
    cls = registry.get(args.method)
    reqs = []
    for i in range(n):
        reqs.append(Request(uid=f"q{i}", kind="predict", x=xs[i]))
        reqs.append(Request(
            uid=f"q{i}", kind="explain", x=xs[i], method=args.method,
            topk=args.topk if (i % 2 and cls.mask_reuse) else None,
            key=jax.random.PRNGKey(100 + i) if cls.needs_key else None))
    t0 = time.time()
    responses = []
    sheds = 0
    for req in reqs:                  # serve()'s dict collapses uids; keep all
        try:
            server.submit(req)
        except ShedError:             # admission refusal: typed, never a stall
            sheds += 1
            continue
        responses.extend(server.poll())
    responses.extend(server.drain())
    dt = time.time() - t0
    n_explain = sum(r.kind == "explain" for r in responses)
    hits = sum(r.cache_hit for r in responses)
    print(f"[serve/cnn] {len(responses)} responses in {dt:.2f}s "
          f"({len(responses) / dt:.1f} req/s); cache hits "
          f"{hits}/{n_explain} explains")
    if admission is not None:
        snap = server.stats.snapshot()
        print(f"[serve/cnn] admission: {sheds} shed at submit "
              f"(by reason {snap['sheds']}), degrades {snap['degrades']}, "
              f"peak queue {snap['peak_queue_depth']}")
    print(f"[serve/cnn] cache: {server.cache.stats.snapshot()}")
    for name, snap in server.stats.snapshot()["methods"].items():
        print(f"  {name:28s} n={snap['count']:3d} p50={snap['p50_us']:.0f}us "
              f"p99={snap['p99_us']:.0f}us hit_rate={snap['hit_rate']:.2f}")
    if tracer is not None:
        tracer.finish()
        tracer.save(args.trace_out)
        print(f"[serve/cnn] trace: {len(tracer.spans)} spans -> "
              f"{args.trace_out} (load in https://ui.perfetto.dev)")
    if args.metrics:
        print("[serve/cnn] unified metrics snapshot:")
        print(dumps_strict(obs_snapshot(), indent=2))
    check_outcome("cnn", responses, sheds, admission)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lm", choices=["lm", "cnn"])
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--topk", type=int, default=3)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    # heavy-traffic hardening knobs (cnn workload); setting either of the
    # first two enables admission control on the server
    ap.add_argument("--capacity", type=int, default=None,
                    help="bounded admission queue: requests beyond this "
                         "many pending are shed with a typed error")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency budget; infeasible or "
                         "expired requests are shed, never silently late")
    ap.add_argument("--degrade-pressure", type=float, default=None,
                    help="queue occupancy in (0,1] above which explains "
                         "degrade (topk->argmax; f32 reroutes to the int16 "
                         "sibling) instead of shedding")
    # method lists derive from the registry: a newly registered explainer
    # is immediately servable without touching this file.
    ap.add_argument("--method", default="saliency", choices=registry.names())
    ap.add_argument("--perturb-samples", type=int, default=None,
                    help="cnn workload: mask fan-out N for the stochastic "
                         "perturbation explainers (lime/rise) — folded "
                         "into the batch axis as [N*B, ...] forwards")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "fxp16"],
                    help="cnn workload numeric path; fxp16 = true int16 "
                         "fixed-point kernels (paper §IV)")
    from repro.plan import profile_names
    ap.add_argument("--device-profile", default=None,
                    help="plan kernel tiles (cnn: conv/vmm; lm: ssm_scan "
                         "chunking) for this "
                         "repro.plan device profile before compiling "
                         f"(one of {profile_names()}, e.g. edge-small = "
                         "2MB on-chip budget; or 'mesh:<profile>:<n>' for "
                         "a mesh-sharded engine whose batcher fills "
                         "max_batch x n seats per launch)")
    ap.add_argument("--autotune", action="store_true",
                    help="refine the tile plan by measured timings "
                         "(persisted in the repro.plan tuning cache)")
    # observability (cnn workload): both are opt-in; the server runs
    # on no-op singletons otherwise (zero-cost guarantee)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto-loadable Chrome trace-event "
                         "JSON of every request's admission -> queued -> "
                         "engine -> cache spans")
    ap.add_argument("--metrics", action="store_true",
                    help="print the unified repro.obs metrics snapshot "
                         "(serve + plan-cache + engine-cache series)")
    args = ap.parse_args()
    compile_cache.enable()

    if args.workload == "lm":
        if args.method not in registry.token_methods():
            raise SystemExit(
                f"--workload lm supports token-capable methods "
                f"{registry.token_methods()}; got {args.method!r}")
        if args.precision == "fxp16":
            raise SystemExit("--workload lm has no int16 fixed-point path "
                             "(token attribution needs float gradients)")
        run_lm(args)
    else:
        run_cnn(args)


if __name__ == "__main__":
    main()
