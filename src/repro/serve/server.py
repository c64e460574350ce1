"""The dispatch loop: admission -> registry -> micro-batcher -> engine -> stats.

``ExplanationServer`` is the subsystem's front door.  Requests go in via
:meth:`submit`; :meth:`poll` pops every micro-batch that is full or past its
latency deadline and runs it:

  * **predict** batches run the adapter's residual-returning forward; each
    request's packed masks are parked in the LRU residual cache under its
    ``uid``.
  * **explain** batches split into cache **hits** — a pure-BP method with a
    cached predict for the same ``uid``: the forward pass is skipped and all
    hits in the bucket backpropagate together through ONE seed-batched fused
    launch over the stored masks — and **colds**: pure-BP methods re-run the
    same residual forward + fused BP programs (warming the cache), composite
    methods dispatch through the registry explainer (exactly the direct
    :mod:`repro.core.attribution` call).  Top-K panel requests ride the same
    seed axis: K one-hot seeds per example, masks loaded once (§III.F).

Heavy-traffic hardening (see :mod:`repro.serve.admission`):

  * an optional :class:`~repro.serve.admission.AdmissionConfig` turns
    :meth:`submit` into an admission decision — bounded queue, per-method
    token buckets, and deadline-aware shedding (a typed
    :class:`~repro.serve.api.ShedError` instead of an unbounded backlog);
  * :meth:`poll` first sweeps out requests whose deadline can no longer be
    met (they complete as structured shed responses, never occupying a
    padded seat), then dispatches batches in EDF order;
  * dispatch is fault-isolated: a poisoned micro-batch (bad shape, adapter
    exception) completes as error responses — the worker loop survives and
    sibling buckets are unaffected; batches that overrun
    ``dispatch_timeout_s`` are flagged and counted (soft timeout: an XLA
    call cannot be preempted in-thread, so the flag is the observable);
  * under degradation pressure, rerouted (``fxp16``) traffic runs cold on a
    lazily-built sibling adapter — its residuals never enter the primary
    cache (an int16 forward's masks must not replay under float engines).

Everything is synchronous and deterministic (injectable clock); an async
transport would wrap ``submit``/``poll`` without touching the dataflow.

With a tracer, every launch is a ``batch/<kind>`` span carrying the serving
thread's CPU and run-queue seconds over it (``cpu_s``, ``runq_s``), with one
``cat="phase"`` child per phase: ``batch.stack``, ``engine.forward``,
``targets``, ``engine.replay``, ``engine.attribute``, ``cache.gather``,
``cache.store`` and ``respond``.  The engine phases carry the launch's
``program``, padded ``rows``, ``live`` rows, ``seeds`` and ``method``.  All
of them are scoped spans (:meth:`repro.obs.trace.Tracer.scope`), so under
``jax.profiler`` they also appear on the device trace's clock.
"""
from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import clock as clock_lib
from repro.obs.trace import (NULL_SCOPE, NULL_SPAN, NULL_TRACER,
                             RequestTrace, Tracer)
from repro.serve import registry
from repro.serve.admission import AdmissionConfig, AdmissionController
from repro.serve.api import (EXPLAIN, PREDICT, SHED_EXPIRED,
                             InvalidRequestError, Request, Response,
                             ShedError, shed_response)
from repro.serve.batcher import Batch, MicroBatcher, pad_size
from repro.serve.residual_cache import CacheEntry, ResidualCache
from repro.serve.stats import ServerStats
from repro.serve.adapters import concat_examples, slice_example


class ExplanationServer:
    def __init__(self, adapter, *, cache_capacity: int = 256,
                 max_batch: int = 8, max_delay_s: float = 0.002,
                 clock: Callable[[], float] = clock_lib.monotonic,
                 method_opts: Optional[Dict[str, dict]] = None,
                 admission: Optional[AdmissionConfig] = None,
                 dispatch_timeout_s: Optional[float] = None,
                 tracer: Optional[Tracer] = None):
        self.adapter = adapter
        self.clock = clock
        # tracer=None is the zero-cost path: NULL_TRACER's start() and
        # scope() return the shared no-op span and scope, and requests
        # never carry a RequestTrace.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if self.tracer.enabled:
            self.tracer.clock = clock      # spans and deadlines share "now"
        self._batch_span = NULL_SPAN       # the launch in flight (_dispatch)
        self._trace_seq = itertools.count()
        # Mesh-sharded adapters (engine built for a mesh:<profile>:<n>
        # device) expose n_shards; the batcher then fills buckets toward
        # max_batch * n_shards seats so every launch occupies the mesh.
        self.batcher = MicroBatcher(max_batch=max_batch,
                                    max_delay_s=max_delay_s, clock=clock,
                                    n_shards=getattr(adapter, "n_shards", 1))
        self.cache = ResidualCache(cache_capacity)
        self.stats = ServerStats()
        self.method_opts = method_opts or {}
        self.dispatch_timeout_s = dispatch_timeout_s
        self.admission = (AdmissionController(admission, now=clock())
                          if admission is not None else None)
        if (admission is not None and admission.degrade is not None
                and admission.degrade.reroute_precision is not None
                and not hasattr(adapter, "with_precision")):
            raise ValueError(
                f"degrade.reroute_precision needs an adapter exposing "
                f"with_precision(); {type(adapter).__name__} does not")
        self._degraded_adapter = None
        self._explainers: Dict[tuple, registry.Explainer] = {}

    # -- public surface -----------------------------------------------------

    def methods(self) -> List[str]:
        """Servable methods — derived from the registry, never hard-coded."""
        return registry.names()

    def submit(self, req: Request) -> None:
        """Admit ``req`` into the queue, or refuse it with a typed error.

        Raises :class:`~repro.serve.api.InvalidRequestError` for poisoned
        payloads (non-finite values, wrong example shape when the adapter
        declares one), ``KeyError`` for unknown methods, and — when
        admission control is configured —
        :class:`~repro.serve.api.ShedError` when the request is refused
        (queue full, rate limited, or its deadline is infeasible given the
        current queue estimate).  Admitted requests always return
        immediately; nothing ever blocks here.
        """
        self._validate(req)
        now = self.clock()
        if self.tracer.enabled:
            # trace id minted at admission; uids repeat (predict + explain
            # share one), so a per-server sequence disambiguates
            tid = f"{req.uid}#{next(self._trace_seq)}"
            req.trace = RequestTrace(self.tracer.start(
                f"request/{req.kind}", cat="request", trace_id=tid,
                t0=now if req.arrive_t is None else req.arrive_t,
                args={"uid": req.uid,
                      "method": req.method if req.kind == EXPLAIN else ""}))
        try:
            if self.admission is not None:
                adm = (req.trace.root.child("admission", cat="admission",
                                            t0=now)
                       if req.trace is not None else NULL_SPAN)
                try:
                    action = self.admission.admit(req,
                                                  self.batcher.pending(),
                                                  now)
                except ShedError as e:
                    adm.end(t=now, result=e.reason)
                    self.stats.record_shed(e.reason)
                    raise
                adm.end(t=now, result=action or "admitted")
                if action is not None:
                    self.stats.record_degrade(action)
            elif req.deadline_s is not None and req.deadline_t is None:
                # deadlines work without admission too; anchor at arrival
                # (is-None, not falsy: replay arrivals at t=0.0 are real)
                req.deadline_t = ((now if req.arrive_t is None
                                   else req.arrive_t) + req.deadline_s)
            if req.kind == EXPLAIN and req.topk is not None:
                cls = registry.get(req.method)
                if not (cls.mask_reuse and self._rules_compatible(
                        self.adapter.store_rules, req.method)):
                    raise ValueError(
                        f"topk panels ride the seed-batched BP and need a "
                        f"mask-reuse method {registry.mask_reuse_methods()} "
                        f"whose masks the adapter stores (store_rules="
                        f"{self.adapter.store_rules!r}); got {req.method!r}")
            self.batcher.submit(req)
        except ShedError as e:
            if req.trace is not None:   # refused requests still terminate
                req.trace.root.end(t=now, status="shed", reason=e.reason)
            raise
        except Exception as e:
            if req.trace is not None:
                req.trace.root.end(t=now, status="error",
                                   error_type=type(e).__name__)
            raise
        if req.trace is not None:
            req.trace.queued = req.trace.root.child("queued", cat="queue",
                                                    t0=now)
        self.stats.record_queue_depth(self.batcher.pending())

    def poll(self, now: Optional[float] = None) -> List[Response]:
        """Run every due micro-batch; returns completed responses
        (including structured shed responses for requests whose deadline
        expired while queued)."""
        now = self.clock() if now is None else now
        est = self._service_estimate()
        out = [self._finish_shed(r)
               for r in self.batcher.expire(now, est)]
        for batch in self.batcher.ready(now, est):
            out.extend(self._dispatch(batch))
        return out

    def drain(self) -> List[Response]:
        """Flush the queue regardless of deadlines (shutdown / tests)."""
        return list(itertools.chain.from_iterable(
            self._dispatch(b) for b in self.batcher.flush()))

    def serve(self, requests: List[Request]) -> Dict[str, Response]:
        """Convenience: submit all, poll to completion, index by uid.

        Shed-at-submit requests surface as structured responses here (the
        batch caller has no per-request try/except)."""
        out: Dict[str, Response] = {}
        for req in requests:
            try:
                self.submit(req)
            except ShedError as e:
                out[req.uid] = shed_response(req, e.reason, e.detail)
                continue
            for resp in self.poll():
                out[resp.uid] = resp
        for resp in self.drain():
            out[resp.uid] = resp
        return out

    # -- validation / admission helpers -------------------------------------

    def _validate(self, req: Request) -> None:
        if req.kind == EXPLAIN:
            cls = registry.get(req.method)    # fail fast on unknown methods
            if cls.needs_key and req.key is None:
                raise InvalidRequestError(
                    f"request {req.uid!r}: method {req.method!r} is "
                    f"stochastic and needs a per-request PRNG key")
        expected = getattr(self.adapter, "example_shape", None)
        if expected is not None and tuple(np.shape(req.x)) != tuple(expected):
            raise InvalidRequestError(
                f"request {req.uid!r}: example shape {np.shape(req.x)} != "
                f"adapter's {tuple(expected)}")
        if self.admission is not None and self.admission.config.reject_nonfinite:
            x = np.asarray(req.x)
            if np.issubdtype(x.dtype, np.floating) and not np.isfinite(x).all():
                raise InvalidRequestError(
                    f"request {req.uid!r}: non-finite values in payload")

    def _service_estimate(self) -> float:
        if self.admission is None:
            return 0.0
        est = self.admission.estimator
        snap = est.snapshot()
        return max(snap.values()) if snap else 0.0

    def _finish_shed(self, req: Request) -> Response:
        self.stats.record_shed(SHED_EXPIRED)
        resp = shed_response(req, SHED_EXPIRED, "deadline expired in queue")
        resp.latency_s = self.clock() - req.arrive_t
        if req.trace is not None:       # expired-in-queue still terminates
            t = req.arrive_t + resp.latency_s
            req.trace.queued.end(t=t, result=SHED_EXPIRED)
            req.trace.root.end(t=t, status="shed", reason=SHED_EXPIRED)
        return resp

    # -- adapters / explainer construction -----------------------------------

    def _adapter_for(self, degraded: bool):
        if not degraded:
            return self.adapter
        if self._degraded_adapter is None:
            precision = self.admission.config.degrade.reroute_precision
            self._degraded_adapter = self.adapter.with_precision(precision)
        return self._degraded_adapter

    def explainer(self, method: str,
                  degraded: bool = False) -> registry.Explainer:
        key = (method, degraded)
        if key not in self._explainers:
            adapter = self._adapter_for(degraded)
            cls = registry.get(method)
            eng_for = getattr(adapter, "engine_for", None)
            if eng_for is not None:
                # Engine-backed adapters: the explainer rides the built
                # engine for its rule set — precision/backend (incl. the
                # fxp16 manual pair) resolved by the spec, in one place.
                self._explainers[key] = cls.from_engine(
                    eng_for(cls.rules), **self.method_opts.get(method, {}))
            else:
                # Legacy adapters: raw closures.  Quantized ones expose a
                # manual BP engine (fxp16 has no jax.vjp); float adapters
                # return None and vjp is used.
                manual = getattr(adapter, "manual_backward", None)
                self._explainers[key] = cls(
                    adapter.model_fn(cls.rules),
                    backward=manual(cls.rules) if manual else None,
                    **self.method_opts.get(method, {}))
        return self._explainers[key]

    # -- dispatch -----------------------------------------------------------

    def _dispatch(self, batch: Batch) -> List[Response]:
        """Fault-isolated batch execution: an exception inside a batch
        becomes per-request error responses, never a dead worker loop."""
        t0 = self.clock()
        scope = NULL_SCOPE
        if self.tracer.enabled:
            # the batch is its own track; request spans point at it by id
            bid = f"batch#{next(self._trace_seq)}"
            scope = self.tracer.scope(
                f"batch/{batch.kind}", cat="batch", trace_id=bid, t0=t0,
                n=len(batch.requests), degraded=batch.degraded,
                method=(batch.requests[0].method
                        if batch.kind == EXPLAIN else ""))
        # the span's ends and its profiler event's stay a few statements
        # apart: their offset is what puts spans on the device's clock
        with scope as bspan:
            if self.tracer.enabled:
                for req in batch.requests:
                    if req.trace is not None:
                        req.trace.queued.end(t=t0)
                        req.trace.engine = req.trace.root.child(
                            "engine", cat="engine", t0=t0,
                            args={"batch": bid})
            times0 = clock_lib.thread_times() if bspan.enabled else None
            self._batch_span = bspan
            try:
                out = self._process(batch)
            except Exception as e:                      # noqa: BLE001
                out = [self._finish_error(req, e) for req in batch.requests]
            finally:
                self._batch_span = NULL_SPAN
            if times0 is not None:
                (cpu0, runq0), (cpu1, runq1) = times0, clock_lib.thread_times()
                bspan.annotate(cpu_s=cpu1 - cpu0,
                               runq_s=(None if runq0 is None or runq1 is None
                                       else runq1 - runq0))
            duration = self.clock() - t0
            bspan.end(t=t0 + duration)
        if (self.dispatch_timeout_s is not None
                and duration > self.dispatch_timeout_s):
            self.stats.record_timeout()
            for resp in out:
                resp.meta["dispatch_timeout_s"] = duration
        if self.admission is not None and batch.requests:
            req0 = batch.requests[0]
            self.admission.estimator.observe(
                req0.kind, req0.method if req0.kind == EXPLAIN else "",
                duration, len(batch.requests))
        return out

    def _phase(self, name: str, **args):
        """A scoped span over one phase of the launch in flight."""
        return self.tracer.scope(name, parent=self._batch_span, cat="phase",
                                 **args)

    def _process(self, batch: Batch) -> List[Response]:
        if batch.kind == PREDICT:
            return self._run_predict(batch)
        return self._run_explain(batch)

    def _finish(self, req: Request, resp: Response) -> Response:
        resp.latency_s = self.clock() - req.arrive_t
        if req.degrade_action is not None:
            resp.meta["degraded"] = req.degrade_action
        self.stats.record(req.kind,
                          req.method if req.kind == EXPLAIN else "",
                          resp.latency_s, resp.cache_hit)
        if req.trace is not None:
            t = req.arrive_t + resp.latency_s
            req.trace.engine.end(t=t)
            req.trace.root.end(t=t, status="ok", cache_hit=resp.cache_hit,
                               latency_s=resp.latency_s)
        return resp

    def _finish_error(self, req: Request, exc: Exception) -> Response:
        """Structured failure for one request of a poisoned batch."""
        self.stats.record_error()
        resp = Response(uid=req.uid, kind=req.kind,
                        method=req.method if req.kind == EXPLAIN else None,
                        error=str(exc), error_type=type(exc).__name__)
        resp.latency_s = self.clock() - req.arrive_t
        if req.trace is not None:       # faulted requests still terminate
            t = req.arrive_t + resp.latency_s
            req.trace.engine.end(t=t)
            req.trace.root.end(t=t, status="error",
                               error_type=type(exc).__name__)
        return resp

    def _run_predict(self, batch: Batch) -> List[Response]:
        with self._phase("batch.stack"):
            xb, live = batch.stack(self.batcher.fill_target)
        rows = xb.shape[0]
        with self._phase("engine.forward", program="forward", rows=rows,
                         live=live, seeds=0, method=""):
            logits, residuals = self.adapter.predict(xb)
            jax.block_until_ready(logits)
        self.stats.record_batch(live, rows)
        now = self.clock()
        with self._phase("cache.store"):
            for i, req in enumerate(batch.requests):
                self.cache.put(req.uid, CacheEntry(
                    logits=logits[i], residuals=slice_example(residuals, i),
                    rules=self.adapter.store_rules))
                if req.trace is not None:
                    req.trace.root.child("cache", cat="cache", t0=now).end(
                        t=now, result="store")
        with self._phase("respond"):
            return [self._finish(req, Response(
                uid=req.uid, kind=PREDICT, logits=logits[i], batch_size=rows))
                for i, req in enumerate(batch.requests)]

    @staticmethod
    def _rules_compatible(stored_rules: str, method: str) -> bool:
        """Can masks stored under ``stored_rules`` replay ``method``'s BP?

        deconvnet-rules forwards store NO ReLU masks (Table II: the rule
        reads only the gradient sign), so those entries can replay nothing
        but deconvnet; saliency/guided-stored masks serve every BP method.
        """
        return method == "deconvnet" or stored_rules != "deconvnet"

    def _run_explain(self, batch: Batch) -> List[Response]:
        method = batch.requests[0].method
        if batch.degraded:
            # Rerouted traffic runs cold on the sibling engine; the primary
            # cache's float residuals cannot replay an int16 backward (and
            # vice versa), so the hit/warm paths are skipped entirely.
            now = self.clock()
            for req in batch.requests:
                if req.trace is not None:
                    req.trace.root.child("cache", cat="cache", t0=now).end(
                        t=now, result="bypass")
            return self._explain_cold(method, batch.requests, degraded=True)
        hits, colds = [], []
        reusable = registry.get(method).mask_reuse
        now = self.clock()
        for req in batch.requests:
            entry = None
            if reusable:
                cand = self.cache.peek(req.uid)
                if cand is not None and self._rules_compatible(cand.rules,
                                                               method):
                    entry = self.cache.get(req.uid)   # accounts the hit
                else:
                    self.cache.count_miss()           # absent or unusable
            if req.trace is not None:
                req.trace.root.child("cache", cat="cache", t0=now).end(
                    t=now, result="hit" if entry is not None else "miss")
            if entry is not None:
                hits.append((req, entry))
            else:
                colds.append(req)
        out = []
        if hits:
            out.extend(self._explain_hits(method, hits))
        if colds:
            out.extend(self._explain_cold(method, colds))
        return out

    def _targets_for(self, req: Request, logits) -> np.ndarray:
        """Resolve the class panel to explain: topk > explicit > argmax."""
        lg = np.asarray(logits)
        if req.topk is not None:
            return np.argsort(-lg)[:req.topk]
        if req.target is not None:
            return np.asarray([req.target])
        return np.asarray([int(np.argmax(lg))])

    def _explain_hits(self, method: str, hits) -> List[Response]:
        """Forward-free path: seed-batched fused BP over cached masks."""
        reqs = [r for r, _ in hits]
        entries = [e for _, e in hits]
        with self._phase("targets"):
            targets = [self._targets_for(r, e.logits)
                       for r, e in zip(reqs, entries)]
        # pow2-pad the hit group too (rows repeat entry 0, sliced off below)
        # so the BP program compiles for a handful of batch shapes only.
        psize = pad_size(len(reqs), self.batcher.fill_target)
        ent_pad = entries + [entries[0]] * (psize - len(reqs))
        tgt_pad = targets + [targets[0]] * (psize - len(reqs))
        with self._phase("cache.gather"):
            residuals = concat_examples([e.residuals for e in ent_pad])
        num_classes = entries[0].logits.shape[-1]
        with self._phase("engine.replay", program="replay", rows=psize,
                         live=len(reqs), seeds=len(targets[0]),
                         method=method):
            # [S, B, C]; S is bucket-homogeneous (topk is in the bucket key)
            seeds = jax.nn.one_hot(jnp.asarray(np.stack(tgt_pad, axis=1)),
                                   num_classes,
                                   dtype=entries[0].logits.dtype)
            rel = self.adapter.explain_cached(method, residuals, seeds)
            jax.block_until_ready(rel)
        self.stats.record_batch(len(reqs), psize)
        with self._phase("respond"):
            return [self._finish(req, Response(
                uid=req.uid, kind=EXPLAIN, logits=entry.logits,
                relevance=rel[:, i] if req.topk is not None else rel[0, i],
                targets=tuple(int(t) for t in targets[i]), method=method,
                cache_hit=True, batch_size=psize))
                for i, (req, entry) in enumerate(zip(reqs, entries))]

    def _explain_cold(self, method: str, reqs: List[Request],
                      degraded: bool = False) -> List[Response]:
        """Explain with no cached residuals — full FP+BP.

        Mask-reuse methods run the SAME two jitted programs as the hit path
        (residual forward, then seed-batched fused BP), so a hit is bitwise
        identical to its cold counterpart by construction — skipping the
        forward never changes the answer — and the forward's masks warm the
        cache for follow-ups.  Composite methods (IG, smoothgrad, ...)
        dispatch through the registry explainer, i.e. exactly the direct
        :mod:`repro.core.attribution` call.  Degraded (rerouted) batches
        run on the sibling adapter and never touch the primary cache.
        """
        adapter = self._adapter_for(degraded)
        if (registry.get(method).mask_reuse
                and self._rules_compatible(adapter.store_rules, method)):
            return self._explain_cold_bp(method, reqs, degraded=degraded)
        with self._phase("batch.stack"):
            xb, live = Batch(("explain",),
                             reqs).stack(self.batcher.fill_target)
        explainer = self.explainer(method, degraded)
        if reqs[0].target is None:             # bucket-homogeneous target kind
            target = None
        else:
            # padding rows explain class 0 and are sliced off below
            target = jnp.asarray([r.target for r in reqs]
                                 + [0] * (xb.shape[0] - live))
        key = None
        if explainer.needs_key:
            if registry.get(method).fold_keys:
                # Fold PER-REQUEST keys along the batch axis: every request
                # draws from its own key, so co-batched stochastic results
                # are identical to singleton serving.  Padding rows redraw
                # under the first key and are sliced off with the batch.
                key = jnp.stack(
                    [jnp.asarray(r.key) for r in reqs]
                    + [jnp.asarray(reqs[0].key)] * (xb.shape[0] - live))
            else:
                # non-foldable stochastic methods ride singleton buckets
                # (batcher token), so reqs is exactly one request here
                key = reqs[0].key
        with self._phase("engine.attribute", program="attribute",
                         rows=xb.shape[0], live=live, seeds=1, method=method):
            logits, rel = explainer.attribute(xb, target=target, key=key)
            jax.block_until_ready(rel)
        self.stats.record_batch(live, xb.shape[0])
        out = []
        with self._phase("respond"):
            for i, req in enumerate(reqs):
                tgt = (req.target if req.target is not None
                       else int(np.argmax(np.asarray(logits[i]))))
                out.append(self._finish(req, Response(
                    uid=req.uid, kind=EXPLAIN, logits=logits[i],
                    relevance=rel[i], targets=(int(tgt),), method=method,
                    batch_size=xb.shape[0])))
        return out

    def _explain_cold_bp(self, method: str, reqs: List[Request],
                         degraded: bool = False) -> List[Response]:
        """Cold pure-BP explain: residual forward + seed-batched fused BP,
        warming the residual cache with the forward's packed masks (primary
        adapter only — degraded residuals are engine-incompatible)."""
        adapter = self._adapter_for(degraded)
        with self._phase("batch.stack"):
            xb, live = Batch(("explain",),
                             reqs).stack(self.batcher.fill_target)
        rows = xb.shape[0]
        with self._phase("engine.forward", program="forward", rows=rows,
                         live=live, seeds=0, method=method):
            logits, residuals = adapter.predict(xb)
        with self._phase("targets"):
            targets = [self._targets_for(r, logits[i])
                       for i, r in enumerate(reqs)]
        with self._phase("engine.replay", program="replay", rows=rows,
                         live=live, seeds=len(targets[0]), method=method):
            tmat = np.concatenate(
                [np.stack(targets, axis=1),
                 np.zeros((targets[0].shape[0], rows - live), int)], axis=1)
            seeds = jax.nn.one_hot(jnp.asarray(tmat), logits.shape[-1],
                                   dtype=logits.dtype)
            rel = adapter.explain_cached(method, residuals, seeds)
            jax.block_until_ready(rel)
        self.stats.record_batch(live, rows)
        if not degraded:
            with self._phase("cache.store"):
                for i, req in enumerate(reqs):
                    self.cache.put(req.uid, CacheEntry(
                        logits=logits[i],
                        residuals=slice_example(residuals, i),
                        rules=adapter.store_rules))
        with self._phase("respond"):
            return [self._finish(req, Response(
                uid=req.uid, kind=EXPLAIN, logits=logits[i],
                relevance=rel[:, i] if req.topk is not None else rel[0, i],
                targets=tuple(int(t) for t in targets[i]), method=method,
                batch_size=rows))
                for i, req in enumerate(reqs)]
