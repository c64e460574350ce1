"""The compile-once attribution engine: configure -> build -> explain.

:func:`build` turns an :class:`~repro.engine.spec.EngineSpec` into an
:class:`Engine` exactly once — backend resolution (manual seed-batched pair
vs ``jax.vjp``), precision routing, and jit of the forward/backward pair all
happen here, never at a call site — and memoizes on spec equality: two
``build()`` calls with equal specs return the SAME engine (shared compiled
callables); changing any spec field builds (and compiles) afresh.

Steady state, every request is pure execution::

    eng = build(EngineSpec(model=CNNModel(params, cfg), method="guided",
                           precision="fxp16", targets=TopK(5)))
    logits = eng.predict(x)                     # forward only
    logits, rel = eng.explain(x)                # FP + seed-batched BP
    logits, rel, res = eng.predict_then_explain(x)   # ...keeping residuals
    rel2 = eng.replay(res, seeds)               # BP phase alone (§III.F)
    logits, ig = eng.ig(x, steps=16)            # composites ride the pair

``fxp16`` needs no ``backward=`` hand-threading anywhere: the spec resolves
to the manual int16 pair automatically (integers have no ``jax.vjp``).
"""
from __future__ import annotations

import math
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.engine import methods
from repro.engine.backward import (BackwardEngine, ManualSeedBatchedBackward,
                                   VjpBackward)
from repro.engine.spec import PERTURB_METHODS, EngineSpec, Fixed, TopK
from repro.obs import metrics as obsm


class Engine:
    """A built attribution engine — all knobs resolved, all programs jitted.

    Construct via :func:`build` (direct construction skips the cache).
    """

    def __init__(self, spec: EngineSpec):
        self.spec = spec
        model = spec.model
        self._mesh = None
        self._n_shards = 1
        if hasattr(model, "token_step"):
            # LM token-attribution engine: two jitted FP+BP step programs,
            # compiled on first use, both running the resolved SSM scan
            # plan: the argmax-seeded one gives the ixg and grad_norm
            # scores of one BP, the other the contrastive ones.
            self._plan = spec.resolve_plan()
            self._token_step = {
                c: jax.jit(model.token_step(spec.method, plan=self._plan,
                                            precision=spec.precision,
                                            contrastive=c))
                for c in (False, True)}
            self._backend: Optional[BackwardEngine] = None
            self._model_fn = None
            return
        self._token_step = None
        self._fused_explain: Dict[Tuple[bool, Optional[int]], Any] = {}
        self._fold_fn = None   # lazily-jitted fold-tiled forward (perturb)
        # folded-batch audit decisions (composite methods): folded M ->
        # engine to dispatch through (self when the plan still fits)
        self._fold_engines: Dict[int, "Engine"] = {}
        # Resource-aware tile planning happens HERE, before any compile —
        # the paper's design-time tile sizing: every kernel of the pair and
        # of the rule-bound logits program runs the planned block shapes.
        self._plan = spec.resolve_plan()
        # Mesh-sharded build: a ``mesh:<profile>:<n>`` device compiles ONE
        # predict/explain pair that shard_maps the batch over the serving
        # mesh (see _shard_map below).  The plan above is
        # already per-shard (plan_cnn splits the batch across the mesh
        # before tiling); here the physical placement is resolved.  A host
        # with fewer devices than shards raises (make_serving_mesh).
        device = (spec.device if spec.device is not None
                  else (self._plan.device if self._plan else None))
        if device is not None:
            from repro.launch.mesh import make_serving_mesh
            from repro.plan import MeshProfile, get_profile
            profile = get_profile(device)
            if isinstance(profile, MeshProfile):
                self._n_shards = profile.n_shards
                self._mesh = make_serving_mesh(profile.n_shards)
        kind = spec.resolve_backward()
        # Perturbation specs are forward-only; the model still builds under
        # a concrete rule set (fwd_rules -> saliency) so the compiled
        # forward is shared with gradient consumers of the same spec shape.
        rules = spec.fwd_rules()
        if kind == "seed_batched":
            if not getattr(model, "has_pair", False):
                raise ValueError(
                    f"model {model!r} exposes no seed-batched pair; "
                    f"use backward='vjp'")
            fwd, bwd = model.pair(rules, spec.precision,
                                  plan=self._plan)
            if self._mesh is not None:
                fwd = self._shard_batch(fwd, residuals=True)
                bwd = self._shard_pair_bwd(bwd)
            self._backend = ManualSeedBatchedBackward(fwd, bwd)
        else:
            f = model.logits_fn(rules, spec.precision,
                                plan=self._plan)
            if self._mesh is not None:
                f = self._shard_batch(f)
            self._backend = VjpBackward(f)
        # Rule-bound logits program: shared by predict, the composite
        # methods, and registry explainers.  Under fxp16 this IS the pair
        # forward (pair-returning) — the manual backward is mandatory there.
        if spec.precision == "fxp16":
            self._model_fn = self._backend.forward
        else:
            f = model.logits_fn(rules, spec.precision,
                                plan=self._plan)
            if self._mesh is not None:
                f = self._shard_batch(f)
            self._model_fn = jax.jit(f)

    # -- mesh-sharded build --------------------------------------------------
    #
    # A Pallas (Mosaic) kernel cannot be partitioned by the compiler, so the
    # sharded programs are explicit ``jax.shard_map``s over the serving
    # mesh's "data" axis: each device runs the unchanged single-device
    # program on its slice of the batch (seeds replicated, masks/indices
    # sharded with their examples).  The batch pads up to a multiple of the
    # shard count and the outputs are sliced back, so any batch size runs.
    # The pair forward's packed uint8 residuals leave the shard_map as int32
    # words (:class:`_Words`): XLA:TPU corrupted [N, 32, 32, 4] uint8 masks
    # when it resharded them (slicing the padded batch, the residual cache's
    # per-example slices) on a v5e 2x2; int32 words reshard intact.  Logits
    # keep their dtype (bf16 under precision="bf16").

    def _shard_map(self, f, in_specs, out_specs):
        return jax.shard_map(f, mesh=self._mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _padded(self, b: int) -> int:
        return -(-b // self._n_shards) * self._n_shards

    def _shard_batch(self, f, *, residuals: bool = False):
        """``f(x [B, ...]) -> logits [B, ...]``, batch-sharded; with
        ``residuals`` f is a pair forward ``-> (logits, residuals)`` and the
        residuals come back as :class:`_Words`."""
        def local(x):
            if not residuals:
                return f(x)
            logits, res = f(x)
            return logits, jax.tree.map(_Words.wrap, res)

        sharded = self._shard_map(local, P("data"), P("data"))

        def run(x):
            b = _batch_of(x)
            out = sharded(_pad_rows(x, 0, self._padded(b)))
            return jax.tree.map(lambda v: v[:b], out)

        return run

    def _shard_pair_bwd(self, bwd):
        """``(residuals [B, ...], seeds [S, B, C]) -> relevance [S, B, ...]``,
        batch-sharded (the seeds axis rides along on every device)."""
        sharded = self._shard_map(
            lambda res, seeds: bwd(_Words.unwrap_tree(res), seeds),
            (P("data"), P(None, "data")), P(None, "data"))

        def run(residuals, seeds):
            b, bp = seeds.shape[1], self._padded(seeds.shape[1])
            rel = sharded(_pad_rows(residuals, 0, bp),
                          _pad_rows(seeds, 1, bp))
            return jax.tree.map(lambda v: v[:, :b], rel)

        return run

    # -- resolved surfaces ---------------------------------------------------

    @property
    def backend(self) -> BackwardEngine:
        """The resolved :class:`BackwardEngine` (manual pair or vjp)."""
        return self._backend

    @property
    def mesh(self):
        """The serving mesh sharded engines compile under (None when the
        spec names a single-core device)."""
        return self._mesh

    @property
    def n_shards(self) -> int:
        """Mesh extent of the spec's device profile (1 = unsharded).  The
        serve batcher fills toward ``max_batch * n_shards`` seats so a
        sharded launch runs at full occupancy."""
        return self._n_shards

    @property
    def plan(self):
        """The resolved ``repro.plan.TilePlan`` the compiled kernels run
        (None when the spec names no device/plan — tiling defaults)."""
        return self._plan

    @property
    def supports_replay(self) -> bool:
        return self._backend is not None and self._backend.supports_replay

    @property
    def model_fn(self):
        """Rule-bound ``f`` for registry explainers / direct method calls.

        Float precisions: ``f(x) -> logits`` (differentiable).  ``fxp16``:
        the pair forward ``f(x) -> (logits, residuals)`` — combine with
        :attr:`composite_backward` (there is no integer ``jax.vjp``).
        """
        return self._model_fn

    @property
    def composite_backward(self):
        """Manual BP engine for the composite/free-function ``backward=``
        knob, or None on float paths where ``jax.vjp`` through
        :attr:`model_fn` is the (equivalent, program-shared) engine."""
        if self.spec.precision == "fxp16":
            return self._backend.backward
        return None

    # -- the two phases ------------------------------------------------------

    def predict(self, x):
        """Forward only: ``x -> logits`` (no residual work on float paths)."""
        self._require_array_engine("predict")
        x, live = self._pad(x)
        logits = self._model_fn(x)
        if self.spec.precision == "fxp16":
            logits = logits[0]
        return self._unpad(logits, live)

    def forward(self, x):
        """Residual-returning forward: ``x -> (logits, residuals)``.

        The residuals are whatever :meth:`replay` needs — bit-packed masks
        on the manual pair (cacheable, §III.F), the input itself on vjp.
        Unpadded/unsliced: this is the serving hot path; batching discipline
        belongs to the caller (see :mod:`repro.serve.batcher`).
        """
        self._require_array_engine("forward")
        return self._backend.forward(x)

    def replay(self, residuals, seeds):
        """BP phase alone: ``seeds [S, B, C] -> relevance [S, B, ...]`` over
        stored residuals — the forward-skipping explain (§III.F)."""
        self._require_array_engine("replay")
        return self._backend.backward(residuals, seeds)

    # -- explain -------------------------------------------------------------

    def explain(self, x, *, target=None, topk: Optional[int] = None):
        """One FP + one seed-batched BP: ``-> (logits, relevance)``.

        Fan-out defaults to ``spec.targets``; ``target``/``topk`` override
        per call.  Scalar fan-out returns ``rel [B, ...]``; top-K returns a
        ``rel [K, B, ...]`` panel (K seeds, one launch, masks shared).

        On the manual pair this is forward + replay (two programs, the same
        two the serving cache uses, so hit == cold by construction); on the
        vjp backend it compiles ONE fused FP+BP program so the forward is
        never run twice.
        """
        self._require_array_engine("explain")
        self._require_gradient_spec("explain")
        if self.supports_replay:
            logits, rel, _ = self.predict_then_explain(x, target=target,
                                                       topk=topk)
            return logits, rel
        target, topk = self._fanout(target, topk)
        x, live = self._pad(x)
        target = self._pad_target(target, live)
        run = self._fused(target is not None, topk)
        logits, rel = run(x, target) if target is not None else run(x)
        return (self._unpad(logits, live),
                self._unpad(rel, live, axis=0 if topk is None else 1))

    def predict_then_explain(self, x, *, target=None,
                             topk: Optional[int] = None):
        """The explicit two-phase form: ``-> (logits, relevance, residuals)``.

        One forward; the returned residuals can :meth:`replay` further
        targets later without another forward (the serving cache's
        contract).  On the vjp backend the "residuals" are the padded input
        and replay re-runs the forward inside the compiled program.
        """
        self._require_array_engine("predict_then_explain")
        self._require_gradient_spec("predict_then_explain")
        target, topk = self._fanout(target, topk)
        x, live = self._pad(x)
        target = self._pad_target(target, live)
        logits, residuals = self._backend.forward(x)
        seeds, squeeze = self._seeds(logits, target, topk)
        rel = self._backend.backward(residuals, seeds)
        rel = rel[0] if squeeze else rel
        return (self._unpad(logits, live),
                self._unpad(rel, live, axis=0 if squeeze else 1),
                residuals)

    # -- composite methods riding the same compiled pair ---------------------

    def ig(self, x, *, steps: int = 16, baseline=None, target=None,
           batched: bool = True):
        """Integrated gradients (steps axis folded into the batch dim).

        The folded ``[steps*B, ...]`` launch is re-audited against the
        resolved plan's budget first (see :meth:`_engine_for_fold`)."""
        eng = self._engine_for_fold(steps if batched else 1, x)
        return methods.integrated_gradients(
            eng._model_fn, x, steps=steps, baseline=baseline, target=target,
            batched=batched, backward=eng.composite_backward)

    def smoothgrad(self, x, key, *, n: int = 8, sigma: float = 0.1,
                   target=None, batched: bool = True):
        """SmoothGrad (noise axis folded into the batch dim; folded shape
        re-audited against the plan budget, see :meth:`_engine_for_fold`)."""
        eng = self._engine_for_fold(n if batched else 1, x)
        return methods.smoothgrad(
            eng._model_fn, x, key, n=n, sigma=sigma, target=target,
            batched=batched, backward=eng.composite_backward)

    def perturb(self, x, key=None, *, method: Optional[str] = None,
                target=None, batched: bool = True,
                n_samples: Optional[int] = None, **opts):
        """Gradient-free perturbation explain: ``-> (logits, heat [B, H, W])``.

        Runs :mod:`repro.perturb` over this engine's compiled forward —
        N masked variants folded into the leading batch axis, ONE forward
        pass, no ``jax.vjp`` anywhere (so this is the explain path that
        works under ``precision="fxp16"``, where gradients don't exist).

        ``method`` defaults to ``spec.method`` (which must then be one of
        ``occlusion | lime | rise``); ``n_samples`` defaults to
        ``spec.n_samples`` then the method default.  ``key`` is required
        for the stochastic methods and may be a BATCHED stack of
        per-example keys (shape ``[B, ...]`` — the serve layer's folded
        per-request keys), yielding independent masks per example.

        The folded ``[N*B, ...]`` forward is re-audited against the
        resolved plan's budget first, exactly like IG's steps fold
        (:meth:`_engine_for_fold`) — replanned or rejected BEFORE launch.
        """
        self._require_array_engine("perturb")
        from repro import perturb as perturb_lib
        method = method if method is not None else self.spec.method
        if method not in PERTURB_METHODS:
            raise ValueError(f"method={method!r} not in {PERTURB_METHODS}; "
                             f"pass method= or build a perturbation spec")
        merged = dict(perturb_lib.PERTURB_DEFAULTS[method])
        if "n_samples" in merged:
            n_samples = (n_samples if n_samples is not None
                         else self.spec.n_samples)
            if n_samples is not None:
                merged["n_samples"] = int(n_samples)
        merged.update({k: v for k, v in opts.items() if v is not None})
        x, live = self._pad(x)
        if key is not None:
            key = jnp.asarray(key)
            kb = perturb_lib.key_batch_size(key)
            if kb is not None and kb < x.shape[0]:
                # pad rows perturb under the first live key; sliced off below
                pad = jnp.broadcast_to(key[:1],
                                       (x.shape[0] - kb,) + key.shape[1:])
                key = jnp.concatenate([key, pad])
        target = self._pad_target(target, live)
        n = perturb_lib.n_masks(method, tuple(x.shape[1:3]), **merged)
        eng = self._engine_for_fold(n if batched else 1, x)
        fn = getattr(perturb_lib, method)
        fwd = eng._fold_forward() if batched else eng._model_fn
        if method == "occlusion":
            logits, heat = fn(fwd, x, target=target,
                              batched=batched, **merged)
        else:
            if key is None:
                raise ValueError(f"{method} is stochastic: pass a PRNG key")
            logits, heat = fn(fwd, x, key, target=target,
                              batched=batched, **merged)
        return self._unpad(logits, live), self._unpad(heat, live)

    def input_x_gradient(self, x, *, target=None):
        """Gradient . input refinement."""
        return methods.input_x_gradient(
            self._model_fn, x, target=target,
            backward=self.composite_backward)

    def contrastive(self, x, target_a, target_b):
        """Why A rather than B — one difference-seeded BP pass."""
        return methods.contrastive(
            self._model_fn, x, target_a, target_b,
            backward=self.composite_backward)

    def attribute_classes(self, x, targets):
        """K explicit classes from one forward (seed-batched when manual)."""
        if self.supports_replay:
            return methods.attribute_classes(self._backend.forward, x,
                                             targets,
                                             backward=self._backend.backward)
        return methods.attribute_classes(self._model_fn, x, targets)

    # -- LM token attribution ------------------------------------------------

    def explain_tokens(self, batch, *, mode: str = "ixg"):
        """LM engines: ``batch -> (last-position logits [B, V], scores
        [B, S])`` — per-prompt-position relevance of the next-token
        prediction (the paper's heatmap over tokens).

        ``mode`` picks the per-token score reduction (``ixg`` input x
        gradient, ``grad_norm`` saliency norm, ``contrastive``
        argmax-vs-runner-up).  ``ixg`` and ``grad_norm`` are two
        reductions of one jitted step program, ``contrastive`` another;
        each compiles on first use per batch shape and runs the engine's
        resolved SSM scan plan."""
        if self._token_step is None:
            raise ValueError(
                f"{type(self.spec.model).__name__} engines explain arrays; "
                f"explain_tokens needs an LMModel spec")
        from repro.launch.steps import TOKEN_MODES
        if mode not in TOKEN_MODES:
            raise ValueError(f"mode={mode!r} not in {TOKEN_MODES}")
        logits, scores = self._token_step[mode == "contrastive"](
            self.spec.model.params, batch)
        return logits, scores[mode]

    # -- internals -----------------------------------------------------------

    def _fold_forward(self):
        """The forward a FOLDED perturbation launch runs.

        Same rule-bound logits program as :attr:`model_fn`, compiled with
        the fold batch tiles (``tiling.fold_batch_tile``) and mask-free
        pointwise stages — bitwise-identical logits, bounded grid cells at
        any ``[N*B, ...]`` fan-out.  Models without a fold-tiled program
        (FnModel, lax-reference CNNs take the kwarg but ignore it) fall
        back to :attr:`model_fn`; fxp16 keeps the int pair forward (its
        integer kernels have no fold twin — correctness over speed there).
        """
        if self.spec.precision == "fxp16":
            return self._model_fn
        if self._fold_fn is None:
            try:
                f = self.spec.model.logits_fn(
                    self.spec.fwd_rules(), self.spec.precision,
                    plan=self._plan, fold=True)
            except TypeError:       # logits_fn without a fold knob
                self._fold_fn = self._model_fn
            else:
                if self._mesh is not None:
                    f = self._shard_batch(f)
                self._fold_fn = jax.jit(f)
        return self._fold_fn

    def _engine_for_fold(self, factor: int, x) -> "Engine":
        """The engine a composite's FOLDED launch must dispatch through.

        ``ig(steps=S)`` / ``smoothgrad(n=S)`` with ``batched=True`` fold the
        S axis into the batch dim, so the planned kernels run at
        ``M = S * B`` — a shape :meth:`EngineSpec.resolve_plan` never
        audited (it covers ``spec.batch`` x targets fan-out only).  This
        closes that gap at call time, memoized per folded M:

          * no plan, or folded M within the audited batch -> ``self``;
          * planned tiles still fit the profile at folded M (the usual
            case: conv batch rides the grid, only ``vmm_bwd`` scales with
            M) -> ``self`` — same program, recompiled at the larger shape;
          * budget violated -> re-plan at the folded batch and dispatch
            through a sibling engine built on that plan (shared via the
            build cache; jit is lazy so an unused sibling never compiles);
          * no feasible tiling at folded M -> the planner's
            :class:`~repro.plan.InfeasiblePlanError` propagates, BEFORE a
            kernel launch that would overrun the device budget.
        """
        if factor <= 1 or self._plan is None:
            return self
        b = jax.tree_util.tree_leaves(x)[0].shape[0]
        folded = int(factor) * int(b)
        if folded <= (self.spec.batch or 1):
            return self
        if folded not in self._fold_engines:
            self._fold_engines[folded] = self._audit_fold(folded)
        return self._fold_engines[folded]

    def _audit_fold(self, folded: int) -> "Engine":
        from dataclasses import replace as _replace

        from repro.plan import cnn_plan_footprints, get_profile, plan_cnn
        spec = self.spec
        profile = get_profile(spec.device if spec.device is not None
                              else self._plan.device)
        # composites backprop ONE seed per folded row, so seeds=1 here even
        # when spec.targets is TopK (panels ride explain(), not ig()).
        fps = cnn_plan_footprints(spec.model.cfg, self._plan,
                                  precision=spec.precision, batch=folded,
                                  seeds=1, profile=profile)
        if all(fp.fits(profile) for fp in fps.values()):
            return self
        plan = plan_cnn(spec.model.cfg, device=profile.name,
                        precision=spec.precision, batch=folded, seeds=1)
        return build(_replace(spec, plan=plan))

    def _require_array_engine(self, op: str):
        if self._token_step is not None:
            raise ValueError(f"{op}() is not available on LM token engines; "
                             f"use explain_tokens(batch)")

    def _require_gradient_spec(self, op: str):
        if self.spec.method in PERTURB_METHODS:
            raise ValueError(
                f"{op}() runs the gradient BP path; spec.method="
                f"{self.spec.method!r} is forward-only — use "
                f"Engine.perturb(x, key=...)")

    def _fanout(self, target, topk) -> Tuple[Any, Optional[int]]:
        """Apply ``spec.targets`` defaults to per-call overrides."""
        if topk is None and target is None:
            tspec = self.spec.targets
            if isinstance(tspec, TopK):
                topk = tspec.k
            elif isinstance(tspec, Fixed):
                target = tspec.target
        return target, topk

    def _fused(self, with_target: bool, topk: Optional[int]):
        """One-program FP+BP for non-replay (vjp) backends, cached per
        fan-out shape — the forward runs exactly once per explain."""
        key = (with_target, topk)
        if key not in self._fused_explain:
            f = self._model_fn

            def run(x, target=None):
                logits, vjp_fn = jax.vjp(f, x)
                seeds, squeeze = self._seeds(logits, target, topk)
                if squeeze:
                    (rel,) = vjp_fn(seeds[0])
                else:
                    rel = jax.vmap(lambda s: vjp_fn(s)[0])(seeds)
                return logits, rel

            self._fused_explain[key] = jax.jit(run)
        return self._fused_explain[key]

    def _seeds(self, logits, target, topk) -> Tuple[jnp.ndarray, bool]:
        """Fan-out (already spec-resolved) to seeds [S, B, C]; True =
        squeeze the S=1 axis after the backward."""
        nc = logits.shape[-1]
        if topk is not None:
            _, idx = jax.lax.top_k(logits, topk)           # [B, K]
            return jax.nn.one_hot(idx.T, nc, dtype=logits.dtype), False
        if target is None:
            target = jnp.argmax(logits, axis=-1)
        target = jnp.broadcast_to(jnp.asarray(target), logits.shape[:-1])
        return jax.nn.one_hot(target, nc, dtype=logits.dtype)[None], True

    def _pad(self, x):
        """Pad the leading batch dim up to ``spec.batch`` (row-0 repeats)."""
        b = self.spec.batch
        if b is None:
            return x, None
        n = jax.tree_util.tree_leaves(x)[0].shape[0]
        if n > b:
            raise ValueError(f"batch {n} exceeds spec.batch={b}")
        if n == b:
            return x, n
        return jax.tree.map(
            lambda v: jnp.concatenate(
                [v, jnp.broadcast_to(v[:1], (b - n,) + v.shape[1:])]), x), n

    def _pad_target(self, target, live):
        """Pad a per-example [live] target array alongside the padded batch
        (padding rows explain class 0 and are sliced off with the batch)."""
        if live is None or target is None:
            return target
        t = jnp.asarray(target)
        if t.ndim == 0 or t.shape[0] != live or live == self.spec.batch:
            return t
        pad = jnp.zeros((self.spec.batch - live,) + t.shape[1:], t.dtype)
        return jnp.concatenate([t, pad])

    @staticmethod
    def _unpad(out, live, axis: int = 0):
        if live is None:
            return out
        return jax.tree.map(
            lambda v: jax.lax.slice_in_dim(v, 0, live, axis=axis), out)

    def __repr__(self):
        return f"<Engine {self.spec!r}>"


@jax.tree_util.register_pytree_node_class
class _Words:
    """A sub-32-bit array carried as int32 words: ``[N, *rest]`` flattened
    to ``[N, ceil(bytes/4)]`` (zero-padded), bitcast back on unwrap.
    Lossless and the same size, so residual-cache accounting is unchanged;
    the leading (batch) axis is kept so slicing per example still works."""

    def __init__(self, words, dtype, rest):
        self.words, self.dtype, self.rest = words, dtype, rest

    def tree_flatten(self):
        return (self.words,), (self.dtype, self.rest)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    @classmethod
    def wrap(cls, v):
        if v.dtype.itemsize >= 4:
            return v
        n, rest = v.shape[0], v.shape[1:]
        per = 4 // v.dtype.itemsize
        flat = v.reshape(n, -1)
        flat = _pad_rows(flat, 1, -(-flat.shape[1] // per) * per)
        words = jax.lax.bitcast_convert_type(flat.reshape(n, -1, per),
                                             jnp.int32)
        return cls(words, v.dtype, rest)

    def unwrap(self):
        n = self.words.shape[0]
        flat = jax.lax.bitcast_convert_type(self.words, self.dtype)
        size = math.prod(self.rest)
        return flat.reshape(n, -1)[:, :size].reshape((n,) + self.rest)

    @staticmethod
    def unwrap_tree(tree):
        return jax.tree.map(
            lambda v: v.unwrap() if isinstance(v, _Words) else v, tree,
            is_leaf=lambda v: isinstance(v, _Words))


def _batch_of(x) -> int:
    return jax.tree_util.tree_leaves(x)[0].shape[0]


def _pad_rows(tree, axis: int, size: int):
    """Zero-pad every leaf's ``axis`` up to ``size`` rows (sliced off after
    the sharded launch, so the filler never reaches a caller)."""
    def pad(v):
        extra = size - v.shape[axis]
        if extra == 0:
            return v
        widths = [(0, 0)] * v.ndim
        widths[axis] = (0, extra)
        return jnp.pad(v, widths)
    return jax.tree.map(pad, tree)


# ---------------------------------------------------------------------------
# the build cache: equal specs share one engine (and its compiled programs)
# ---------------------------------------------------------------------------

_BUILD_CACHE: "OrderedDict[EngineSpec, Engine]" = OrderedDict()

#: LRU bound on memoized engines.  Specs hold strong references to their
#: params trees, so an unbounded cache would pin every params object a
#: long-lived process ever built (e.g. periodic weight refreshes); evicted
#: engines keep working for whoever still holds them — only the sharing via
#: ``build()`` lapses.
MAX_CACHED_ENGINES = 64


def build(spec: EngineSpec) -> Engine:
    """Resolve + compile an engine for ``spec``, memoized on spec equality.

    Model handles hash by params identity (see :mod:`repro.engine.spec`),
    so rebuilding with the same params/config/knobs is free and shares the
    jitted forward/backward pair across every consumer (serve adapters,
    benchmarks, examples); changing ANY field — method, precision, backward,
    targets, batch, model — produces a fresh engine.  The memo is an LRU
    bounded at ``MAX_CACHED_ENGINES``.
    """
    eng = _BUILD_CACHE.get(spec)
    if eng is None:
        obsm.ENGINE_BUILDS.inc(outcome="build")
        _BUILD_CACHE[spec] = eng = Engine(spec)
        while len(_BUILD_CACHE) > MAX_CACHED_ENGINES:
            _BUILD_CACHE.popitem(last=False)
            obsm.ENGINE_BUILDS.inc(outcome="evict")
    else:
        obsm.ENGINE_BUILDS.inc(outcome="hit")
        _BUILD_CACHE.move_to_end(spec)
    return eng


def clear_cache() -> None:
    """Drop every memoized engine (tests / params turnover)."""
    _BUILD_CACHE.clear()


def cache_size() -> int:
    return len(_BUILD_CACHE)
