"""EngineSpec — the declarative, compile-once attribution configuration.

The paper's HLS accelerator is configured ONCE (algorithm, layer shapes,
tile sizes, fixed-point format) and then executes inference + backprop many
times with zero per-request setup.  ``EngineSpec`` is that design-time
configuration as a frozen, hashable value object::

    spec = EngineSpec(model=CNNModel(params, cfg), method="guided",
                      precision="fxp16", targets=TopK(5))
    eng = repro.engine.build(spec)          # resolves + compiles ONCE
    logits, rel = eng.explain(images)       # steady-state: zero setup

Every knob that used to be hand-threaded through free-function call sites
(``method=``, ``precision=``, ``backward=``, target fan-out) lives here;
:func:`repro.engine.build` memoizes on spec equality, so equal specs share
one compiled forward/backward pair and changing ANY field recompiles.

Model handles (:class:`CNNModel`, :class:`LMModel`, :class:`FnModel`)
compare by parameter IDENTITY (the pytree object), not by value — arrays
have no cheap equality — plus config equality.  Rebinding the same params
object therefore reuses the cache; a fresh/updated params tree builds a
fresh engine.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple, Union

PRECISIONS = ("f32", "bf16", "fxp16")
BACKWARDS = ("auto", "vjp", "seed_batched")
RULE_SETS = ("saliency", "deconvnet", "guided")
#: Gradient-free perturbation methods (repro.perturb): forward-only specs —
#: no BP rules; ``Engine.perturb`` folds the N-mask fan-out into the batch
#: axis exactly like IG folds its steps (same plan re-audit).
PERTURB_METHODS = ("occlusion", "lime", "rise")


# ---------------------------------------------------------------------------
# target fan-out policy (the paper's §III.F: which output seeds to replay)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Argmax:
    """Explain the predicted class (the paper's default seed)."""


@dataclass(frozen=True)
class Fixed:
    """Always explain one fixed class id."""

    target: int


@dataclass(frozen=True)
class TopK:
    """Explain the top-K classes per example — K one-hot seeds ride the
    seed-batched axis, every stored mask loaded once (§III.F)."""

    k: int

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"TopK.k must be >= 1, got {self.k}")


TargetSpec = Union[Argmax, Fixed, TopK]


# ---------------------------------------------------------------------------
# model handles
# ---------------------------------------------------------------------------


class _ParamsIdentity:
    """eq/hash mixin: params by object identity, config by value."""

    def _key(self) -> Tuple:
        raise NotImplementedError

    def __eq__(self, other):
        return type(other) is type(self) and self._key() == other._key()

    def __hash__(self):
        return hash((type(self).__name__,) + self._key())


@dataclass(frozen=True, eq=False)
class CNNModel(_ParamsIdentity):
    """Handle on the paper's Table III CNN (:mod:`repro.models.cnn`).

    ``use_pallas=True`` (default) routes through the fused Pallas blocks —
    required for the seed-batched manual pair and for ``fxp16``;
    ``use_pallas=False`` keeps the ``lax`` reference ops, where only the
    ``jax.vjp`` backend exists.
    """

    params: Any
    cfg: Any                    # cnn.CNNConfig
    use_pallas: bool = True

    def _key(self):
        return (id(self.params), self.cfg, self.use_pallas)

    @property
    def has_pair(self) -> bool:
        return self.use_pallas

    def pair(self, method: str, precision: str, *, jittable: bool = True,
             plan=None) -> Tuple[Callable, Callable]:
        """The seed-batched (forward, backward) closure pair.

        ``jittable=True`` strips the static ``feat_shape`` tuple from the
        forward's residual dict and re-binds it host-side in the backward —
        the one protocol every jitted consumer must follow (under ``jax.jit``
        the tuple would round-trip as traced scalars and break the
        backward's reshape).  ``jittable=False`` returns the eager pair with
        ``feat_shape`` inline (the legacy ``cnn.seed_batched_attribution``
        contract).

        ``plan`` (a ``repro.plan.TilePlan``) threads planner-chosen block
        shapes into every fused kernel of both halves; ``None`` keeps the
        tiling-policy defaults.
        """
        from repro.models import cnn
        if precision not in PRECISIONS:
            raise ValueError(f"precision={precision!r} not in {PRECISIONS}")
        params, cfg = self.params, self.cfg
        if not jittable:
            def forward(x):
                return cnn.forward_with_residuals(params, x, cfg, method,
                                                  precision, plan=plan)

            def backward(residuals, seeds):
                return cnn.backward_seeds(params, residuals, seeds, cfg,
                                          method, precision, plan=plan)

            return forward, backward

        feat_shape = cfg.feature_hw() + (cfg.channels[-1],)

        def forward(x):
            logits, res = cnn.forward_with_residuals(params, x, cfg, method,
                                                     precision, plan=plan)
            return logits, {k: v for k, v in res.items() if k != "feat_shape"}

        def backward(residuals, seeds):
            residuals = dict(residuals, feat_shape=feat_shape)
            return cnn.backward_seeds(params, residuals, seeds, cfg, method,
                                      precision, plan=plan)

        return forward, backward

    def logits_fn(self, method: str, precision: str, plan=None,
                  fold: bool = False) -> Callable:
        """Rule-bound differentiable ``f`` for the vjp backend / registry
        explainers.  Float precisions only: under ``fxp16`` there is no
        integer ``jax.vjp`` — the Engine exposes the PAIR forward as its
        ``model_fn`` instead (one source of truth for that routing).

        ``fold=True`` selects the forward-only folded-batch program (fold
        batch tiles, mask-free pointwise stages — see ``cnn._apply_fold``)
        that ``Engine.perturb`` runs its ``[N*B, ...]`` fan-out through.
        """
        from repro.models import cnn
        if precision == "fxp16":
            raise ValueError("fxp16 has no differentiable logits_fn; use "
                             "the seed-batched pair (CNNModel.pair) — the "
                             "Engine routes this automatically")
        params, cfg, use_pallas = self.params, self.cfg, self.use_pallas

        def f(v):
            return cnn.apply(params, v, cfg, method=method,
                             use_pallas=use_pallas, precision=precision,
                             plan=plan, fold=fold)

        return f


@dataclass(frozen=True, eq=False)
class LMModel(_ParamsIdentity):
    """Handle on the transformer zoo for token attribution
    (:func:`repro.launch.steps.make_attribute_step`): FP + input-gradient BP
    over the embedding stack, scores reduced per prompt position."""

    params: Any
    cfg: Any                    # models.config.ModelConfig
    triangle_skip: bool = True

    def _key(self):
        return (id(self.params), self.cfg, self.triangle_skip)

    @property
    def has_pair(self) -> bool:
        return False            # vjp-only: no manual residual pair for LMs

    def token_step(self, method: str, *, plan=None,
                   mode: str = "ixg") -> Callable:
        """``(batch) -> (last-position logits [B, V], scores [B, S])``.

        ``method`` must be a gradient rule set (perturbation methods are
        forward-only over pixel grids — there is no token BP to run).
        ``plan`` threads a ``plan_lm`` TilePlan's ``(d_tile, chunk)`` knobs
        into the SSM Pallas scan launches; ``mode`` picks the per-token
        score reduction (``ixg | grad_norm | contrastive`` — see
        :func:`repro.launch.steps.make_attribute_step`).
        """
        if method not in RULE_SETS:
            raise ValueError(
                f"token attribution needs a gradient rule set {RULE_SETS}; "
                f"method={method!r} has no token BP")
        from repro.launch import steps as steps_lib
        step = steps_lib.make_attribute_step(
            self.cfg, method, triangle_skip=self.triangle_skip,
            plan=plan, mode=mode)
        params = self.params

        def run(batch):
            return step(params, batch)

        return run


@dataclass(frozen=True, eq=False)
class FnModel(_ParamsIdentity):
    """Handle on an arbitrary rule-bound callable factory.

    ``make_f(method) -> f(x) -> logits`` — the escape hatch for models
    outside the zoo.  vjp-only (no manual pair).  Identity-hashed on the
    factory object.
    """

    make_f: Callable[[str], Callable]

    def _key(self):
        return (id(self.make_f),)

    @property
    def has_pair(self) -> bool:
        return False

    def logits_fn(self, method: str, precision: str, plan=None) -> Callable:
        if precision == "fxp16":
            raise ValueError("FnModel has no manual pair; precision='fxp16' "
                             "requires a model exposing seed-batched "
                             "residuals (e.g. CNNModel)")
        return self.make_f(method)


# ---------------------------------------------------------------------------
# the spec itself
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class EngineSpec:
    """Declarative configure-once description of an attribution engine.

    Fields:
      * ``model`` — a model handle (:class:`CNNModel`, :class:`LMModel`,
        :class:`FnModel`).
      * ``method`` — backward rule set: ``saliency | deconvnet | guided``
        (composite methods like IG ride any rule set via
        ``Engine.ig/smoothgrad/...``), or a gradient-free perturbation
        method ``occlusion | lime | rise`` (forward-only — served by
        ``Engine.perturb``; the compiled forward is rule-independent).
      * ``precision`` — numeric path: ``f32 | bf16 | fxp16`` (paper §IV;
        ``fxp16`` = true int16 kernels, auto-routed to the manual backward).
      * ``backward`` — backend selection: ``auto`` resolves to the
        seed-batched manual pair when the model exposes one (always for
        ``fxp16``), else ``jax.vjp``; force with ``vjp``/``seed_batched``.
      * ``targets`` — default seed fan-out for ``explain``:
        :class:`Argmax`, :class:`Fixed`, or :class:`TopK`.
      * ``batch`` — optional static batch size: inputs are padded up to it
        (and outputs sliced back) so one compiled program serves any
        smaller batch, the serving-shape discipline of the micro-batcher.
      * ``device`` — a ``repro.plan`` device-profile name (``"detected"``,
        ``"tpu-v5e"``, ``"edge-small"``, ...): ``build`` runs the
        resource-aware tile planner for that profile BEFORE compiling, so
        every fused kernel executes block shapes fitted to its on-chip
        budget (the paper's per-FPGA-target resource model).  The
        ``"mesh:<profile>:<n>"`` form names a ``repro.plan.MeshProfile``
        (N cores of ``<profile>``): the planner splits the batch axis
        across the mesh before tiling the per-shard slice, and
        ``build`` compiles ONE sharded predict/explain pair under the
        serving mesh (``Engine.mesh`` / ``Engine.n_shards``); on a
        1-shard mesh the engine is bitwise-identical to the single-core
        one.
      * ``plan`` — an explicit pre-built ``repro.plan.TilePlan`` (overrides
        ``device``-driven planning; e.g. a plan from another process or a
        hand-tuned one).
      * ``autotune`` — refine the analytic tile ranking by measured kernel
        timings at build time, through the persistent tuning cache (warm
        builds replan from the cache without re-measuring).
      * ``n_samples`` — stochastic perturbation fan-out (``lime``/``rise``
        specs only): the N masks ``Engine.perturb`` folds into the batch
        axis.  ``None`` keeps the method default
        (``repro.perturb.PERTURB_DEFAULTS``); occlusion's fan-out is
        geometric (window/stride), not sampled, so it rejects the field.
    """

    model: Any
    method: str = "saliency"
    precision: str = "f32"
    backward: str = "auto"
    targets: TargetSpec = field(default_factory=Argmax)
    batch: Optional[int] = None
    device: Optional[str] = None
    plan: Optional[Any] = None
    autotune: bool = False
    n_samples: Optional[int] = None

    def __post_init__(self):
        if self.method not in RULE_SETS + PERTURB_METHODS:
            raise ValueError(f"method={self.method!r} not in "
                             f"{RULE_SETS + PERTURB_METHODS}")
        if self.n_samples is not None:
            if self.method not in ("lime", "rise"):
                raise ValueError(
                    f"n_samples applies to stochastic perturbation methods "
                    f"('lime', 'rise'); method={self.method!r}")
            if self.n_samples < 1:
                raise ValueError(
                    f"n_samples must be >= 1, got {self.n_samples}")
        if self.method in PERTURB_METHODS and isinstance(self.targets, TopK):
            raise ValueError(
                "perturbation methods explain one target per example "
                "(no seed-batched BP to ride a top-K panel); use "
                "Argmax/Fixed targets")
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"precision={self.precision!r} not in {PRECISIONS}")
        if self.backward not in BACKWARDS:
            raise ValueError(
                f"backward={self.backward!r} not in {BACKWARDS}")
        if self.precision == "fxp16" and self.backward == "vjp":
            raise ValueError("precision='fxp16' is integer arithmetic — "
                             "jax.vjp does not exist; use backward='auto' "
                             "or 'seed_batched'")
        if self.batch is not None and self.batch < 1:
            raise ValueError(f"batch must be >= 1, got {self.batch}")
        if self.device is not None:
            from repro.plan import get_profile
            get_profile(self.device)        # validate the name eagerly

    def fwd_rules(self) -> str:
        """The backward-rule set the model is built with.

        Perturbation methods are forward-only — the rule choice never
        executes — so their engines compile the (identical) forward under
        saliency rules and share it with every other saliency consumer via
        the build cache.
        """
        return self.method if self.method in RULE_SETS else "saliency"

    def resolve_backward(self) -> str:
        """The backend ``build`` will actually use (auto-selection rule)."""
        if self.backward != "auto":
            return self.backward
        has_pair = getattr(self.model, "has_pair", False)
        if self.precision == "fxp16":
            if not has_pair:
                raise ValueError(
                    "precision='fxp16' needs a model with a seed-batched "
                    "pair (CNNModel(use_pallas=True))")
            return "seed_batched"
        return "seed_batched" if has_pair else "vjp"

    def resolve_plan(self):
        """The ``TilePlan`` the built engine's kernels will run, or None.

        An explicit ``plan`` wins; otherwise a ``device`` name triggers the
        resource-aware planner over the model's kernel shapes — ``plan_cnn``
        for CNN handles, ``plan_lm`` (the SSM scan's ``(d_tile, chunk)``
        knobs) for LM handles with mamba/hybrid segments; Fn models and
        dense LM stacks have no planned Pallas kernels.  Seed
        fan-out comes from ``targets`` (TopK rides the seeds axis through
        every fused backward, so it scales the planned footprints).

        The budget audit covers the spec's declared shapes: ``batch`` (or
        1) x the targets fan-out.  Composite methods that FOLD extra axes
        into the batch dim at call time (``ig(steps=)``, ``smoothgrad(n=)``
        with ``batched=True``) run the same kernels at a larger M than was
        audited — ``Engine._engine_for_fold`` closes that gap per call:
        it re-audits the folded footprint against the profile budget,
        re-plans (or raises ``InfeasiblePlanError``) when the planned tiles
        no longer fit, and memoizes the decision per folded size.
        """
        if self.plan is not None:
            return self.plan
        if self.device is None or not hasattr(self.model, "cfg"):
            return None
        if hasattr(self.model, "token_step"):
            # LM handle: plan the SSM scan chunking (dense stacks have no
            # planned Pallas kernel — None keeps the default launches).
            cfg = self.model.cfg
            if not any(k in ("mamba", "hybrid")
                       for k, _, _ in cfg.layer_plan()):
                return None
            from repro.plan import LM_PLAN_SEQ, TuningCache, plan_lm
            return plan_lm(cfg, device=self.device, precision=self.precision,
                           batch=self.batch or 1, seq=LM_PLAN_SEQ,
                           autotune=self.autotune,
                           cache=TuningCache() if self.autotune else None)
        if not getattr(self.model, "has_pair", False):
            return None
        from repro.plan import TuningCache, plan_cnn
        seeds = self.targets.k if isinstance(self.targets, TopK) else 1
        cache = TuningCache() if self.autotune else None
        return plan_cnn(self.model.cfg, device=self.device,
                        precision=self.precision, batch=self.batch or 1,
                        seeds=seeds, autotune=self.autotune, cache=cache)
