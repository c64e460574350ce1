"""Model adapters: the narrow waist between the server and the engine.

An adapter owns per-rule-set :class:`repro.engine.Engine` instances —
built once via ``repro.engine.build(EngineSpec(...))`` and shared through
the global build cache — and exposes the three programs the dispatch loop
calls:

  * ``predict(xb)`` — residual-returning forward (``Engine.forward``): the
    bit-packed residuals (ReLU sign bits, 2-bit pool argmax) come back with
    the logits so the server can park them in the
    :class:`~repro.serve.residual_cache.ResidualCache`;
  * ``explain_cached(method, residuals, seeds)`` — the BP phase alone
    (``Engine.replay``), seed-batched over stored masks (paper §III.F);
  * ``engine_for(rules)`` / ``model_fn(rules)`` — the engine (and its
    rule-bound callable) for the registry's cold explainers.

:class:`CNNAdapter` wires the paper's Table III CNN; both cold and cached
paths run the SAME compiled pair, so a cache hit is bit-exact with a cold
explain — it just skips the forward pass.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, Tuple

import jax.numpy as jnp

from repro import engine as engine_lib
from repro.models import cnn


class CNNAdapter:
    """Serve the paper CNN: residual-returning predict + fused BP explain.

    ``store_rules`` picks the rule set masks are stored under at predict
    time.  "saliency" stores the full mask/index set, which every pure-BP
    method can consume (guided ANDs the mask with the gradient sign,
    deconvnet reads only the sign — neither needs masks beyond it), so one
    predict serves follow-up explains of ANY registered mask-reuse method.

    All compiled programs come from ``repro.engine.build``: one engine per
    rule set, derived from the base spec with ``dataclasses.replace`` so
    precision/model/backend are decided exactly once (and shared with any
    other consumer building the same spec).
    """

    input_kind = "image"

    def __init__(self, params, cfg: cnn.CNNConfig, *,
                 store_rules: str = "saliency", precision: str = "f32",
                 device: str = None, autotune: bool = False):
        if precision not in cnn.PRECISIONS:
            raise ValueError(
                f"precision={precision!r} not in {cnn.PRECISIONS}")
        self.params = params
        self.cfg = cfg
        self.store_rules = store_rules
        # Numeric knob (paper §IV): "fxp16" serves TRUE int16 fixed-point —
        # predict stores masks computed in the quantized domain and every
        # explain (hit, cold pure-BP, or composite via the engine's manual
        # ``backward``) replays the fused BP in int16.
        self.precision = precision
        # ``device`` names a repro.plan profile: every engine this adapter
        # builds (and its per-rule siblings, via replace()) serves with
        # tile shapes planned for that resource budget.  A
        # "mesh:<profile>:<n>" name builds mesh-sharded engines — the
        # adapter then reports n_shards and the server batches toward
        # full mesh occupancy.
        self.engine = engine_lib.build(engine_lib.EngineSpec(
            model=engine_lib.CNNModel(params, cfg), method=store_rules,
            precision=precision, device=device, autotune=autotune))
        self._engines: Dict[str, engine_lib.Engine] = {store_rules: self.engine}

    @classmethod
    def from_engine(cls, eng: engine_lib.Engine) -> "CNNAdapter":
        """Adapt an already-built engine AS CONFIGURED; its method is the
        store rule set, and every other spec field (model flags, backend,
        targets, batch) is preserved — per-rule sibling engines derive from
        this spec via ``replace(spec, method=...)``."""
        spec = eng.spec
        self = cls.__new__(cls)
        self.params = spec.model.params
        self.cfg = spec.model.cfg
        self.store_rules = spec.method
        self.precision = spec.precision
        self.engine = eng
        self._engines = {spec.method: eng}
        return self

    @property
    def example_shape(self) -> Tuple[int, int, int]:
        """Expected per-example shape — lets the server reject malformed
        payloads at submit instead of poisoning a compiled batch."""
        return (*self.cfg.in_hw, self.cfg.in_ch)

    @property
    def n_shards(self) -> int:
        """Mesh extent of the base engine (1 = single-core).  The server
        reads this to size the batcher's ``fill_target`` so sharded
        launches run at full mesh occupancy."""
        return self.engine.n_shards

    # -- engines -------------------------------------------------------------

    def with_precision(self, precision: str) -> "CNNAdapter":
        """A sibling adapter serving the SAME weights at another precision
        (the admission layer's ``reroute_precision`` degradation target).
        Engines derive from the base spec via ``replace``, so they share the
        global build cache with any other consumer of that spec."""
        eng = engine_lib.build(replace(self.engine.spec, precision=precision))
        return CNNAdapter.from_engine(eng)

    def engine_for(self, rules: str) -> engine_lib.Engine:
        """The (cached) engine whose backward runs under ``rules`` — same
        spec as the base engine with only the method field changed."""
        if rules not in self._engines:
            self._engines[rules] = engine_lib.build(
                replace(self.engine.spec, method=rules))
        return self._engines[rules]

    # -- forward with residuals ----------------------------------------------

    def predict(self, xb) -> Tuple[jnp.ndarray, Any]:
        """[B, H, W, C] -> (logits [B, num_classes], residual pytree)."""
        return self.engine.forward(xb)

    # -- BP phase over stored residuals --------------------------------------

    def explain_cached(self, method: str, residuals, seeds) -> jnp.ndarray:
        """seeds [S, B, classes] -> relevance [S, B, H, W, Cin]; NO forward."""
        return self.engine_for(method).replay(residuals, seeds)

    # -- rule-bound model fn for cold explainers -----------------------------

    def model_fn(self, rules: str):
        """Under fxp16 the returned ``f`` is the residual forward (pair
        output) — cold composite explainers must pair it with
        :meth:`manual_backward`, since the int16 path has no ``jax.vjp``."""
        return self.engine_for(rules).model_fn

    def manual_backward(self, rules: str):
        """Manual BP engine for registry explainers, or None on float paths
        (where ``jax.vjp`` through :meth:`model_fn` is the engine).  Reuses
        the same compiled program as :meth:`explain_cached` — no duplicate
        compilation of an identical backward."""
        return self.engine_for(rules).composite_backward
