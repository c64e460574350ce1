"""LMAdapter — token-attribution serving behind the CNN adapter protocol.

The serve dispatch loop (:mod:`repro.serve.server`) is adapter-agnostic:
admission, micro-batching, tracing, and fault isolation all run the same
whether a request carries an image or a token sequence.  This adapter makes
LM requests flow through it:

  * ``input_kind = "tokens"`` — payloads are int token ids ``[S]``;
  * ``example_shape`` is None — sequences come in many lengths, so the
    server skips its fixed-shape check and the BATCHER's bucket key (which
    includes the payload shape) provides the discipline instead:
    equal-length requests co-batch, different lengths never share a launch.
    :func:`bucket_len` / :func:`pad_tokens` give clients the pow2 length
    grid that keeps the number of compiled programs small;
  * ``predict`` is a jitted last-position-logits forward returning
    ``(logits, None)`` — there are NO replayable residuals for the token
    stack (``mask_reuse=False`` on every token explainer), so the residual
    cache stores nothing useful and :meth:`explain_cached` refuses loudly;
    decode-loop KV/residual reuse is the roadmap stretch;
  * per-rule engines come from the same build cache as everyone else's
    (``replace(spec, method=...)``), so the registry's token explainers ride
    the engine's planned SSM scan.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import engine as engine_lib
from repro.models import transformer as tf

#: Token id LEFT-padding fills with.  The token programs mask the leading
#: run of it out of every mixer (``transformer.live_positions``), so a
#: padded prompt's live positions compute what the unpadded prompt's do.
PAD_ID = tf.PAD_ID

#: Smallest sequence bucket; shorter requests pad up to it.
MIN_BUCKET = 8


def bucket_len(s: int, min_len: int = MIN_BUCKET) -> int:
    """The pow2 sequence-length bucket for a length-``s`` request."""
    n = max(int(s), 1)
    b = max(int(min_len), 1)
    while b < n:
        b *= 2
    return b


def pad_tokens(tokens, length: Optional[int] = None, pad_id: int = PAD_ID):
    """LEFT-pad a ``[S]`` or ``[B, S]`` token array to ``length``
    (default: its :func:`bucket_len`).

    Left padding keeps the live tokens adjacent to the explained position
    (the final one); the token programs mask the padded prefix, whose
    per-position scores are reported as zeros.
    """
    t = jnp.asarray(tokens, jnp.int32)
    s = t.shape[-1]
    length = bucket_len(s) if length is None else int(length)
    if length < s:
        raise ValueError(f"cannot pad length-{s} tokens down to {length}")
    if length == s:
        return t
    pad = [(0, 0)] * (t.ndim - 1) + [(length - s, 0)]
    return jnp.pad(t, pad, constant_values=pad_id)


class LMAdapter:
    """Serve token-level LM attribution through the ExplanationServer."""

    input_kind = "tokens"

    #: token id the prompts are left-padded with
    pad_id = PAD_ID

    def __init__(self, params, cfg, *, store_rules: str = "saliency",
                 precision: str = "f32", device: Optional[str] = None,
                 autotune: bool = False, max_batch: Optional[int] = None):
        self.params = params
        self.cfg = cfg
        self.store_rules = store_rules
        self.precision = precision
        #: the server's seats per launch (None: the server's default); a
        #: long-context model sizes it so the longest bucket's launch fits
        #: beside the weights
        self.max_batch = max_batch
        # The base engine: resolves the SSM scan plan for ``device`` once;
        # per-rule siblings share it via the global build cache.
        self.engine = engine_lib.build(engine_lib.EngineSpec(
            model=engine_lib.LMModel(params, cfg), method=store_rules,
            precision=precision, device=device, autotune=autotune))
        self._engines = {store_rules: self.engine}
        self._predict = None

    @classmethod
    def from_engine(cls, eng: engine_lib.Engine) -> "LMAdapter":
        """Adapt an already-built LM engine as configured."""
        spec = eng.spec
        self = cls.__new__(cls)
        self.params = spec.model.params
        self.cfg = spec.model.cfg
        self.store_rules = spec.method
        self.precision = spec.precision
        self.max_batch = None
        self.engine = eng
        self._engines = {spec.method: eng}
        self._predict = None
        return self

    @property
    def example_shape(self):
        """None: sequences bucket by length (batcher key), not one shape."""
        return None

    @property
    def n_shards(self) -> int:
        return self.engine.n_shards

    # -- engines -------------------------------------------------------------

    def with_precision(self, precision: str) -> "LMAdapter":
        eng = engine_lib.build(replace(self.engine.spec,
                                       precision=precision))
        out = LMAdapter.from_engine(eng)
        out.max_batch = self.max_batch
        return out

    def engine_for(self, rules: str) -> engine_lib.Engine:
        if rules not in self._engines:
            self._engines[rules] = engine_lib.build(
                replace(self.engine.spec, method=rules))
        return self._engines[rules]

    # -- the server programs -------------------------------------------------

    def predict(self, xb) -> Tuple[jnp.ndarray, None]:
        """tokens [B, S] -> (last-position logits [B, V], residuals=None).

        No residuals: the token stack has no replayable mask pair, so a
        PREDICT parks nothing reusable in the cache (the explainers are all
        ``mask_reuse=False`` and never look).
        """
        if self._predict is None:
            from repro.engine.spec import lm_compute_cfg, lm_precision
            cfg = lm_compute_cfg(self.cfg, self.precision)
            method = self.store_rules

            def run(params, tokens):
                h = tf.embed_inputs(params, cfg, {"tokens": tokens})
                logits, _ = tf.forward_from_embeddings(
                    params, cfg, h, method=method, remat=False,
                    last_only=True,
                    live=tf.live_positions(tokens, h.shape[1]))
                return logits[:, -1, :]

            self._predict = jax.jit(lm_precision(run, self.precision))
        return self._predict(self.params, xb), None

    def explain_cached(self, method: str, residuals, seeds):
        raise ValueError(
            "LM serving has no residual replay: token attribution re-runs "
            "the forward (decode-loop KV/residual reuse is a roadmap "
            "stretch); token explainers are mask_reuse=False and never "
            "take this path")

    def model_fn(self, rules: str):
        """LM engines expose no array ``model_fn``; the registry's token
        explainers dispatch through ``engine.explain_tokens`` instead."""
        return self.engine_for(rules).model_fn

    def manual_backward(self, rules: str):
        return self.engine_for(rules).composite_backward
