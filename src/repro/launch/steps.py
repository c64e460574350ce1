"""pjit-able steps: train / prefill / decode / attribute, plus their
sharding trees.  These are the programs the multi-pod dry-run lowers for
every (arch x shape) cell and the drivers execute for real.

Numerics: f32 master params + Adam moments; bf16 compute casts (except
SSM dynamics params, kept f32 — exp() of bf16 decay rates is lossy).
Gradients accumulate in f32 across microbatches (lax.scan), the memory/
throughput trade the paper's "tile-based computation" corresponds to at
pod scale.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import attribution
from repro.dist import params as dist_params
from repro.engine import methods as engine_methods
from repro.dist.sharding import physical_spec
from repro.models import transformer as tf
from repro.models.config import SSM_KINDS, ModelConfig
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, cosine_schedule

_KEEP_F32 = ("A_log", "dt_bias", "D")   # SSM dynamics: stay f32 in compute


class TrainState(NamedTuple):
    params: Dict     # f32 master
    opt: object      # AdamWState


# ---------------------------------------------------------------------------
# casts / loss
# ---------------------------------------------------------------------------


def cast_for_compute(params, cfg: ModelConfig):
    def cast(path, p):
        name = dist_params._leaf_name(path)
        if p.ndim >= 2 and p.dtype == jnp.float32 and name not in _KEEP_F32:
            return p.astype(cfg.jdtype)
        return p
    return jax.tree_util.tree_map_with_path(cast, params)


def ce_loss(logits, labels, cfg: ModelConfig):
    """Stable CE over the (vocab-sharded) logits; GSPMD-friendly one-hot dot."""
    lg = logits.astype(jnp.float32)
    if cfg.frontend == "patches":       # loss only over the text positions
        lg = lg[:, cfg.n_patches:, :]
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.sum(jax.nn.one_hot(labels, cfg.vocab, dtype=lg.dtype) * lg,
                 axis=-1)
    return jnp.mean(lse - ll)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def make_train_state_init(cfg: ModelConfig):
    cfg32 = cfg.with_(dtype="float32")

    def init_fn(key) -> TrainState:
        params = tf.init(key, cfg32)
        return TrainState(params=params, opt=adamw_init(params))

    return init_fn


def make_train_step(cfg: ModelConfig, *, microbatches: int = 1,
                    peak_lr: float = 2e-4, warmup_steps: int = 100,
                    total_steps: int = 10_000, clip: float = 1.0,
                    triangle_skip: bool = True):
    """(state, batch) -> (state, metrics). ``batch`` = input_specs("train")."""

    def loss_fn(params_c, mb):
        fwd_batch = {k: v for k, v in mb.items() if k != "labels"}
        logits, aux = tf.forward(params_c, cfg, fwd_batch,
                                 triangle_skip=triangle_skip)
        ce = ce_loss(logits, mb["labels"], cfg)
        return ce + aux, ce

    def train_step(state: TrainState, batch: Dict):
        params_c = cast_for_compute(state.params, cfg)
        if microbatches == 1:
            (loss, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params_c, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            mbs = jax.tree.map(
                lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                    + x.shape[1:]), batch)

            def body(carry, mb):
                g_acc, l_acc, ce_acc = carry
                (l, ce), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params_c, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l, ce_acc + ce), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params_c)
            (grads, loss, ce), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss, ce = loss / microbatches, ce / microbatches

        grads, gnorm = clip_by_global_norm(grads, clip)
        lr = cosine_schedule(state.opt.step, peak_lr=peak_lr,
                             warmup_steps=warmup_steps,
                             total_steps=total_steps)
        new_params, new_opt = adamw_update(grads, state.opt, state.params,
                                           lr=lr)
        metrics = {"loss": loss, "ce": ce, "gnorm": gnorm, "lr": lr}
        return TrainState(new_params, new_opt), metrics

    return train_step


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, *, triangle_skip: bool = True):
    def prefill_step(params, batch, cache):
        logits, cache = tf.prefill(params, cfg, batch, cache,
                                   triangle_skip=triangle_skip)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, cache, tokens, pos):
        logits, cache = tf.decode_step(params, cfg, tokens, cache, pos)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache

    return decode_step


#: Per-token score reductions ``make_attribute_step`` can compile.
TOKEN_MODES = ("ixg", "grad_norm", "contrastive")


def ssm_scan_tiles(cfg: ModelConfig, plan=None):
    """Per-SEGMENT ``{si: (d_tile, chunk)}`` launch knobs for the SSM scan.

    LM attribution always routes SSM segments through the Pallas scan
    kernel; this maps a ``repro.plan.TilePlan``'s ``ssm<si>.scan`` entries
    (see ``repro.plan.lm_kernel_shapes``) onto the launch knobs.  Segments
    without a plan entry — and the whole stack when ``plan`` is None — get
    the UNPLANNED launch: the whole channel dim in one grid cell
    (``d_tile=cfg.d_inner``) at the model's native ``ssm_chunk``.  Grid
    splits are bitwise-neutral for the scan, so planned and unplanned
    launches compute identical bits.  Returns None for stacks with no SSM
    segments (dense/moe: nothing to tile).
    """
    tiles = {}
    for si, (kind, _, _) in enumerate(cfg.layer_plan()):
        if kind not in SSM_KINDS:
            continue
        t = plan.get(f"ssm{si}.scan") if plan is not None else None
        tiles[si] = ((t.d_tile, t.chunk) if t is not None
                     else (cfg.d_inner, cfg.ssm_chunk))
    return tiles or None


def make_token_step(cfg: ModelConfig, method: str = "saliency", *,
                    triangle_skip: bool = True, plan=None,
                    contrastive: bool = False):
    """``(params, batch) -> (last-position logits [B, V], {mode: [B, S]})``:
    one FP + input-gradient BP, reduced per prompt position.  A prompt's
    left padding (:data:`repro.models.transformer.PAD_ID`) is masked out
    of every mixer, so its positions score zero.

    The argmax seed gives both gradient reductions from the one BP
    (``ixg`` and ``grad_norm``); ``contrastive`` seeds argmax minus
    runner-up and gives ``contrastive``.  Only the last position's head
    is computed, and each layer is rematerialised by ``cfg.remat``.
    """
    scan_tiles = ssm_scan_tiles(cfg, plan)

    def token_step(params, batch):
        h = tf.embed_inputs(params, cfg, batch)
        enc_frames = batch.get("frames")
        live = tf.live_positions(batch["tokens"], h.shape[1])

        def f(e):
            return tf.forward_from_embeddings(
                params, cfg, e, method=method, enc_frames=enc_frames,
                triangle_skip=triangle_skip, scan_tiles=scan_tiles,
                last_only=True, live=live)[0]

        if contrastive:
            logits, _, scores = engine_methods.attribute_tokens_contrastive(
                f, h)
            return logits[:, -1, :], {"contrastive": scores}
        logits, rel, scores = attribution.attribute_tokens(f, h)
        return logits[:, -1, :], {
            "ixg": scores,
            "grad_norm": jnp.linalg.norm(rel.astype(jnp.float32), axis=-1)}

    return token_step


def make_attribute_step(cfg: ModelConfig, method: str = "saliency", *,
                        triangle_skip: bool = True, plan=None,
                        mode: str = "ixg"):
    """The paper's technique as a serving feature: FP + input-grad BP.

    Returns per-position relevance scores [B, S] for the final-position
    prediction (VLM: the first n_patches scores are the image heatmap).
    ``mode`` picks the per-token reduction:

      * ``"ixg"`` — input x gradient (signed), the default heatmap;
      * ``"grad_norm"`` — L2 norm of the embedding gradient (pure saliency);
      * ``"contrastive"`` — argmax-vs-runner-up difference seed
        (:func:`repro.engine.methods.attribute_tokens_contrastive`).

    ``plan`` (a ``repro.plan.TilePlan`` from ``plan_lm``) threads planned
    ``(d_tile, chunk)`` launch knobs into the SSM Pallas scan of every
    mamba/hybrid segment; None keeps the unplanned whole-D launch (same
    bits — the scan's grid splits are bitwise-neutral).
    """
    if mode not in TOKEN_MODES:
        raise ValueError(f"mode={mode!r} not in {TOKEN_MODES}")
    step = make_token_step(cfg, method, triangle_skip=triangle_skip,
                           plan=plan, contrastive=mode == "contrastive")

    def attribute_step(params, batch):
        logits, scores = step(params, batch)
        return logits, scores[mode]

    return attribute_step


# ---------------------------------------------------------------------------
# sharding trees
# ---------------------------------------------------------------------------


def batch_shardings(batch_sds: Dict, mesh: Mesh):
    def spec(k, v):
        if v.ndim == 2 and v.dtype == jnp.int32:
            return physical_spec(("batch", None), mesh)
        return physical_spec(("batch",) + (None,) * (v.ndim - 1), mesh)
    return {k: NamedSharding(mesh, spec(k, v)) for k, v in batch_sds.items()}


def state_shardings(state_sds: TrainState, mesh: Mesh) -> TrainState:
    pshard = dist_params.param_sharding_tree(state_sds.params, mesh)
    opt = state_sds.opt
    return TrainState(
        params=pshard,
        opt=type(opt)(
            step=NamedSharding(mesh, P()),
            mu=dist_params.param_sharding_tree(opt.mu, mesh),
            nu=dist_params.param_sharding_tree(opt.nu, mesh),
        ),
    )


def cache_shardings(cfg: ModelConfig, cache_sds, mesh: Mesh,
                    batch_size: int):
    """KV/state cache shardings.

    Batch >= DP size: shard batch over (pod, data).  Small-batch long-context
    decode (long_500k): sequence-parallel instead — the cache T axis shards
    over "data" and the fused head axis over "model".
    """
    dp = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            dp *= mesh.shape[ax]
    batch_big = batch_size >= dp

    def spec(path, leaf):
        name = dist_params._leaf_name(path)
        if name in ("k", "v", "ck", "cv"):          # [L, B, T, Kv*hd]
            if batch_big:
                return physical_spec((None, "batch", None, "model"), mesh)
            return physical_spec((None, None, "data", "model"), mesh)
        if name == "h":                              # [L, B, d_inner, N]
            bax = "batch" if batch_big else None
            return physical_spec((None, bax, "model", None), mesh)
        if name == "conv":                           # [L, B, k-1, d_inner]
            bax = "batch" if batch_big else None
            return physical_spec((None, bax, None, "model"), mesh)
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, spec(p, l)), cache_sds)
