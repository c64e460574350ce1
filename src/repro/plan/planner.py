"""Tile planner: enumerate legal candidates, rank analytically, autotune.

The planning loop per kernel is the paper's HLS design-space sweep:

  1. enumerate ALIGNED candidates — sublane-multiple conv Cout tiles,
     sublane-/lane-aligned pow2 (tm, tk, tn) triples for the matmuls —
     every one of which divides the padded dim it tiles;
  2. compute the analytic :class:`~repro.plan.model.Footprint` of each and
     REJECT any whose on-chip bytes exceed the profile budget;
  3. rank the survivors by the roofline time estimate (ties prefer the
     larger tile: fewer grid cells, fewer block reloads);
  4. optionally (``autotune=True``) measure the top candidates with the
     real Pallas kernels on zero-filled operands and keep the fastest.

:func:`plan_cnn` runs that loop over every kernel launch of the paper CNN
(conv fwd, fused conv BP, pool, FC fwd, fused FC BP — per layer) and
returns a :class:`TilePlan`, the pytree-of-tiles that
``repro.models.cnn`` threads into each wrapper.  A
:class:`~repro.plan.cache.TuningCache` short-circuits the whole loop on a
hit, so warm builds replan in microseconds without re-measuring.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from repro.kernels.tiling import LANE, SUBLANE, align_up, pow2_span
from repro.plan import model as cost
from repro.plan.cache import TuningCache, cache_key
from repro.plan.profiles import MeshProfile, get_profile

#: precision -> operand dtype recorded in cache keys.
PLAN_DTYPES = {"f32": "float32", "bf16": "bfloat16", "fxp16": "int16"}

#: candidates measured per kernel when ``autotune=True``.
AUTOTUNE_TOP_K = 3


class InfeasiblePlanError(ValueError):
    """No candidate tile fits the profile's on-chip budget."""


# ---------------------------------------------------------------------------
# tiles and the plan pytree
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ConvTile:
    """Cout tile of the conv kernels (fwd and fused bwd)."""

    co_tile: int


@dataclass(frozen=True)
class VmmTile:
    """(M, K, N) block triple of the forward FC matmul."""

    tm: int
    tk: int
    tn: int


@dataclass(frozen=True)
class VmmBwdTile:
    """(K, N) block pair of the fused FC backward (M rides whole)."""

    tk: int
    tn: int


@dataclass(frozen=True)
class ScanTile:
    """(d_tile, chunk) pair of the selective-scan kernel: how many channels
    ride one grid cell and how many timesteps one sequential chunk covers.
    Grid splits are bitwise-neutral for the scan (each (d, n) element's
    per-timestep trajectory is computed in the same op order regardless of
    the split), so the knob trades VMEM for HBM reloads, never numerics."""

    d_tile: int
    chunk: int


@dataclass(frozen=True)
class TilePlan:
    """Frozen mapping ``layer-kernel key -> tile`` for one device target.

    Keys follow the CNN layer walk: ``conv{i}.fwd`` / ``conv{i}.bwd`` /
    ``fc{i}.fwd`` / ``fc{i}.bwd``.  Hashable (it rides inside
    ``EngineSpec``) and stable under iteration order.
    """

    device: str
    precision: str
    entries: Tuple[Tuple[str, Any], ...]

    def __post_init__(self):
        object.__setattr__(self, "_index", dict(self.entries))

    def get(self, key: str, default=None):
        return self._index.get(key, default)

    def keys(self) -> Tuple[str, ...]:
        return tuple(k for k, _ in self.entries)

    def __len__(self) -> int:
        return len(self.entries)

    def summary(self) -> str:
        lines = [f"TilePlan(device={self.device}, precision={self.precision})"]
        for key, tile in self.entries:
            lines.append(f"  {key:12s} {tile}")
        return "\n".join(lines)


def _encode_tile(tile) -> List[int]:
    if isinstance(tile, ConvTile):
        return [tile.co_tile]
    if isinstance(tile, VmmTile):
        return [tile.tm, tile.tk, tile.tn]
    if isinstance(tile, ScanTile):
        return [tile.d_tile, tile.chunk]
    return [tile.tk, tile.tn]


_TILE_ARITY = {"conv2d_fwd": 1, "conv2d_bwd": 1, "vmm_fwd": 3, "vmm_bwd": 2,
               "ssm_scan": 2}


def _decode_tile(family: str, blob) -> Any:
    """Cache blob -> tile, or ``ValueError`` on an arity/shape mismatch
    (the planner treats that as a cache miss and replans, never crashes)."""
    vals = [int(v) for v in blob]
    arity = _TILE_ARITY.get(family)
    if arity is None or len(vals) != arity:
        raise ValueError(f"cache blob {blob!r} does not decode as a "
                         f"{family} tile (need {arity} ints)")
    if family in ("conv2d_fwd", "conv2d_bwd"):
        return ConvTile(*vals)
    if family == "vmm_fwd":
        return VmmTile(*vals)
    if family == "ssm_scan":
        return ScanTile(*vals)
    return VmmBwdTile(*vals)


# ---------------------------------------------------------------------------
# autotune measurement (module-level so tests can stub/count)
# ---------------------------------------------------------------------------


def _measure_us(fn, iters: int = 2) -> float:
    import jax
    out = fn()                                   # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _operand_dtype(precision: str):
    import jax.numpy as jnp
    return {"f32": jnp.float32, "bf16": jnp.bfloat16,
            "fxp16": jnp.int16}[precision]


def measure_kernel(family: str, kw: Dict[str, Any], tile,
                   precision: str) -> float:
    """Wall-time one real kernel launch under ``tile`` (zero operands)."""
    import functools

    import jax
    import jax.numpy as jnp

    dt = _operand_dtype(precision)
    fxp = precision == "fxp16"
    if family == "conv2d_fwd":
        from repro.kernels.conv2d.conv2d import conv2d_pallas
        from repro.kernels.conv2d.fxp import conv2d_fxp_pallas
        x = jnp.zeros((kw["n"], kw["h"], kw["w"], kw["cin"]), dt)
        w = jnp.zeros((kw["k"], kw["k"], kw["cin"], kw["cout"]), dt)
        op = conv2d_fxp_pallas if fxp else conv2d_pallas
        fn = jax.jit(functools.partial(op, co_tile=tile.co_tile))
        return _measure_us(lambda: fn(x, w))
    if family == "conv2d_bwd":
        from repro.kernels.conv2d.conv2d import conv2d_bwd_fused_pallas
        from repro.kernels.conv2d.fxp import conv2d_bwd_fused_fxp_pallas
        s, n, hg, wg = kw["s"], kw["n"], kw["hg"], kw["wg"]
        k, c, cout = kw["k"], kw["c"], kw["cout"]
        pooled, gated = kw["pooled"], kw.get("gated", True)
        h, w_sp = (2 * hg, 2 * wg) if pooled else (hg, wg)
        g = jnp.zeros((s, n, hg, wg, c), dt)
        wt = jnp.zeros((k, k, c, cout), dt)
        idx = (jnp.zeros((n, hg, wg, -(-c // 4)), jnp.uint8)
               if pooled else None)
        mask = (jnp.zeros((n, h, w_sp, -(-c // 8)), jnp.uint8)
                if gated else None)
        op = conv2d_bwd_fused_fxp_pallas if fxp else conv2d_bwd_fused_pallas
        fn = jax.jit(functools.partial(op, pool_idx=idx, relu_mask=mask,
                                       gate=gated, co_tile=tile.co_tile))
        return _measure_us(lambda: fn(g, wt))
    if family == "vmm_fwd":
        from repro.kernels.vmm.fxp import vmm_fxp_pallas
        from repro.kernels.vmm.vmm import vmm_pallas
        x = jnp.zeros((kw["m"], kw["k"]), dt)
        w = jnp.zeros((kw["k"], kw["n"]), dt)
        op = vmm_fxp_pallas if fxp else vmm_pallas
        fn = jax.jit(functools.partial(op, tm=tile.tm, tk=tile.tk,
                                       tn=tile.tn))
        return _measure_us(lambda: fn(x, w))
    if family == "vmm_bwd":
        from repro.kernels.vmm.fxp import vmm_bwd_fused_fxp_pallas
        from repro.kernels.vmm.vmm import vmm_bwd_fused_pallas
        s, m, k, n = kw["s"], kw["m"], kw["k"], kw["n"]
        gated = kw.get("gated", True)
        g = jnp.zeros((s, m, k), dt)
        w = jnp.zeros((k, n), dt)
        mask = jnp.zeros((m, -(-k // 8)), jnp.uint8) if gated else None
        op = vmm_bwd_fused_fxp_pallas if fxp else vmm_bwd_fused_pallas
        fn = jax.jit(functools.partial(op, relu_mask=mask, gate=gated,
                                       tk=tile.tk, tn=tile.tn))
        return _measure_us(lambda: fn(g, w))
    if family == "ssm_scan":
        from repro.kernels.ssm_scan.ssm_scan import selective_scan_pallas
        b, s, d, n = kw["b"], kw["s"], kw["d"], kw["n"]
        dt_ = jnp.zeros((b, s, d), jnp.float32)   # call site casts dt to f32
        x = jnp.zeros((b, s, d), dt)
        bm = jnp.zeros((b, s, n), jnp.float32)
        cm = jnp.zeros((b, s, n), jnp.float32)
        a = jnp.zeros((d, n), jnp.float32)
        h0 = jnp.zeros((b, d, n), jnp.float32)
        fn = jax.jit(functools.partial(selective_scan_pallas,
                                       d_tile=tile.d_tile, chunk=tile.chunk))
        return _measure_us(lambda: fn(dt_, x, bm, cm, a, h0))
    raise ValueError(f"unknown kernel family {family!r}")


# ---------------------------------------------------------------------------
# per-family planning
# ---------------------------------------------------------------------------


def _footprint(family: str, kw: Dict[str, Any], tile, precision: str,
               profile) -> cost.Footprint:
    """Footprint of one launch on ``profile`` (its MXU edge, and for the
    conv families its on-chip layout: tiled VMEM or flat RAM)."""
    mxu = profile.mxu
    layout = (profile.sublane, profile.lane) if profile.tiled_vmem else None
    if family == "conv2d_fwd":
        return cost.conv2d_fwd_footprint(
            kw["n"], kw["h"], kw["w"], kw["k"], kw["cin"], kw["cout"],
            tile.co_tile if tile is not None else None,
            precision=precision, mxu=mxu, layout=layout)
    if family == "conv2d_bwd":
        return cost.conv2d_bwd_footprint(
            kw["s"], kw["n"], kw["hg"], kw["wg"], kw["k"], kw["c"],
            kw["cout"], tile.co_tile if tile is not None else None,
            pooled=kw["pooled"], gated=kw.get("gated", True),
            precision=precision, mxu=mxu, layout=layout)
    if family == "vmm_fwd":
        t = tile or VmmTile(None, None, None)
        return cost.vmm_fwd_footprint(kw["m"], kw["k"], kw["n"],
                                      t.tm, t.tk, t.tn,
                                      precision=precision, mxu=mxu)
    if family == "vmm_bwd":
        t = tile or VmmBwdTile(None, None)
        return cost.vmm_bwd_footprint(kw["s"], kw["m"], kw["k"], kw["n"],
                                      t.tk, t.tn,
                                      gated=kw.get("gated", True),
                                      precision=precision, mxu=mxu)
    if family == "pool":
        return cost.pool_footprint(kw["n"], kw["h"], kw["w"], kw["c"],
                                   precision=precision)
    if family == "ssm_scan":
        return cost.ssm_scan_footprint(
            kw["b"], kw["s"], kw["d"], kw["n"],
            tile.d_tile if tile is not None else None,
            tile.chunk if tile is not None else kw["chunk_default"],
            precision=precision)
    raise ValueError(f"unknown kernel family {family!r}")


def _candidates(family: str, kw: Dict[str, Any]) -> List[Any]:
    if family in ("conv2d_fwd", "conv2d_bwd"):
        return [ConvTile(t)
                for t in pow2_span(SUBLANE, align_up(kw["cout"], SUBLANE))]
    if family == "vmm_fwd":
        tms = pow2_span(SUBLANE, align_up(kw["m"], SUBLANE))
        tks = pow2_span(LANE, align_up(kw["k"], LANE))
        tns = pow2_span(LANE, align_up(kw["n"], LANE))
        return [VmmTile(tm, tk, tn)
                for tm in tms for tk in tks for tn in tns]
    if family == "vmm_bwd":
        tks = pow2_span(LANE, align_up(kw["k"], LANE))
        tns = pow2_span(LANE, align_up(kw["n"], LANE))
        return [VmmBwdTile(tk, tn) for tk in tks for tn in tns]
    if family == "ssm_scan":
        # d_tile must DIVIDE the channel axis (the kernel asserts it);
        # chunk lengths are free pow2s — the kernel pads the tail chunk.
        d = kw["d"]
        dts = [t for t in pow2_span(SUBLANE, d) if d % t == 0]
        cks = pow2_span(SUBLANE, align_up(kw["s"], SUBLANE))
        return [ScanTile(dt, ck) for dt in dts for ck in cks]
    raise ValueError(f"no tile candidates for family {family!r}")


def _tile_volume(tile) -> int:
    if isinstance(tile, ConvTile):
        return tile.co_tile
    if isinstance(tile, VmmTile):
        return tile.tm * tile.tk * tile.tn
    if isinstance(tile, ScanTile):
        return tile.d_tile * tile.chunk
    return tile.tk * tile.tn


def _plan_family(family: str, kw: Dict[str, Any], profile, precision: str,
                 autotune: bool) -> Tuple[Any, Optional[float]]:
    """The four-step sweep: enumerate -> reject over-budget -> rank ->
    (optionally) measure.  Returns ``(tile, measured_us | None)``."""
    scored = []
    for tile in _candidates(family, kw):
        fp = _footprint(family, kw, tile, precision, profile)
        if fp.fits(profile):
            scored.append((fp.est_time_s(profile), -_tile_volume(tile), tile))
    if not scored:
        raise InfeasiblePlanError(
            f"{family} {kw} has no tile fitting {profile.name}'s "
            f"{profile.vmem_bytes} B on-chip budget under "
            f"precision={precision!r}")
    scored.sort(key=lambda t: t[:2])
    if not autotune:
        return scored[0][2], None
    best_us, best = None, scored[0][2]
    for _, _, tile in scored[:AUTOTUNE_TOP_K]:
        us = measure_kernel(family, kw, tile, precision)
        if best_us is None or us < best_us:
            best_us, best = us, tile
    return best, best_us


def plan_conv2d(n: int, h: int, w: int, k: int, cin: int, cout: int, *,
                profile=None, precision: str = "f32",
                autotune: bool = False) -> ConvTile:
    """Plan the conv forward Cout tile for one layer shape."""
    profile = get_profile(profile)
    kw = dict(n=n, h=h, w=w, k=k, cin=cin, cout=cout)
    return _plan_family("conv2d_fwd", kw, profile, precision, autotune)[0]


def plan_vmm(m: int, k: int, n: int, *, profile=None,
             precision: str = "f32", autotune: bool = False) -> VmmTile:
    """Plan the FC forward (tm, tk, tn) triple for one matmul shape."""
    profile = get_profile(profile)
    kw = dict(m=m, k=k, n=n)
    return _plan_family("vmm_fwd", kw, profile, precision, autotune)[0]


# ---------------------------------------------------------------------------
# whole-model planning (the paper CNN layer walk)
# ---------------------------------------------------------------------------


def shard_batch_seeds(batch: int, seeds: int,
                      n_shards: int) -> Tuple[int, int]:
    """Per-shard ``(batch, seeds)`` of a mesh-sharded engine launch.

    The engine shard_maps the batch axis only: it pads the batch to a
    multiple of ``n_shards``, so every shard runs ``ceil(batch / n)``
    examples, and every shard runs the whole seeds axis (the top-K panel
    rides along with its examples).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    return -(-max(batch, 1) // n_shards), max(seeds, 1)


def cnn_kernel_shapes(cfg, batch: int = 1, seeds: int = 1):
    """Every kernel launch of the CNN's forward + fused-BP stack, in layer
    order: ``(key, family, shape-kwargs)`` triples.  This single walk is
    shared by the planner, the footprint audit, and the tests."""
    out = []
    h, w = cfg.in_hw
    cin, k = cfg.in_ch, cfg.kernel
    for i, cout in enumerate(cfg.channels):
        pooled = (i + 1) % cfg.pool_every == 0
        out.append((f"conv{i}.fwd", "conv2d_fwd",
                    dict(n=batch, h=h, w=w, k=k, cin=cin, cout=cout)))
        hg, wg = (h // 2, w // 2) if pooled else (h, w)
        out.append((f"conv{i}.bwd", "conv2d_bwd",
                    dict(s=seeds, n=batch, hg=hg, wg=wg, k=k, c=cout,
                         cout=cin, pooled=pooled, gated=cfg.conv_relu)))
        if pooled:
            out.append((f"pool{i}", "pool", dict(n=batch, h=h, w=w, c=cout)))
            h, w = h // 2, w // 2
        cin = cout
    fin = cfg.flat_features()
    dims = tuple(cfg.fc) + (cfg.num_classes,)
    n_fc = len(dims)
    for i, f in enumerate(dims):
        out.append((f"fc{i}.fwd", "vmm_fwd", dict(m=batch, k=fin, n=f)))
        out.append((f"fc{i}.bwd", "vmm_bwd",
                    dict(s=seeds, m=batch, k=f, n=fin, gated=i < n_fc - 1)))
        fin = f
    return out


def plan_cnn(cfg, device=None, precision: str = "f32", *, batch: int = 1,
             seeds: int = 1, autotune: bool = False,
             cache: Optional[TuningCache] = None) -> TilePlan:
    """Plan every kernel of the CNN stack for ``device``.

    ``cache`` (a :class:`TuningCache`) short-circuits planning AND
    measuring per kernel on a hit; misses are planned, measured when
    ``autotune`` is set, and written through.  Pool launches carry no tile
    knob but are still audited against the budget.

    A :class:`~repro.plan.profiles.MeshProfile` device splits the batch
    across its shards FIRST (:func:`shard_batch_seeds`) and
    tiles the per-shard slice against the per-core budget — the paper's
    fit-the-envelope discipline applied per core of an N-core mesh.  On a
    1-shard mesh the local shapes equal the global ones, so the plan is
    identical to the underlying single-core profile's.
    """
    if precision not in PLAN_DTYPES:
        raise ValueError(f"precision={precision!r} not in "
                         f"{tuple(PLAN_DTYPES)}")
    profile = get_profile(device)
    if isinstance(profile, MeshProfile):
        batch, seeds = shard_batch_seeds(batch, seeds, profile.n_shards)
    dtype = PLAN_DTYPES[precision]
    entries = []
    for key, family, kw in cnn_kernel_shapes(cfg, batch, seeds):
        if family == "pool":
            fp = _footprint(family, kw, None, precision, profile)
            if not fp.fits(profile):
                raise InfeasiblePlanError(
                    f"{key} ({family} {kw}) needs {fp.vmem_bytes} B on-chip "
                    f"> {profile.name}'s {profile.vmem_bytes} B budget")
            continue
        ck = None
        if cache is not None:
            sig = [int(v) for v in kw.values()]
            ck = cache_key(family, sig, dtype, precision, profile.name)
            # an analytic-only entry must not satisfy an autotuned build
            hit = cache.lookup(ck, require_measured=autotune)
            if hit is not None:
                try:
                    entries.append((key, _decode_tile(family, hit["tile"])))
                    continue
                except (KeyError, TypeError, ValueError):
                    pass        # wrong-family blob: replan + store over it
        tile, measured = _plan_family(family, kw, profile, precision,
                                      autotune)
        if cache is not None:
            cache.store(ck, {"family": family, "tile": _encode_tile(tile),
                             "measured_us": measured})
        entries.append((key, tile))
    return TilePlan(device=profile.name, precision=precision,
                    entries=tuple(entries))


def cnn_plan_footprints(cfg, plan: Optional[TilePlan], *,
                        precision: str = "f32", batch: int = 1,
                        seeds: int = 1, profile=None
                        ) -> Dict[str, cost.Footprint]:
    """Analytic footprint of every kernel launch under ``plan`` (missing
    entries fall back to the default tile policy) — the per-layer resource
    audit the acceptance tests check against the profile budget.  Mesh
    profiles audit the per-shard slice (the shapes the planner tiled),
    matching :func:`plan_cnn`'s split."""
    profile = get_profile(profile if profile is not None
                          else (plan.device if plan else None))
    if isinstance(profile, MeshProfile):
        batch, seeds = shard_batch_seeds(batch, seeds, profile.n_shards)
    out = {}
    for key, family, kw in cnn_kernel_shapes(cfg, batch, seeds):
        tile = plan.get(key) if plan is not None else None
        out[key] = _footprint(family, kw, tile, precision, profile)
    return out


# ---------------------------------------------------------------------------
# whole-model planning (the LM attribution stack)
# ---------------------------------------------------------------------------

#: sequence length the engine plans LM kernels at.  The scan's per-cell
#: VMEM is sequence-independent once ``chunk <= s`` (the kernel clamps
#: ``ck = min(chunk, s)``), so one planning length serves every bucket.
LM_PLAN_SEQ = 128


def lm_kernel_shapes(cfg, batch: int = 1, seq: int = LM_PLAN_SEQ):
    """Every planned kernel launch of the LM attribution stack, in segment
    order: ``(key, family, shape-kwargs)`` triples.

    Today that is one ``ssm_scan`` launch per mamba/hybrid segment of
    ``cfg.layer_plan()`` (the chunk-length knob is the first LM knob — the
    attention/FFN matmuls stay on XLA and are follow-on work).
    ``chunk_default`` records the config's unplanned chunk length so the
    tile=None footprint models the launch the step runs without a plan.
    """
    out = []
    for si, (kind, _count, _window) in enumerate(cfg.layer_plan()):
        if kind in ("mamba", "hybrid"):
            out.append((f"ssm{si}.scan", "ssm_scan",
                        dict(b=batch, s=seq, d=cfg.d_inner, n=cfg.ssm_state,
                             chunk_default=cfg.ssm_chunk)))
    return out


def plan_lm(cfg, device=None, precision: str = "f32", *, batch: int = 1,
            seq: int = LM_PLAN_SEQ, autotune: bool = False,
            cache: Optional[TuningCache] = None) -> TilePlan:
    """Plan the LM attribution stack's Pallas launches for ``device``,
    mirroring :func:`plan_cnn`: enumerate aligned (d_tile, chunk)
    candidates per ssm segment, reject over-budget ones, rank by the cost
    model, optionally refine by measurement, raise
    :class:`InfeasiblePlanError` when nothing fits.

    No ``fxp16``: the LM stack is a float (f32/bf16) path — token
    attribution runs through ``jax.vjp``, not the int16 manual backward.
    """
    if precision not in ("f32", "bf16"):
        raise ValueError(f"plan_lm supports precision f32|bf16, "
                         f"got {precision!r}")
    profile = get_profile(device)
    if isinstance(profile, MeshProfile):
        batch, _ = shard_batch_seeds(batch, 1, profile.n_shards)
    dtype = PLAN_DTYPES[precision]
    entries = []
    for key, family, kw in lm_kernel_shapes(cfg, batch, seq):
        ck = None
        if cache is not None:
            sig = [int(v) for v in kw.values()]
            ck = cache_key(family, sig, dtype, precision, profile.name)
            hit = cache.lookup(ck, require_measured=autotune)
            if hit is not None:
                try:
                    entries.append((key, _decode_tile(family, hit["tile"])))
                    continue
                except (KeyError, TypeError, ValueError):
                    pass        # wrong-family blob: replan + store over it
        tile, measured = _plan_family(family, kw, profile, precision,
                                      autotune)
        if cache is not None:
            cache.store(ck, {"family": family, "tile": _encode_tile(tile),
                             "measured_us": measured})
        entries.append((key, tile))
    return TilePlan(device=profile.name, precision=precision,
                    entries=tuple(entries))


def lm_plan_footprints(cfg, plan: Optional[TilePlan], *,
                       precision: str = "f32", batch: int = 1,
                       seq: int = LM_PLAN_SEQ, profile=None
                       ) -> Dict[str, cost.Footprint]:
    """Analytic footprint of every LM kernel launch under ``plan`` (None
    entries model the unplanned whole-D launch) — the budget audit the
    acceptance tests check, mirroring :func:`cnn_plan_footprints`."""
    profile = get_profile(profile if profile is not None
                          else (plan.device if plan else None))
    if isinstance(profile, MeshProfile):
        batch, _ = shard_batch_seeds(batch, 1, profile.n_shards)
    out = {}
    for key, family, kw in lm_kernel_shapes(cfg, batch, seq):
        tile = plan.get(key) if plan is not None else None
        out[key] = _footprint(family, kw, tile, precision, profile)
    return out
