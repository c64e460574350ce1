"""The plain reference: the paper CNN and its three backward rules in jnp.

It follows the paper (Table II/III, Eq. 3-5), not the program, and imports
nothing of it:

* conv (SAME, stride 1) + bias -> ReLU -> 2x2 max-pool after every
  ``pool_every``-th conv; then FC + ReLU hidden layers and the output FC;
* the backward of every layer is its transposed conv or matmul; a pool
  routes the gradient to the first maximum of its window (row-major, the
  2-bit index the forward stores); a ReLU passes ``g`` where the forward
  input was positive (saliency, Eq. 3), where ``g`` is positive
  (deconvnet, Eq. 4), or where both are (guided, Eq. 5).

``mode`` sets the arithmetic of every conv and matmul, forward and back:

* ``"highest"``: float32 products and sums (the configuration's f32);
* ``"high"``: three bfloat16 passes (``hi*hi + hi*lo + lo*hi``), the
  precision below float32 at highest: the control of f32 configurations.
  On a TPU it is the MXU's own ``Precision.HIGH``; elsewhere, where XLA
  computes f32 dots in full whatever the precision asked, the split is
  written out;
* ``"int8"``: 8-bit fixed point (Q3.4 activations and gradients, Q1.6
  weights, backward seeds pre-scaled by 2^6 as the 16-bit path does), the
  precision below int16 fixed point: the control of fxp16 configurations.

The parameters are arguments of the jitted programs, so the reference
compiles once per shape whatever the weights.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_DN = ("NHWC", "HWIO", "NHWC")
#: the 8-bit control's fixed-point formats and seed gain
INT8_ACT_FRAC, INT8_WGT_FRAC, INT8_SEED_GAIN = 4, 6, 64.0
BIAS_STD = 0.05
#: rows per reference launch
BLOCK = 128


# -- weights ------------------------------------------------------------------

def init_params(model: dict, weight_seed: int):
    """He-normal weights and N(0, BIAS_STD) biases, made on the device in one
    jitted call from the seed, in float32 (the type the program takes)."""
    shapes: Dict[str, List[Tuple[tuple, tuple]]] = {"conv": [], "fc": []}
    cin, k = model["in_ch"], model["kernel"]
    for c in model["channels"]:
        shapes["conv"].append(((k, k, cin, c), (c,)))
        cin = c
    h, w = model["in_hw"]
    n_pools = len(model["channels"]) // model["pool_every"]
    fin = (h >> n_pools) * (w >> n_pools) * cin
    for f in list(model["fc"]) + [model["num_classes"]]:
        shapes["fc"].append(((fin, f), (f,)))
        fin = f

    @jax.jit
    def make(key):
        out = {"conv": [], "fc": []}
        for group in ("conv", "fc"):
            for wshape, bshape in shapes[group]:
                key, kw, kb = jax.random.split(key, 3)
                fan_in = int(np.prod(wshape[:-1]))
                out[group].append({
                    "w": jax.random.normal(kw, wshape, jnp.float32)
                    * np.float32(np.sqrt(2.0 / fan_in)),
                    "b": jax.random.normal(kb, bshape, jnp.float32)
                    * np.float32(BIAS_STD)})
        return out

    return make(jax.random.PRNGKey(int(weight_seed) & 0x7FFFFFFF))


# -- arithmetic modes -----------------------------------------------------------

def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _fixed(x, frac: int, bits: int = 8):
    scale = float(1 << frac)
    lim = float(1 << (bits - 1))
    return jnp.clip(jnp.round(x * scale), -lim, lim - 1) / scale


def _ste(x, frac: int):
    """Round onto the fixed-point grid going forward; pass gradients."""
    return x + jax.lax.stop_gradient(_fixed(x, frac) - x)


def _contract(fn, a, b, mode: str):
    if mode in ("highest", "int8"):     # int8 operands are on their grid
        return fn(a, b, precision=HIGHEST)
    if mode == "high":
        if jax.default_backend() == "tpu":
            return fn(a, b, precision=jax.lax.Precision.HIGH)
        ah, al = _split(a)
        bh, bl = _split(b)
        f32 = jnp.float32
        return (fn(ah, bh, preferred_element_type=f32)
                + fn(ah, bl, preferred_element_type=f32)
                + fn(al, bh, preferred_element_type=f32))
    raise ValueError(f"unknown reference mode {mode!r}")


def _conv2d(x, w, **kw):
    return jax.lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                        dimension_numbers=_DN, **kw)


def _matmul(x, w, **kw):
    return jnp.matmul(x, w, **kw)


# -- layers with their backward rules ---------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def conv(x, w, mode):
    return _contract(_conv2d, x, w, mode)


def _conv_fwd(x, w, mode):
    return conv(x, w, mode), w


def _conv_bwd(mode, w, g):
    wt = jnp.flip(w, (0, 1)).swapaxes(2, 3)
    dx = _contract(_conv2d, g, wt, mode)
    if mode == "int8":
        dx = _fixed(dx, INT8_ACT_FRAC)
    return dx, jnp.zeros_like(w)


conv.defvjp(_conv_fwd, _conv_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def dense(x, w, mode):
    return _contract(_matmul, x, w, mode)


def _dense_fwd(x, w, mode):
    return dense(x, w, mode), w


def _dense_bwd(mode, w, g):
    dx = _contract(_matmul, g, w.T, mode)
    if mode == "int8":
        dx = _fixed(dx, INT8_ACT_FRAC)
    return dx, jnp.zeros_like(w)


dense.defvjp(_dense_fwd, _dense_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def relu(x, method):
    return jnp.maximum(x, 0)


def _relu_fwd(x, method):
    return jnp.maximum(x, 0), x > 0


def _relu_bwd(method, positive, g):
    if method == "saliency":
        keep = positive
    elif method == "deconvnet":
        keep = g > 0
    elif method == "guided":
        keep = positive & (g > 0)
    else:
        raise ValueError(f"unknown method {method!r}")
    return (jnp.where(keep, g, 0).astype(g.dtype),)


relu.defvjp(_relu_fwd, _relu_bwd)


def _windows(x):
    n, h, w, c = x.shape
    xw = x.reshape(n, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 5, 2, 4)
    return xw.reshape(n, h // 2, w // 2, c, 4)


@jax.custom_vjp
def maxpool(x):
    return jnp.max(_windows(x), axis=-1)


def _pool_fwd(x):
    xw = _windows(x)
    return jnp.max(xw, axis=-1), jnp.argmax(xw, axis=-1)


def _pool_bwd(idx, g):
    n, hp, wp, c = g.shape
    routed = jax.nn.one_hot(idx, 4, dtype=g.dtype) * g[..., None]
    routed = routed.reshape(n, hp, wp, c, 2, 2).transpose(0, 1, 4, 2, 5, 3)
    return (routed.reshape(n, 2 * hp, 2 * wp, c),)


maxpool.defvjp(_pool_fwd, _pool_bwd)


# -- the network ----------------------------------------------------------------

def forward(params, x, model: dict, method: str, mode: str):
    """[N, H, W, C] -> logits [N, classes] under ``method``'s rules."""
    q = mode == "int8"
    if q:
        x = _ste(x, INT8_ACT_FRAC)
    for i, p in enumerate(params["conv"]):
        w, b = p["w"], p["b"]
        if q:
            w, b = _fixed(w, INT8_WGT_FRAC), _fixed(b, INT8_ACT_FRAC)
        x = conv(x, w, mode) + b
        if q:
            x = _ste(x, INT8_ACT_FRAC)
        if model["conv_relu"]:
            x = relu(x, method)
        if (i + 1) % model["pool_every"] == 0:
            x = maxpool(x)
    x = x.reshape(x.shape[0], -1)
    n_fc = len(params["fc"])
    for i, p in enumerate(params["fc"]):
        w, b = p["w"], p["b"]
        if q:
            w, b = _fixed(w, INT8_WGT_FRAC), _fixed(b, INT8_ACT_FRAC)
        x = dense(x, w, mode) + b
        if q:
            x = _ste(x, INT8_ACT_FRAC)
        if i < n_fc - 1:
            x = relu(x, method)
    return x


@partial(jax.jit, static_argnums=(3, 4, 5))
def _explain(params, x, seeds, model_key, method, mode):
    model = dict(model_key)
    logits, vjp = jax.vjp(lambda v: forward(params, v, model, method, mode),
                          x)
    if mode == "int8":
        (rel,) = vjp(_fixed(seeds * INT8_SEED_GAIN, INT8_ACT_FRAC))
        rel = rel / INT8_SEED_GAIN
    else:
        (rel,) = vjp(seeds)
    return logits, rel


def _model_key(model: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def explain_rows(params, model: dict, x: np.ndarray, targets: np.ndarray,
                 method: str, mode: str) -> Tuple[np.ndarray, np.ndarray]:
    """Logits and relevance of ``targets[r]`` for image ``x[r]``, row by row,
    in blocks of BLOCK rows: -> (logits [R, classes], rel [R, H, W, C])."""
    key = _model_key(model)
    nc = model["num_classes"]
    logits, rels = [], []
    for lo in range(0, len(x), BLOCK):
        xb = x[lo:lo + BLOCK]
        tb = targets[lo:lo + BLOCK]
        live = len(xb)
        if live < BLOCK:
            xb = np.concatenate([xb, np.repeat(xb[:1], BLOCK - live, 0)])
            tb = np.concatenate([tb, np.repeat(tb[:1], BLOCK - live, 0)])
        seeds = np.eye(nc, dtype=np.float32)[tb]
        lg, rel = _explain(params, jnp.asarray(xb), jnp.asarray(seeds), key,
                           method, mode)
        logits.append(np.asarray(lg)[:live])
        rels.append(np.asarray(rel)[:live])
    return np.concatenate(logits), np.concatenate(rels)
