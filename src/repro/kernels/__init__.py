"""Pallas TPU kernels for the paper's compute hot-spots (§III.B-E).

Each subpackage mirrors an FPGA compute block:

  conv2d/     tiled SINGLE-DOT convolution: the K*K taps are gathered in
              VMEM (im2col on the already-loaded block) into one
              [H*W, K^2*Cin] @ [K^2*Cin, Tco] MXU contraction per tile.
              FP and BP share the kernel — BP loads flipped-transposed
              weights (paper Fig. 6, Table I).
  vmm/        tiled FC matmul — FP, and BP via transposed operand load.
  relu_mask/  fused ReLU + 1-bit packed mask emit, and the three masked
              BP dataflows (paper Fig. 4).
  pool/       2x2 max-pool + 2-bit argmax emit, and unpool BP (Fig. 5).
  ssm_scan/   state-stationary selective scan (mamba hot-spot; beyond-paper:
              recurrent state persists in VMEM across the seq-chunk grid).

FUSED BACKWARD DATAFLOW (the paper's central overhead claim, Fig. 4-6):
a CNN layer's backward step — 2-bit unpool scatter, 1-bit mask unpack +
method gating (saliency / deconvnet / guided), and the flipped-transpose
conv or transposed matmul — executes as ONE ``pallas_call``
(``conv2d.conv2d_bwd_fused_pallas`` / ``vmm.vmm_bwd_fused_pallas``).  The
pointwise stages run as prologues on the incoming gradient (optionally an
epilogue gate for the previous layer's rectifier on the outgoing one), so
the gradient never round-trips HBM between stages.  HBM traffic per pooled
conv layer backward (paper conv4, f32): unfused 3 calls move the full-res
gradient twice — ~483 KB; fused moves only the endpoint gradients +
residuals + weights — ~227 KB (53% less; `benchmarks/kernels.py` reports
both).  A leading seeds axis S folds into the sublane dimension of the
fused dots, so explaining S classes is one grid launch per layer sharing
every stored mask/index load (the paper's mask-reuse amortization; wired
through ``repro.core.attribution.attribute_classes(backward=...)`` and
``repro.models.cnn.seed_batched_attribution``).

TRUE INT16 FIXED POINT (paper §IV): each hot family carries an ``fxp``
module (``conv2d/fxp.py``, ``vmm/fxp.py``, ``pool/fxp.py``) with the same
tiling and fused-backward structure but the FPGA's numeric contract —
Q7.8 int16 operands, Q1.14 int16 weights, int32 MXU accumulation, one
round-half-up shift requantization with symmetric saturation (contract +
NumPy mirror in :mod:`repro.core.fixedpoint`; bit-exact oracle tests in
``tests/test_kernels_fxp.py``).  The mask prologues are bit-domain and
shared verbatim with the float kernels.

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling, MXU-aligned
dots) and are validated on CPU with interpret=True against the ref.py
oracles.  Every wrapper resolves its ``interpret`` argument through
:func:`interpret_mode`: ``None`` compiles on TPU and interprets elsewhere,
and an explicit ``interpret=True`` on a TPU backend is refused, so a chip
run can never fall back to the Python interpreter unnoticed.

Mosaic (the TPU kernel compiler) constrains the bodies: strided VMEM
accesses only on 32-bit data, no scatter, no lane-splitting reshapes, no
16-bit compares and no int16 MXU operands.  16-bit operands are therefore
widened to 32 bits for the VPU work (:func:`widen`), bit packing is an
exact small matmul (``relu_mask.pack_fields``), and the int16 dots are
four int8 MXU dots (``repro.core.fixedpoint.dot_i16``).
"""
from typing import Optional

import jax
import jax.numpy as jnp


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a wrapper's ``interpret`` argument.

    ``None`` -> interpret exactly when the backend is not a TPU (CPU
    validation).  ``True`` on a TPU backend raises: on the chip every
    kernel runs compiled.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise RuntimeError("interpret=True on a TPU backend: Pallas kernels "
                           "run compiled on the chip, never interpreted")
    return bool(interpret)


def compiler_params():
    """Mosaic parameters of the planned kernels (conv, vmm).

    The scoped-VMEM limit is the detected device profile's ``vmem_bytes``,
    the budget the planner fits tiles into (``repro.plan.profiles``), so
    the planner and the compiler work to one figure.  Called at trace
    time: the profile is read from the backend, never at import.
    """
    from jax.experimental.pallas import tpu as pltpu

    from repro.plan.profiles import detect
    return pltpu.CompilerParams(vmem_limit_bytes=detect().vmem_bytes)


_CARRIER = {jnp.dtype(jnp.bfloat16): jnp.dtype(jnp.float32),
            jnp.dtype(jnp.int16): jnp.dtype(jnp.int32)}


def carrier(dtype) -> jnp.dtype:
    """32-bit carrier of a 16-bit dtype (bf16 -> f32, int16 -> int32)."""
    dtype = jnp.dtype(dtype)
    return _CARRIER.get(dtype, dtype)


def mxu_precision(dtype) -> jax.lax.Precision:
    """Contract precision of an in-kernel dot: f32 operands ask for full
    f32 (Mosaic's fp32 contract) so the f32 path tracks an f32 reference;
    other operands are exact on the MXU as they are.  Always explicit: an
    unset precision would follow ``jax.default_matmul_precision``, and
    Mosaic refuses an fp32 contract on bf16 or int8 operands."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def widen(x: jnp.ndarray) -> jnp.ndarray:
    """A block on its 32-bit :func:`carrier` — exact.

    The TPU vector unit has no 16-bit compares and strided VMEM accesses
    need 32-bit data, so in-kernel selects run on the carrier.
    """
    return x.astype(carrier(x.dtype))


def validate_bp_gates(method: str, gate, relu_mask, out_gate, out_relu_mask):
    """Shared argument contract of the four fused-BP wrappers (f32 + fxp16).

    ``gate``/``out_gate`` default to mask presence; forcing a gate with no
    stored mask is only valid for the deconvnet rule (Eq. 4 reads just the
    gradient sign — Table II stores no mask for it).  Returns the resolved
    ``(gate, out_gate)`` pair.
    """
    if gate is None:
        gate = relu_mask is not None
    if out_gate is None:
        out_gate = out_relu_mask is not None
    if gate and relu_mask is None and method != "deconvnet":
        raise ValueError(
            f"gate=True without relu_mask is only valid for "
            f"method='deconvnet' (Eq. 4 reads just the gradient sign); "
            f"method={method!r} needs the stored 1-bit mask")
    if out_gate and out_relu_mask is None and method != "deconvnet":
        raise ValueError(
            f"out_gate=True without out_relu_mask is only valid for "
            f"method='deconvnet'; method={method!r} needs the stored mask")
    return gate, out_gate
