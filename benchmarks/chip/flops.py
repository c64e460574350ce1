"""Operations and least bytes of the paper CNN's work, from layer shapes.

They count the algorithm's work, not an implementation's: a conv layer is
``H * W * k * k * Cin * Cout`` multiply-adds, and its least traffic reads
its input, weights and stored masks once and writes its output once.  So
they read the same whatever kernel computes them, and a kernel's roofline
share is ``max(flops / peak, bytes / bandwidth)`` over its measured time.

Layers follow the model block of a configuration file: ``channels`` convs
of ``kernel`` x ``kernel`` (SAME padding, stride 1, ReLU after each when
``conv_relu``), a 2x2 max-pool after every ``pool_every``-th conv, then the
``fc`` hidden layers (ReLU) and the ``num_classes`` output layer.

One backward seed costs what the forward does: each layer's input gradient
is a transposed conv (or matmul) of the same multiply-adds, down to the
image.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

#: bytes per activation / weight element, per served precision
ELEMENT_BYTES = {"f32": 4, "bf16": 2, "fxp16": 2}


@dataclass(frozen=True)
class Layer:
    kind: str          # "conv" | "fc"
    h: int             # input height (1 for fc)
    w: int             # input width (1 for fc)
    cin: int
    cout: int
    k: int             # kernel size (1 for fc)
    relu: bool
    pool: bool

    @property
    def macs(self) -> int:
        """Multiply-adds per example (and per backward seed)."""
        return self.h * self.w * self.k * self.k * self.cin * self.cout

    @property
    def out_hw(self) -> int:
        """Output positions per example after the pool, if any."""
        hw = self.h * self.w
        return hw // 4 if self.pool else hw


def layers(model: dict) -> List[Layer]:
    h, w = model["in_hw"]
    cin, k = model["in_ch"], model["kernel"]
    out: List[Layer] = []
    for i, c in enumerate(model["channels"]):
        pool = (i + 1) % model["pool_every"] == 0
        out.append(Layer("conv", h, w, cin, c, k, model["conv_relu"], pool))
        cin = c
        if pool:
            h, w = h // 2, w // 2
    fin = h * w * cin
    widths = list(model["fc"]) + [model["num_classes"]]
    for i, f in enumerate(widths):
        out.append(Layer("fc", 1, 1, fin, f, 1, i < len(widths) - 1, False))
        fin = f
    return out


def forward_flops(model: dict) -> int:
    """FLOPs of one example's forward pass (2 per multiply-add)."""
    return 2 * sum(lyr.macs for lyr in layers(model))


def backward_seed_flops(model: dict) -> int:
    """FLOPs of one backward seed of one example, down to the image."""
    return 2 * sum(lyr.macs for lyr in layers(model))


def request_flops(model: dict, kind: str, seeds: int, cold: bool) -> int:
    """FLOPs a served request needs: a predict is one forward; an explain
    is ``seeds`` backward seeds, plus the forward when it ran cold."""
    if kind == "predict":
        return forward_flops(model)
    return (forward_flops(model) if cold else 0) + seeds * backward_seed_flops(
        model)


def _mask_bytes(lyr: Layer, batch: int, method: str) -> float:
    """Stored residual bytes a layer's backward reads: a 1-bit ReLU mask
    (not under deconvnet, which stores none) and 2-bit pool indices."""
    n = 0.0
    if lyr.relu and method != "deconvnet":
        n += batch * lyr.h * lyr.w * lyr.cout / 8
    if lyr.pool:
        n += batch * lyr.out_hw * lyr.cout * 2 / 8
    return n


def conv_forward_launch(lyr: Layer, batch: int, precision: str) -> Dict:
    """One conv layer's forward dot at ``batch`` examples."""
    e = ELEMENT_BYTES[precision]
    return {"flops": 2 * batch * lyr.macs,
            "bytes": e * (batch * lyr.h * lyr.w * (lyr.cin + lyr.cout)
                          + lyr.k * lyr.k * lyr.cin * lyr.cout)}


def conv_backward_launch(lyr: Layer, batch: int, seeds: int,
                         precision: str, method: str) -> Dict:
    """One conv layer's fused backward step (unpool, mask gate, transposed
    conv) for ``seeds`` seeds at ``batch`` examples: reads the gradient at
    the block's output, the stored masks once and the weights; writes the
    input gradient."""
    e = ELEMENT_BYTES[precision]
    return {"flops": 2 * seeds * batch * lyr.macs,
            "bytes": (e * seeds * batch * (lyr.out_hw * lyr.cout
                                           + lyr.h * lyr.w * lyr.cin)
                      + e * lyr.k * lyr.k * lyr.cin * lyr.cout
                      + _mask_bytes(lyr, batch, method))}


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bytes_per_s: float) -> float:
    """Least time the chip needs for the work: the larger of the two
    bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)
