"""The paper's CNN (Table III) as the harness reaches it: wiring only.

A configuration names its model kind (``"kind"``), and the harness finds
everything that knows the model in ``kinds/<kind>.py``: the weights, the
system under test, the payloads a seed draws, the shapes to warm up, the
comparison with the plain reference, and the module that counts the work.
This kind names the CNN's reference (``chipbench/reference.py``), its
comparison (``chipbench/compare.py``), its FLOPs (``flops.py``) and the
program's CNN serving path.
"""
from __future__ import annotations

import numpy as np

import flops  # noqa: F401  (the kind's work counter: RunContext.flops)
from chipbench import cell, compare, reference, traffic

init_params = reference.init_params
served = compare.served_from_response
numbers = compare.numbers
control_answers = compare.control_answers


def build_adapter(config: dict, params, chips: int):
    """The system under test: EngineSpec -> build -> CNNAdapter, at the
    configuration's precision, on the cell's chips."""
    from repro import engine as engine_lib
    from repro.models import cnn
    from repro.serve import CNNAdapter
    model = config["model"]
    eng = engine_lib.build(engine_lib.EngineSpec(
        model=engine_lib.CNNModel(params, cnn.CNNConfig(
            in_hw=tuple(model["in_hw"]), in_ch=model["in_ch"],
            channels=tuple(model["channels"]), kernel=model["kernel"],
            fc=tuple(model["fc"]), num_classes=model["num_classes"],
            conv_relu=model["conv_relu"], pool_every=model["pool_every"])),
        method="saliency", precision=config["precision"],
        device=cell.engine_device(config, chips)))
    return CNNAdapter.from_engine(eng)


def payloads(model: dict, mix: dict, seed: int, n: int) -> np.ndarray:
    """``n`` N(0, 1) images of the model's input shape, drawn from
    ``seed`` (the mix does not shape them)."""
    shape = tuple(model["in_hw"]) + (model["in_ch"],)
    return traffic.rng(seed, 4).standard_normal((n,) + shape,
                                                dtype=np.float32)


def warm_payloads(plan: traffic.Plan, fill_target: int):
    """One launch shape: the plan's first ``fill_target`` images."""
    return [[plan.payload(j) for j in range(fill_target)]]
