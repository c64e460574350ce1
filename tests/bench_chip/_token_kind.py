"""A token model kind for the harness's tests: the program's LMAdapter at
a small preset, fed left-padded token ids of a few length buckets.

Copied into a test harness directory as ``kinds/token.py``.  It checks the
seam between the harness and a model kind, not the model: its
"reference" is the program's own forward and token step, run row by row
after the window.  The configuration's model block names the preset
(``{"arch": ..., "size": "smoke"}``); the mix names the prompt lengths
(``"lengths"``), which every seed sends in the same proportions.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from chipbench import cell, traffic

#: how each token method is computed by the engine's token step
MODES = {"token_saliency": "grad_norm", "token_ixg": "ixg",
         "token_contrastive": "contrastive"}


def _cfg(model: dict):
    import repro.configs as configs
    return (configs.get_smoke(model["arch"]) if model.get("size") == "smoke"
            else configs.get(model["arch"]))


def init_params(model: dict, weight_seed: int):
    import jax
    from repro.models import transformer as tf
    return tf.init(jax.random.PRNGKey(int(weight_seed) & 0x7FFFFFFF),
                   _cfg(model))


def build_adapter(config: dict, params, chips: int):
    from repro import lm
    return lm.LMAdapter(params, _cfg(config["model"]),
                        precision=config["precision"],
                        device=cell.engine_device(config, chips))


def payloads(model: dict, mix: dict, seed: int, n: int) -> List[np.ndarray]:
    """``n`` prompts of the mix's lengths in turn, ids drawn from ``seed``,
    each left-padded to its pow2 bucket."""
    from repro import lm
    vocab = _cfg(model).vocab
    r = traffic.rng(seed, 4)
    lengths = [int(v) for v in mix["lengths"]]
    out = []
    for i in range(n):
        s = lengths[i % len(lengths)]
        toks = r.integers(1, vocab, size=s, dtype=np.int32)
        out.append(np.asarray(lm.pad_tokens(toks)))
    return out


def warm_payloads(plan: traffic.Plan, fill_target: int):
    """Per length bucket the plan sends, ``fill_target`` of its prompts."""
    by_len: Dict[int, List[np.ndarray]] = {}
    for p in plan.payloads:
        by_len.setdefault(p.shape[-1], []).append(p)
    return [[group[j % len(group)] for j in range(fill_target)]
            for group in by_len.values()]


@dataclass
class Served:
    kind: str
    method: Optional[str]
    x: np.ndarray                      # [S] token ids
    logits: np.ndarray                 # [vocab], last position
    relevance: Optional[np.ndarray] = None   # [S]


def served(kind: str, payload: np.ndarray, resp) -> Served:
    rel = None if kind != "explain" else np.asarray(resp.relevance,
                                                    np.float32)
    return Served(kind=kind, method=resp.method if kind == "explain" else None,
                  x=payload, logits=np.asarray(resp.logits,
                                               np.float32).reshape(-1),
                  relevance=rel)


def numbers(params, model: dict, items: List[Served]) -> Dict[str, float]:
    """The widest logit and relevance gaps to the program's own token
    step on each prompt alone, over the reference's largest magnitude."""
    from repro import engine as engine_lib
    eng = engine_lib.build(engine_lib.EngineSpec(
        model=engine_lib.LMModel(params, _cfg(model))))
    logit_err = rel_err = 0.0
    for it in items:
        mode = MODES.get(it.method, "ixg")
        lg, rel = eng.explain_tokens({"tokens": it.x[None]}, mode=mode)
        want = np.asarray(lg, np.float32).reshape(-1)
        logit_err = max(logit_err, float(np.abs(it.logits - want).max())
                        / max(float(np.abs(want).max()), 1e-30))
        if it.relevance is not None:
            ref = np.asarray(rel, np.float32).reshape(-1)
            rel_err = max(rel_err, float(np.abs(it.relevance - ref).max())
                          / max(float(np.abs(ref).max()), 1e-30))
    return {"logit_err": logit_err, "relevance_err": rel_err}


def control_answers(params, model: dict, items: List[Served], mode: str):
    raise NotImplementedError("the test kind has no control")


class flops:
    """The test kind counts no work: ``dispatch_mfu`` reads nothing."""

    @staticmethod
    def request_flops(model: dict, kind: str, seeds: int, cold: bool) -> int:
        return 0
