"""What decides ``correct``: the served answers against the plain reference.

Every answer the window produced is compared: each predict's logits, and
each explain's logits, targets and relevance, for every target of a panel.
The numbers, each as a share of the reference's own scale:

* ``logit_err``: the widest gap of a served logit, over the largest
  reference logit of its image;
* ``target_gap``: how far the reference's logit of a served target lies
  below the reference's k-th best (k = the panel's width), over the
  largest reference logit: 0 when the served targets are the reference's
  top k;
* ``relevance_err.pNN``: per served relevance map, the widest gap to the
  reference's map over the reference map's largest magnitude; the NN-th
  percentile over all of them;
* ``relevance_l2.pNN``: per served relevance map, the L2 norm of the gap
  over the reference map's L2 norm; the NN-th percentile.

The j-th map of an explain is held against the reference's map of the
reference's own j-th class (its argmax, or the j-th of its top k), so a
wrong or misordered target reads as a wrong map.

A configuration states the limit of each number it holds (``limits``);
``PERF.md`` gives the readings each limit was set from.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import reference
from chipbench.stats import percentile

#: the relevance percentiles reported (one or more are held to limits)
RELEVANCE_PERCENTILES = (50, 90, 99, 100)


@dataclass
class Served:
    """One answer as the client got it (host arrays)."""
    kind: str                          # "predict" | "explain"
    method: Optional[str]
    x: np.ndarray                      # [H, W, C]
    logits: np.ndarray                 # [classes]
    targets: Tuple[int, ...] = ()
    relevance: Optional[np.ndarray] = None   # [K, H, W, C]


def served_from_response(kind: str, x: np.ndarray, resp) -> Served:
    rel = None
    if kind == "explain":
        rel = np.asarray(resp.relevance, np.float32)
        if rel.ndim == x.ndim:
            rel = rel[None]
    return Served(kind=kind, method=resp.method if kind == "explain" else None,
                  x=x, logits=np.asarray(resp.logits, np.float32).reshape(-1),
                  targets=tuple(int(t) for t in (resp.targets or ())),
                  relevance=rel)


def _rows(items: List[Served]):
    """Explain rows grouped by method: (item index, target) pairs."""
    by_method: Dict[str, List[Tuple[int, int]]] = {}
    for i, it in enumerate(items):
        if it.kind == "explain":
            for t in it.targets:
                by_method.setdefault(it.method, []).append((i, t))
    return by_method


def _logits(params, model, items: List[Served], mode: str) -> np.ndarray:
    x = np.stack([it.x for it in items])
    lg, _ = reference.explain_rows(params, model, x,
                                   np.zeros(len(items), np.int64),
                                   "saliency", mode)
    return lg


def _relevance(params, model, items: List[Served], mode: str
               ) -> Dict[Tuple[int, int], np.ndarray]:
    out = {}
    for method, rows in _rows(items).items():
        x = np.stack([items[i].x for i, _ in rows])
        t = np.asarray([t for _, t in rows])
        _, rel = reference.explain_rows(params, model, x, t, method, mode)
        for r, key in enumerate(rows):
            out[key] = rel[r]
    return out


def control_answers(params, model: dict, items: List[Served], mode: str
                    ) -> List[Served]:
    """The reference computed in ``mode``, put in the program's place: the
    same requests answered with its own logits, its own top-k targets and
    their relevance."""
    lg = _logits(params, model, items, mode)
    out = []
    for it, row in zip(items, lg):
        k = len(it.targets)
        out.append(Served(kind=it.kind, method=it.method, x=it.x, logits=row,
                          targets=tuple(int(t) for t in np.argsort(-row)[:k])))
    rel = _relevance(params, model, out, mode)
    for i, it in enumerate(out):
        if it.kind == "explain":
            it.relevance = np.stack([rel[(i, t)] for t in it.targets])
    return out


def numbers(params, model: dict, items: List[Served]) -> Dict[str, float]:
    """Compare answers with the reference at ``highest``; see module doc."""
    ref_lg = _logits(params, model, items, "highest")
    ranked = [Served(kind=it.kind, method=it.method, x=it.x, logits=row,
                     targets=tuple(int(t) for t in
                                   np.argsort(-row)[:len(it.targets)]))
              for it, row in zip(items, ref_lg)]
    ref_rel = _relevance(params, model, ranked, "highest")
    logit_err = target_gap = 0.0
    rel_errs: List[float] = []
    rel_l2: List[float] = []
    for i, (it, want) in enumerate(zip(items, ref_lg)):
        scale = max(float(np.abs(want).max()), 1e-30)
        logit_err = max(logit_err,
                        float(np.abs(it.logits - want).max()) / scale)
        if it.kind != "explain":
            continue
        k = len(it.targets)
        kth = float(np.sort(want)[::-1][k - 1])
        worst = min(float(want[t]) for t in it.targets)
        target_gap = max(target_gap, max(0.0, kth - worst) / scale)
        for j, t in enumerate(ranked[i].targets):
            ref = ref_rel[(i, t)]
            rscale = max(float(np.abs(ref).max()), 1e-30)
            gap = it.relevance[j] - ref
            rel_errs.append(float(np.abs(gap).max()) / rscale)
            rel_l2.append(float(np.linalg.norm(gap))
                          / max(float(np.linalg.norm(ref)), 1e-30))
    out = {"logit_err": logit_err, "target_gap": target_gap}
    for name, vals in (("relevance_err", rel_errs), ("relevance_l2", rel_l2)):
        vals.sort()
        for q in RELEVANCE_PERCENTILES:
            v = percentile(vals, q)
            if v is not None:
                out[f"{name}.p{q}"] = v
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each limited number beside its limit; correct when none is above."""
    checks = {name: {"value": values[name], "limit": float(limit)}
              for name, limit in limits.items() if name in values}
    missing = [name for name in limits if name not in values]
    ok = not missing and all(c["value"] <= c["limit"]
                             for c in checks.values())
    return ok, checks
