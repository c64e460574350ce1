"""What a per-layer reader is given: the window's records, the server's
counters and spans, the reduced device trace, the launches the window
made, rebuilt from the program's spans, and the model kind's module that
counts the work (``flops``)."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from chipbench import tracing
from chipbench.drive import EXPLAIN, PREDICT, Window


@dataclass
class Launch:
    """One program execution the server made."""
    program: str          # "forward" | "replay"
    rows: int             # padded batch rows
    seeds: int = 1        # backward seeds per row (replay)
    method: str = "saliency"


def pad_rows(n: int, fill_target: int) -> int:
    """The padded rows of a launch of ``n`` requests: the next power of two,
    capped at the server's seats (the batcher's arithmetic)."""
    p = 1
    while p < n:
        p *= 2
    return min(p, fill_target)


def launches_from_spans(spans, window: Window, fill_target: int
                        ) -> List[Launch]:
    """Rebuild the launches from the server's request, engine, cache and
    batch spans: a predict batch is one forward; an explain batch replays
    its cache hits, and runs its misses forward and then replays them."""
    batches: Dict[str, Dict[str, Any]] = {}
    req: Dict[str, Dict[str, Any]] = {}
    for s in spans:
        if s.cat == "batch":
            batches[s.trace_id] = {"kind": s.name.split("/", 1)[1],
                                   "method": s.args.get("method") or "",
                                   "hits": [], "misses": [], "n": 0}
        elif s.cat == "request":
            req.setdefault(s.trace_id, {})["uid"] = s.args.get("uid")
            req[s.trace_id]["kind"] = s.name.split("/", 1)[1]
        elif s.name == "engine":
            req.setdefault(s.trace_id, {})["batch"] = s.args.get("batch")
        elif s.name == "cache":
            req.setdefault(s.trace_id, {})["cache"] = s.args.get("result")
    for r in req.values():
        b = batches.get(r.get("batch"))
        if b is None:
            continue
        b["n"] += 1
        if b["kind"] == EXPLAIN:
            (b["hits"] if r.get("cache") == "hit" else b["misses"]).append(r)
    out: List[Launch] = []
    for b in batches.values():
        if b["kind"] == PREDICT:
            if b["n"]:
                out.append(Launch("forward", pad_rows(b["n"], fill_target)))
            continue
        for group, cold in ((b["hits"], False), (b["misses"], True)):
            if not group:
                continue
            rec = window.recs.get((group[0]["uid"], EXPLAIN))
            seeds = (rec.topk or 1) if rec is not None else 1
            rows = pad_rows(len(group), fill_target)
            if cold:
                out.append(Launch("forward", rows))
            out.append(Launch("replay", rows, seeds, b["method"]))
    return out


@dataclass
class RunContext:
    model: dict                      # the configuration's model block
    precision: str
    chips: int
    shards: int                      # devices one launch spans
    peak: dict                       # peaks.json entry of the device kind
    window: Window
    server: Any                      # the window's ExplanationServer
    spans: List[Any] = field(default_factory=list)
    trace: Optional[tracing.DeviceTrace] = None
    launches: List[Launch] = field(default_factory=list)
    flops: Any = None                # the model kind's work counter module

    tracing = tracing

    @property
    def bf16_peak(self) -> float:
        return float(self.peak["bf16_flops_per_s"])

    @property
    def hbm_bw(self) -> float:
        return float(self.peak["hbm_bytes_per_s"])

    def rows_per_shard(self, rows: int) -> int:
        return math.ceil(rows / self.shards)

    def served_flops(self) -> float:
        """FLOPs of every request the window served, padding not counted."""
        total = 0
        for rec in self.window.records():
            if not rec.ok:
                continue
            seeds = (rec.topk or 1) if rec.kind == EXPLAIN else 0
            total += self.flops.request_flops(
                self.model, rec.kind, seeds,
                cold=rec.kind == EXPLAIN and not rec.resp.cache_hit)
        return float(total)

    def dispatch_s(self) -> float:
        """Summed wall time of the server's batch dispatch spans."""
        return sum(s.duration or 0.0 for s in self.spans if s.cat == "batch")
