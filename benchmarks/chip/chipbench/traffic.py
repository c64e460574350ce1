"""The one traffic generator: a mix file's parameters and a seed -> a plan.

A mix (``traffic/<mix>.json``) names ``"generator": "sessions"`` and sets:

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does)
  or ``"closed"`` (``clients`` callers, each sending its next request when
  the last one completed);
* ``rate_per_s`` (open): arrivals per second; ``arrivals``: ``"poisson"``,
  or ``"bursty"`` with ``burst_factor``, ``burst_len_s``, ``idle_len_s``
  (an on/off cycle whose mean rate is ``rate_per_s``);
* ``predict_first``: true for sessions (a predict, then an explain of the
  same uid ``think`` seconds after the predict completed, exponential with
  mean ``think_mean_s``); false for explain-only traffic with fresh uids;
* ``methods``, ``panel_share`` and ``panel_k``: each explain's method is
  drawn uniformly, and ``panel_share`` of them ask for a top-``panel_k``
  panel instead of the argmax class.

A run's timing skeleton (arrival times, think times and which session asks
for which method and panel) is drawn from the mix alone and is the same in
every run; ``--seed`` draws the payloads, one per session, through the
model kind's ``payloads`` (``kinds/<kind>.py``: N(0, 1) images for the
CNN).  So every seed holds the same work at the same moments, and two runs
differ by the system's own noise and by what the payloads make the model
answer (argmax and top-k targets), not by where a burst falls.
"""
from __future__ import annotations

import fractions
import math
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

_MASK64 = (1 << 64) - 1
#: the seed of every run's timing skeleton
SKELETON_SEED = 0
#: Sessions pre-drawn for a closed loop; more are taken round-robin.
CLOSED_POOL = 8192
#: Distinct payloads drawn for a closed loop (sessions cycle over them).
CLOSED_PAYLOADS = 1024


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of draws for one use of ``seed``."""
    return np.random.default_rng([int(seed) & _MASK64, stream])


def exponential_set(n: int, mean: float, r: np.random.Generator) -> np.ndarray:
    """``n`` exponential draws as a fixed set of quantiles, in random order."""
    q = (np.arange(n) + 0.5) / n
    return r.permutation(-np.log1p(-q) * mean)


def explain_kinds(n: int, methods: List[str], panel_share: float,
                  panel_k: int, r: np.random.Generator
                  ) -> List[Tuple[str, Optional[int]]]:
    """``n`` (method, topk) pairs in balanced shuffled blocks: each block
    holds every method equally often and ``panel_share`` of each method's
    explains as top-``panel_k`` panels."""
    frac = fractions.Fraction(panel_share).limit_denominator(12)
    block = [(m, panel_k if j < frac.numerator else None)
             for m in methods for j in range(frac.denominator)]
    out: List[Tuple[str, Optional[int]]] = []
    while len(out) < n:
        out.extend(block[i] for i in r.permutation(len(block)))
    return out[:n]


def _bursty_times(unit: np.ndarray, rate: float, burst_factor: float,
                  burst_len_s: float, idle_len_s: float) -> np.ndarray:
    """Map unit-rate arrival times through the inverse cumulative intensity
    of an on/off cycle (``burst_factor`` x for ``burst_len_s``, then 0.1 x
    for ``idle_len_s``) normalised to mean ``rate``."""
    cycle = burst_len_s + idle_len_s
    mean_factor = (burst_factor * burst_len_s + 0.1 * idle_len_s) / cycle
    on = rate * burst_factor / mean_factor
    off = rate * 0.1 / mean_factor
    per_cycle = on * burst_len_s + off * idle_len_s      # = rate * cycle
    k, rest = np.divmod(unit, per_cycle)
    in_on = rest < on * burst_len_s
    t_in = np.where(in_on, rest / on,
                    burst_len_s + (rest - on * burst_len_s) / off)
    return k * cycle + t_in


@dataclass
class Plan:
    """What one run sends: drawn in full before the window opens."""
    loop: str                       # "open" | "closed"
    predict_first: bool             # sessions (predict, then explain)
    arrivals: np.ndarray            # open: offsets from window start, s
    think: np.ndarray               # per session think time, s
    kinds: List[Tuple[str, Optional[int]]]   # per session explain kind
    payloads: Sequence[np.ndarray]  # per session request input (the kind's)
    clients: int = 0                # closed loop callers

    def payload(self, session: int) -> np.ndarray:
        return self.payloads[session % len(self.payloads)]

    def kind(self, session: int) -> Tuple[str, Optional[int]]:
        return self.kinds[session % len(self.kinds)]

    def think_s(self, session: int) -> float:
        return float(self.think[session % len(self.think)])


def make_plan(mix: dict, seed: int, seconds: float,
              payloads: Callable[[dict, int, int], Sequence[np.ndarray]]
              ) -> Plan:
    """The plan of one run: the timing skeleton from ``mix``, and
    ``payloads(mix, seed, n)``'s ``n`` request inputs (a kind's
    ``payloads`` with its model bound)."""
    if mix.get("generator") != "sessions":
        raise ValueError(f"unknown traffic generator {mix.get('generator')!r}")
    loop = mix["loop"]
    methods = list(mix["methods"])
    if loop == "open":
        rate = float(mix["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        gaps = exponential_set(n, 1.0, rng(SKELETON_SEED, 1))
        # unit-rate arrivals, the last half a gap before n: n arrivals on
        # [0, n), which the rate (or the on/off cycle) maps onto the window
        unit = np.cumsum(gaps) * ((n - 0.5) / gaps.sum())
        if mix.get("arrivals", "poisson") == "bursty":
            arrivals = _bursty_times(unit, rate, float(mix["burst_factor"]),
                                     float(mix["burst_len_s"]),
                                     float(mix["idle_len_s"]))
        else:
            arrivals = unit / rate
        n_payloads = n
        clients = 0
    elif loop == "closed":
        n = CLOSED_POOL
        arrivals = np.zeros(0)
        n_payloads = CLOSED_PAYLOADS
        clients = int(mix["clients"])
    else:
        raise ValueError(f"loop must be open|closed, got {loop!r}")
    think_mean = float(mix.get("think_mean_s", 0.0))
    think = (exponential_set(n, think_mean, rng(SKELETON_SEED, 2))
             if think_mean > 0 else np.zeros(n))
    kinds = explain_kinds(n, methods, float(mix.get("panel_share", 0.0)),
                          int(mix.get("panel_k", 1)), rng(SKELETON_SEED, 3))
    return Plan(loop=loop, predict_first=bool(mix["predict_first"]),
                arrivals=arrivals, think=think, kinds=kinds,
                payloads=payloads(mix, seed, n_payloads), clients=clients)


def buckets(mix: dict) -> List[Tuple[str, Optional[int]]]:
    """The (method, topk) explain buckets a mix can send."""
    frac = fractions.Fraction(float(mix.get("panel_share", 0.0))
                              ).limit_denominator(12)
    panels = [None] if frac.numerator == 0 else (
        [int(mix["panel_k"])] if frac == 1 else [None, int(mix["panel_k"])])
    return [(m, k) for m in mix["methods"] for k in panels]


def pad_sizes(fill_target: int) -> List[int]:
    """The padded batch sizes a server with ``fill_target`` seats launches:
    the powers of two below it, and the full launch."""
    return [1 << i for i in range(math.ceil(math.log2(fill_target)))] + [
        fill_target]
