"""LRU cache of bit-packed forward residuals, keyed by request id.

The paper's FPGA answers "why?" cheaply because the forward pass already
parked its ReLU sign bits (1 bit/elt) and max-pool argmax crumbs
(2 bits/window) in BRAM: an explanation is then ONLY the BP phase over those
masks (§III.F).  This module is the serving-time analogue — a *predict*
request stores its packed masks here, and a follow-up *explain* for the same
``uid`` (any pure-BP method, any target/top-K panel) skips the forward pass
entirely and goes straight to the fused seed-batched backward.

Entries are tiny by construction (the paper's 137x cut: 24.7 Kb vs 3.4 Mb
for the Table III CNN at batch 1), so thousands of in-flight explanations
fit where a handful of activation caches would; the cache still bounds
itself by entry count and reports its exact bit footprint.

A launch's entries share its residual tree by reference (each names its
``row``; a 1-row launch's tree already is its one example), so storing a
launch issues no device op; the per-example slice is taken only where an
explain hit needs it, when the server gathers the replay batch.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np

from repro.obs import metrics as obsm


def residual_bits(residuals: Any) -> int:
    """Exact stored-bit count of a residual pytree (packed uint8 = 8 b/elt)."""
    return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize * 8
               for leaf in jax.tree.leaves(residuals)
               if hasattr(leaf, "dtype"))


def example_bits(residuals: Any) -> int:
    """Bits of ONE example of a batched residual pytree, from the leaf
    shapes alone: equals ``residual_bits(slice_example(residuals, i))``."""
    return sum(int(np.prod(leaf.shape[1:] if leaf.ndim else ()))
               * leaf.dtype.itemsize * 8
               for leaf in jax.tree.leaves(residuals)
               if hasattr(leaf, "dtype"))


@dataclass
class CacheEntry:
    logits: Any          # [C] — the predicted logits (argmax targets, seeds)
    residuals: Any       # packed masks/indices pytree: ONE example's, or,
                         # with ``row``, the whole launch's it was stored from
    rules: str           # rule set the forward stored masks under
    row: Optional[int] = None   # this example's row in ``residuals``
    bits: int = 0        # one example's bits, whatever ``residuals`` holds

    def __post_init__(self):
        if not self.bits:
            self.bits = (residual_bits(self.residuals) if self.row is None
                         else example_bits(self.residuals))


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    bits_stored: int = 0
    peak_bits: int = 0
    zero_copy_rows: int = 0    # hit rows replayed from the stored tree as is
    copied_rows: int = 0       # hit rows sliced or concatenated at gather

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def zero_copy_share(self) -> float:
        total = self.zero_copy_rows + self.copied_rows
        return self.zero_copy_rows / total if total else 0.0

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "hit_rate": self.hit_rate(),
                "bits_stored": self.bits_stored, "peak_bits": self.peak_bits,
                "zero_copy_rows": self.zero_copy_rows,
                "copied_rows": self.copied_rows,
                "zero_copy_share": self.zero_copy_share()}


class ResidualCache:
    """Bounded LRU: ``uid -> CacheEntry``; get() refreshes recency."""

    def __init__(self, capacity: int = 256):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._entries: "OrderedDict[str, CacheEntry]" = OrderedDict()
        self.stats = CacheStats()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, uid: str) -> bool:
        return uid in self._entries

    def put(self, uid: str, entry: CacheEntry) -> None:
        if uid in self._entries:
            self.stats.bits_stored -= self._entries.pop(uid).bits
        self._entries[uid] = entry
        self.stats.bits_stored += entry.bits
        self.stats.peak_bits = max(self.stats.peak_bits,
                                   self.stats.bits_stored)
        obsm.RESIDUAL_CACHE.inc(event="store")
        while len(self._entries) > self.capacity:
            _, old = self._entries.popitem(last=False)
            self.stats.bits_stored -= old.bits
            self.stats.evictions += 1
            obsm.RESIDUAL_CACHE.inc(event="eviction")
        obsm.RESIDUAL_CACHE_BITS.set(self.stats.bits_stored)

    def get(self, uid: str) -> Optional[CacheEntry]:
        entry = self._entries.get(uid)
        if entry is None:
            self.count_miss()
            return None
        self._entries.move_to_end(uid)
        self.stats.hits += 1
        obsm.RESIDUAL_CACHE.inc(event="hit")
        return entry

    def count_miss(self) -> None:
        """Account a miss decided outside :meth:`get` (e.g. a present but
        rules-incompatible entry the server declines to use)."""
        self.stats.misses += 1
        obsm.RESIDUAL_CACHE.inc(event="miss")

    def count_gather(self, rows: int, copied: int) -> None:
        """Account one gather of ``rows`` hit rows, ``copied`` of which were
        sliced or concatenated (the rest entered the replay as stored)."""
        self.stats.zero_copy_rows += rows - copied
        self.stats.copied_rows += copied

    def peek(self, uid: str) -> Optional[CacheEntry]:
        """Presence probe — no recency update, no hit/miss accounting."""
        return self._entries.get(uid)
