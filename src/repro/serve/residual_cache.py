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

Every stored example's residuals live in one device-resident slot table:
one array per residual leaf, ``[capacity, *leaf.shape[1:]]`` in the leaf's
dtype, built from the first stored tree and placed on its device(s) — on a
mesh, replicated over the forward's devices and holding the int32 words the
forward hands out.  An entry names its ``slot``.  Storing a launch is ONE
jitted write of its live rows (padding rows dropped, the table donated), and
a hit group is ONE jitted take of its slots; both programs' shapes depend on
the launch's padded size alone, whichever launches a group draws from.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_serving_mesh
from repro.obs import metrics as obsm


def residual_bits(residuals: Any) -> int:
    """Exact stored-bit count of a residual pytree (packed uint8 = 8 b/elt)."""
    return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize * 8
               for leaf in jax.tree.leaves(residuals)
               if hasattr(leaf, "dtype"))


def example_bits(residuals: Any) -> int:
    """Bits of ONE example of a batched residual pytree, from the leaf
    shapes alone: one row of every leaf."""
    return sum(int(np.prod(leaf.shape[1:] if leaf.ndim else ()))
               * leaf.dtype.itemsize * 8
               for leaf in jax.tree.leaves(residuals)
               if hasattr(leaf, "dtype"))


@dataclass
class CacheEntry:
    logits: Any          # [C] host row — the predicted logits (targets, seeds)
    rules: str           # rule set the forward stored masks under
    bits: int            # one example's residual bits
    slot: Optional[int] = None   # its row of the slot table (None: the
                                 # adapter's forward returned no arrays)


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    bits_stored: int = 0
    peak_bits: int = 0
    gathers: int = 0           # take programs issued for hit groups
    gathered_rows: int = 0     # rows they moved (padded group sizes)

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def rows_per_gather(self) -> float:
        return self.gathered_rows / self.gathers if self.gathers else 0.0

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "hit_rate": self.hit_rate(),
                "bits_stored": self.bits_stored, "peak_bits": self.peak_bits,
                "gathers": self.gathers, "gathered_rows": self.gathered_rows,
                "rows_per_gather": self.rows_per_gather()}


def _constrain(leaves: List[Any], mesh: Optional[Mesh], spec: P):
    if mesh is None:
        return leaves
    sharding = NamedSharding(mesh, spec)
    return [jax.lax.with_sharding_constraint(v, sharding) for v in leaves]


@partial(jax.jit, static_argnums=0, donate_argnums=1)
def _write(mesh, table, slots, rows):
    """Rows ``i`` of ``rows`` into slots ``slots[i]`` of every leaf's table;
    an out-of-range slot (a padding row) writes nothing."""
    out = [t.at[slots].set(r, mode="drop") for t, r in zip(table, rows)]
    return _constrain(out, mesh, P())


@partial(jax.jit, static_argnums=0)
def _take(mesh, table, slots):
    """The table's rows ``slots`` of every leaf; on a mesh they come out
    split over its devices when the row count divides evenly (each device
    takes its own rows from its replica, with no collective)."""
    out = [jnp.take(t, slots, axis=0) for t in table]
    even = mesh is not None and slots.shape[0] % mesh.size == 0
    return _constrain(out, mesh, P(*mesh.axis_names) if even else P())


def _placement(leaf):
    """-> (mesh or None, sharding) of a table for ``leaf``: replicated over
    the serving mesh a multi-device leaf comes from (a jitted program takes
    arguments in one device order only, and the mesh orders its devices by
    the chips' topology), else on the leaf's one device."""
    sharding = getattr(leaf, "sharding", None)     # None: a host array
    devices = ({jax.devices()[0]} if sharding is None
               else sharding.device_set)
    if len(devices) == 1:
        return None, jax.sharding.SingleDeviceSharding(next(iter(devices)))
    mesh = make_serving_mesh(len(devices))
    return mesh, NamedSharding(mesh, P())


class ResidualCache:
    """Bounded LRU: ``uid -> CacheEntry``; get() refreshes recency."""

    def __init__(self, capacity: int = 256):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._entries: "OrderedDict[str, CacheEntry]" = OrderedDict()
        self.stats = CacheStats()
        self._table: Optional[List[Any]] = None   # one array per leaf
        self._treedef = None
        self._mesh: Optional[Mesh] = None
        self._free = list(range(capacity - 1, -1, -1))   # pop() -> slot 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, uid: str) -> bool:
        return uid in self._entries

    def store(self, uids: Sequence[str], logits: np.ndarray, residuals,
              rules: str) -> None:
        """Park a launch: ``uids[i]`` gets row ``i`` of ``logits`` (host)
        and of the batched ``residuals``.  The rows enter the slot table
        through one write program, not waited on; a launch with no residual
        arrays stores its logits alone."""
        leaves, treedef = jax.tree.flatten(residuals)
        if leaves and self._table is None:
            self._build(leaves, treedef)
        bits = example_bits(residuals)
        slots = np.full(leaves[0].shape[0] if leaves else 0, self.capacity,
                        np.int32)
        row_of = {}                  # slot -> the launch row now holding it
        for i, uid in enumerate(uids):
            entry = CacheEntry(logits=logits[i], rules=rules, bits=bits)
            self._put(uid, entry, bool(leaves))
            if entry.slot is not None:
                if entry.slot in row_of:     # reused within this launch:
                    slots[row_of[entry.slot]] = self.capacity   # last wins
                row_of[entry.slot] = i
                slots[i] = entry.slot
        if leaves:
            self._table = _write(self._mesh, self._table, slots, leaves)

    def gather(self, entries: Sequence[CacheEntry], rows: int):
        """The residual tree of ``entries``' examples, padded to ``rows``
        rows by repeating the first: one take program over every leaf."""
        if entries[0].slot is None:
            return None
        slots = [e.slot for e in entries]
        slots += slots[:1] * (rows - len(slots))
        self.stats.gathers += 1
        self.stats.gathered_rows += rows
        return jax.tree.unflatten(self._treedef, _take(
            self._mesh, self._table, np.asarray(slots, np.int32)))

    def _build(self, leaves, treedef) -> None:
        self._mesh, sharding = _placement(leaves[0])
        self._treedef = treedef
        self._table = [jax.device_put(
            np.zeros((self.capacity, *lf.shape[1:]), lf.dtype), sharding)
            for lf in leaves]

    def _put(self, uid: str, entry: CacheEntry, slotted: bool) -> None:
        """Insert ``entry``, evicting the LRU entries past capacity; a
        re-stored uid keeps its slot, a new one takes a free slot."""
        old = self._entries.pop(uid, None)
        if old is not None:
            self.stats.bits_stored -= old.bits
        self._entries[uid] = entry
        self.stats.bits_stored += entry.bits
        self.stats.peak_bits = max(self.stats.peak_bits,
                                   self.stats.bits_stored)
        obsm.RESIDUAL_CACHE.inc(event="store")
        while len(self._entries) > self.capacity:
            _, evicted = self._entries.popitem(last=False)
            self.stats.bits_stored -= evicted.bits
            self._free.extend([] if evicted.slot is None else [evicted.slot])
            self.stats.evictions += 1
            obsm.RESIDUAL_CACHE.inc(event="eviction")
        if slotted:
            entry.slot = (old.slot if old is not None and old.slot is not None
                          else self._free.pop())
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

    def peek(self, uid: str) -> Optional[CacheEntry]:
        """Presence probe — no recency update, no hit/miss accounting."""
        return self._entries.get(uid)
