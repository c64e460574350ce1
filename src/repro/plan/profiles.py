"""DeviceProfile — the resource envelope the planner fits tiles into.

The paper sizes its BRAM tiles per FPGA target; here the same role is a
frozen dataclass: an on-chip (VMEM) byte budget, the vector-unit geometry
every block shape must align to, and the bandwidth/compute peaks the cost
model converts footprints into time with.

Profiles:

  * ``detected`` — the host we are actually on: on a TPU the profile of
    its ``device_kind`` (an unknown kind is an error, never a default); on
    the CPU harness (interpret mode) the ``tpu-v5e`` envelope under the
    name ``detected``, so a plan made on the CPU is the plan the chip runs.
  * ``tpu-v5e``  — one TPU v5e chip: published peaks (Google Cloud, "TPU
    v5e" system architecture page: 197 TFLOP/s bf16, 819 GB/s HBM) and a
    96 MiB budget of the core's 128 MiB VMEM.  The budget is also the
    scoped-VMEM limit every planned kernel asks the compiler for
    (``repro.kernels.compiler_params``), so a tile the planner fits is a
    tile the compiler accepts.  VMEM is tiled (``tiled_vmem``): the
    footprint model pads arrays to whole (sublane, lane) tiles.
  * ``edge-large`` / ``edge-small`` / ``edge-tiny`` — constrained 4/2/1 MB
    on-chip budgets mirroring the paper's edge-FPGA deployment points
    (large/mid ZU+ class parts down to a small Artix-class part), with
    proportionally scaled bandwidth and MAC-array peaks.  Under these the
    planner must split work the default profile keeps whole.
  * ``mesh:<profile>:<n>`` — a :class:`MeshProfile`: N cores of
    ``<profile>``, each with that profile's per-core VMEM/bandwidth/MXU
    envelope.  The planner splits the batch axis across the cores
    FIRST and then tiles the per-core slice against the per-core
    budget, so one mesh-sharded launch obeys the same resource discipline
    as N independent single-core launches.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Tuple

from repro.kernels.tiling import LANE, SUBLANE

MB = 1 << 20


@dataclass(frozen=True)
class DeviceProfile:
    """A planning target: alignment geometry + resource budget + peaks."""

    name: str
    #: on-chip working-set budget every kernel invocation must fit (bytes).
    vmem_bytes: int
    #: second-to-last block-dim multiple (f32 VPU rows).
    sublane: int = SUBLANE
    #: last block-dim multiple (VPU lanes / MXU edge).
    lane: int = LANE
    #: MXU/MAC-array edge — tiles at or above this saturate the array.
    mxu: int = 128
    #: DRAM/HBM bandwidth the cost model charges traffic against (GB/s).
    hbm_gbps: float = 100.0
    #: peak MAC throughput at full utilization (TFLOP/s).
    mxu_tflops: float = 10.0
    #: on-chip memory holds arrays in (sublane, lane) tiles, as TPU VMEM
    #: does: the footprint model pads an array's last two dims to whole
    #: tiles and double-buffers pipelined blocks.  False models flat
    #: on-chip RAM (the edge targets' BRAM).
    tiled_vmem: bool = False

    def __post_init__(self):
        if self.vmem_bytes <= 0:
            raise ValueError(f"vmem_bytes must be positive, got "
                             f"{self.vmem_bytes}")


@dataclass(frozen=True)
class MeshProfile(DeviceProfile):
    """N identical cores, each with a per-core :class:`DeviceProfile`
    envelope.

    All inherited fields (``vmem_bytes``, ``hbm_gbps``, ``mxu_tflops``,
    geometry) are PER CORE, so the cost model's ``Footprint.fits`` check
    is unchanged: a kernel invocation must fit one core's budget — the
    mesh buys parallel shards, never a bigger working set.  ``n_shards``
    is the mesh extent the planner splits the batch axis over and
    the occupancy target the serve batcher fills toward
    (``max_batch * n_shards`` seats per launch).
    """

    n_shards: int = 1

    def __post_init__(self):
        super().__post_init__()
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")

    @property
    def core(self) -> DeviceProfile:
        """The per-core envelope this mesh replicates."""
        return DeviceProfile(
            name=self.name.split(":")[1] if ":" in self.name else self.name,
            vmem_bytes=self.vmem_bytes, sublane=self.sublane,
            lane=self.lane, mxu=self.mxu, hbm_gbps=self.hbm_gbps,
            mxu_tflops=self.mxu_tflops, tiled_vmem=self.tiled_vmem)


def mesh_profile(core, n_shards: int) -> MeshProfile:
    """N-core mesh of ``core`` (a profile name or :class:`DeviceProfile`),
    named ``mesh:<core>:<n>`` so the mesh extent rides plan cache keys and
    ``TilePlan.device`` round-trips."""
    base = get_profile(core)
    if isinstance(base, MeshProfile):
        raise ValueError(f"cannot nest meshes: {base.name!r}")
    return MeshProfile(
        name=f"mesh:{base.name}:{int(n_shards)}",
        vmem_bytes=base.vmem_bytes, sublane=base.sublane, lane=base.lane,
        mxu=base.mxu, hbm_gbps=base.hbm_gbps, mxu_tflops=base.mxu_tflops,
        tiled_vmem=base.tiled_vmem, n_shards=int(n_shards))


PROFILES: Dict[str, DeviceProfile] = {
    p.name: p for p in (
        # Google Cloud, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
        DeviceProfile("tpu-v5e", vmem_bytes=96 * MB, mxu=128,
                      hbm_gbps=819.0, mxu_tflops=197.0, tiled_vmem=True),
        # Paper-style edge targets: small on-chip budgets, narrow MAC
        # arrays, DDR-class bandwidth.
        DeviceProfile("edge-large", vmem_bytes=4 * MB, mxu=64,
                      hbm_gbps=25.6, mxu_tflops=1.0),
        DeviceProfile("edge-small", vmem_bytes=2 * MB, mxu=32,
                      hbm_gbps=12.8, mxu_tflops=0.5),
        DeviceProfile("edge-tiny", vmem_bytes=1 * MB, mxu=16,
                      hbm_gbps=6.4, mxu_tflops=0.25),
    )
}


#: ``jax.devices()[0].device_kind`` -> profile name.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v5 lite": "tpu-v5e",
}


def detect() -> DeviceProfile:
    """The profile of the host backend.

    On a TPU: the profile keyed by the device's ``device_kind``; a kind not
    in :data:`DEVICE_KINDS` raises rather than borrow another chip's peaks.
    Everywhere else the kernels run in interpret mode and the planner
    adopts the ``tpu-v5e`` envelope, so a plan made on the CPU harness is
    the plan the chip runs.
    """
    import jax
    if jax.default_backend() == "tpu":
        kind = jax.devices()[0].device_kind
        if kind not in DEVICE_KINDS:
            raise ValueError(
                f"no device profile for TPU device_kind {kind!r}; known "
                f"kinds: {sorted(DEVICE_KINDS)} (add a sourced profile to "
                f"repro.plan.profiles)")
        return PROFILES[DEVICE_KINDS[kind]]
    return dataclasses.replace(PROFILES["tpu-v5e"], name="detected")


def profile_names() -> Tuple[str, ...]:
    """Single-core names accepted by :func:`get_profile` /
    ``EngineSpec(device=...)``; the open-ended ``mesh:<name>:<n>`` family
    is accepted on top of these."""
    return ("detected",) + tuple(PROFILES)


def get_profile(name) -> DeviceProfile:
    """Resolve a profile by name (``None``/"detected" -> :func:`detect`,
    ``mesh:<profile>:<n>`` -> :func:`mesh_profile`), or pass a
    :class:`DeviceProfile` through unchanged."""
    if isinstance(name, DeviceProfile):
        return name
    if name is None or name == "detected":
        return detect()
    if isinstance(name, str) and name.startswith("mesh:"):
        parts = name.split(":")
        if len(parts) != 3 or not parts[2].isdigit() or int(parts[2]) < 1:
            raise ValueError(
                f"malformed mesh profile {name!r}; expected "
                f"mesh:<profile>:<n> with n >= 1, e.g. 'mesh:edge-small:4'")
        return mesh_profile(parts[1], int(parts[2]))
    try:
        return PROFILES[name]
    except KeyError:
        raise ValueError(f"unknown device profile {name!r}; choose from "
                         f"{profile_names()} or 'mesh:<profile>:<n>'"
                         ) from None
