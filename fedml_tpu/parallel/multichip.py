"""Multi-chip scale-out of the fused federated round.

Three pieces the N-chip round is assembled from:

1. **The round mesh plan** (:func:`plan_multichip`): given a device
   count, a frozen-base size and the per-device HBM limit, choose how
   many devices the frozen base must be FSDP-sharded over (the smallest
   power-of-two slice whose per-shard parameter bytes fit under the
   limit with working headroom — the same arithmetic the PR 10 program
   catalog later *verifies* from the compiled program's per-shard
   ``memory_analysis``) and hand the remaining mesh extent to the
   client-parallel ``dp`` axis. The plan also owns the virtual-mesh
   guard below.

2. **The single-core virtual-mesh guard**
   (:func:`is_single_core_virtual_mesh`): XLA:CPU aborts the process
   with a hardcoded 40 s collective-rendezvous timeout whenever the
   serial compute between collectives on N virtual devices
   time-sharing one physical core exceeds 40 s (measured in the r05
   dry run: the full-depth 6.76B step *compiles* over fsdp=8 but dies
   at the first parameter all-gather — "Expected 8 threads to join the
   rendezvous, but only 5 arrived"). Real multi-chip hardware has a
   core per chip; the limit is purely a 1-core-harness artifact. The
   plan therefore DEPTH-REDUCES on such a host (loud log +
   ``shard/depth_reductions`` counter), never hangs.

3. **Per-shard fused aggregation** (:func:`shard_stacked`): the server
   aggregation programs (``compress/fused_weighted_sum``,
   ``integrity/robust_agg``, ``secagg/unmask_finalize``) all reduce
   stacked per-client blocks coordinate-wise over the client axis.
   Sharding the *coordinate* axes across an ``("agg",)`` mesh makes
   every one of them per-shard with ZERO code change inside the
   program: each device holds all C clients' values for 1/N of the
   coordinates, so the weighted einsum / sort-trim / mod-2^k unmask
   run locally per shard with no collective inside the reduction and
   the result is **bit-identical** to the unsharded program — the
   per-coordinate reduction order over clients is untouched by where
   the coordinate lives. Per-device memory (stacked wire blocks + f32
   temporaries) drops by N, the host still only ever touches int8
   wire, and the catalog's mesh_spec/per-shard-HBM records pick the
   layout up automatically from the compiled executable.
"""
from __future__ import annotations

import logging
import os
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

logger = logging.getLogger(__name__)

__all__ = [
    "MultichipPlan",
    "agg_mesh",
    "efficiency_basis",
    "is_single_core_virtual_mesh",
    "plan_multichip",
    "shard_stacked",
    "VIRTUAL_MESH_MAX_LAYERS",
]

# depth ceiling on a single-core virtual mesh: 4 Llama-7B-class layers
# over fsdp=8 measured ~30 s/device-segment in the r05 dry run — already
# a near-miss against XLA:CPU's 40 s rendezvous abort. 2 keeps the
# guard's margin ≥ 2× for every shape the bench runs.
VIRTUAL_MESH_MAX_LAYERS = 2


def is_single_core_virtual_mesh(n_devices: Optional[int] = None) -> bool:
    """True when >`cpu_count` virtual CPU devices time-share this host.

    The regime where XLA:CPU's fixed 40 s collective rendezvous can
    fire spuriously: devices exist (``--xla_force_host_platform_device_
    count`` / ``jax_num_cpu_devices``) but cores to run their
    between-collective segments concurrently do not. A real CPU fleet
    (cores ≥ devices) and every TPU/GPU backend return False.
    """
    try:
        if jax.default_backend() != "cpu":
            return False
        n = int(n_devices) if n_devices else jax.device_count()
    except Exception:  # pragma: no cover - backend init failure
        return False
    return n > 1 and n > (os.cpu_count() or 1)


def efficiency_basis(devices) -> str:
    """What a scaling sweep over ``devices`` can honestly report.

    Decided by the device PLATFORM, never by how many host cores there
    are: virtual CPU devices share one host's memory system whatever the
    core count, so ``wall_1 / wall_N`` (``serialized-virtual-mesh``,
    ideal 1.0 — pure partition overhead) is all they measure. Only real
    accelerators report ``wall-clock`` (``wall_1 / (N * wall_N)``).
    """
    return ("serialized-virtual-mesh" if devices[0].platform == "cpu"
            else "wall-clock")


@dataclass
class MultichipPlan:
    """The round's mesh layout + guard decision, ready to build."""

    n_devices: int
    dp: int                      # client-parallel lanes
    fsdp: int                    # frozen-base shards
    n_layers: int                # depth the round will actually run
    requested_layers: int
    virtual: bool                # single-core virtual mesh detected
    depth_reduced: bool
    reason: str = ""
    per_shard_param_bytes: float = 0.0
    hbm_limit_bytes: float = 0.0
    notes: dict = field(default_factory=dict)

    @property
    def axes(self) -> dict:
        return {"dp": self.dp, "fsdp": self.fsdp}


# what one element of the frozen base costs relative to bf16, scale
# arrays included: int8 pays 1 B + a per-output-channel f32 (negligible);
# int4/nf4 pack two codes per byte + one f32 absmax per 64-block
# (0.5 + 4/64 = 0.5625 B/elem → 0.28125x)
_BASE_QUANT_SCALE = {"": 1.0, "bf16": 1.0, "int8": 0.5,
                     "int4": 0.28125, "nf4": 0.28125}


def plan_multichip(n_devices: int, n_layers: int,
                   param_bytes: float = 0.0,
                   hbm_limit_bytes: float = 0.0,
                   headroom: float = 0.35,
                   base_quantize: str = "") -> MultichipPlan:
    """Choose (dp, fsdp) for ``n_devices`` and apply the virtual guard.

    ``param_bytes`` is the frozen base's total size (bf16 on the wire
    shapes the bench runs); fsdp is the smallest power-of-two divisor
    of ``n_devices`` whose per-shard slice leaves ``headroom`` of the
    device free for activations/temps — the catalog's compiled
    per-shard ``peak_hbm_bytes`` then *verifies* the plan instead of
    being the plan. Every remaining factor of two goes to ``dp``:
    client slots are embarrassingly parallel, so dp is where extra
    devices buy rounds/s.

    ``base_quantize`` ("int8" | "int4" | "nf4") scales ``param_bytes``
    down to what the quantized-resident base actually occupies before
    the fsdp search — a 4-bit base is ~0.28x of bf16, so shard depth
    drops and the freed factors of two become dp lanes.
    """
    n = int(n_devices)
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    if n & (n - 1):
        raise ValueError(
            f"multichip plan needs a power-of-two device count, got {n} "
            "(pass the largest power of two ≤ your slice)")
    bq = str(base_quantize or "").lower()
    if bq not in _BASE_QUANT_SCALE:
        raise ValueError(
            f"base_quantize={base_quantize!r}: must be one of "
            f"{sorted(k for k in _BASE_QUANT_SCALE if k)} (or empty)")
    param_bytes = float(param_bytes) * _BASE_QUANT_SCALE[bq]
    fsdp = 1
    if param_bytes > 0 and hbm_limit_bytes > 0:
        budget = (1.0 - float(headroom)) * float(hbm_limit_bytes)
        while fsdp < n and float(param_bytes) / fsdp > budget:
            fsdp *= 2
        if float(param_bytes) / fsdp > budget:
            raise ValueError(
                f"frozen base ({param_bytes / 1e9:.2f} GB) does not fit "
                f"{n} device(s) of {hbm_limit_bytes / 1e9:.2f} GB at "
                f"{1 - headroom:.0%} occupancy — need a bigger slice")
    dp = n // fsdp

    virtual = is_single_core_virtual_mesh(n)
    layers = int(n_layers)
    reduced = False
    reason = ""
    if virtual and n > 1 and layers > VIRTUAL_MESH_MAX_LAYERS:
        reduced = True
        reason = (
            f"single-core virtual mesh ({n} devices on "
            f"{os.cpu_count() or 1} core(s)): depth reduced "
            f"{layers} → {VIRTUAL_MESH_MAX_LAYERS} layers to stay far "
            "inside XLA:CPU's 40s collective-rendezvous abort (r05: "
            "full depth compiles, then dies at the first all-gather). "
            "Real multi-chip hardware runs the full depth.")
        layers = VIRTUAL_MESH_MAX_LAYERS
        logger.warning("multichip guard: %s", reason)

    plan = MultichipPlan(
        n_devices=n, dp=dp, fsdp=fsdp, n_layers=layers,
        requested_layers=int(n_layers), virtual=virtual,
        depth_reduced=reduced, reason=reason,
        per_shard_param_bytes=float(param_bytes) / fsdp,
        hbm_limit_bytes=float(hbm_limit_bytes),
        notes={"base_quantize": bq} if bq else {})
    try:
        from fedml_tpu.telemetry.registry import get_registry

        reg = get_registry()
        reg.gauge("shard/devices").set(float(n))
        reg.gauge("shard/dp", labels={"program": "plan"}).set(float(dp))
        reg.gauge("shard/fsdp", labels={"program": "plan"}).set(float(fsdp))
        if reduced:
            reg.counter("shard/depth_reductions").inc()
    except Exception:  # pragma: no cover - telemetry must never gate a plan
        pass
    return plan


def agg_mesh(n_devices: Optional[int] = None,
             devices: Optional[Sequence[Any]] = None) -> Mesh:
    """The 1-axis ``("agg",)`` mesh the per-shard aggregation runs over."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices:
        devs = devs[: int(n_devices)]
    return Mesh(np.asarray(devs), axis_names=("agg",))


def _coord_spec(shape: Tuple[int, ...], n_shards: int, axis_name: str,
                skip_leading: int) -> P:
    """A PartitionSpec sharding the largest divisible coordinate axis.

    Only axes divisible by ``n_shards`` qualify (``device_put`` on this
    jax refuses ragged shards). Returns the replicated spec when no
    coordinate axis divides — tiny leaves (biases, scalars, per-client
    scale vectors) ride whole on every device; the big matrices that
    dominate the wire are the ones the split pays for.
    """
    best = -1
    for i in range(skip_leading, len(shape)):
        if shape[i] < n_shards or shape[i] % n_shards:
            continue
        if best < 0 or shape[i] > shape[best]:
            best = i
    parts: list = [None] * len(shape)
    if best >= 0:
        parts[best] = axis_name
    return P(*parts)


def shard_stacked(blocks, mesh: Mesh, axis_name: str = "agg",
                  leading_client_axis: bool = True):
    """Lay stacked aggregation inputs out per-shard on ``mesh``.

    ``blocks`` is any nest of arrays; each leaf with a client-leading
    layout ``[C, *coords]`` (``leading_client_axis=True``) keeps its
    client axis whole and splits its largest coordinate axis across the
    mesh — the layout under which every coordinate-wise client
    reduction (weighted sum, sort-trim, mod-2^k unmask) is local to a
    shard. Leaves too small to split are replicated so the whole
    argument list shares one device set. The downstream ``jax.jit``
    follows these committed shardings (GSPMD), so the existing fused
    programs run per-shard unmodified.
    """
    n = int(mesh.size)
    skip = 1 if leading_client_axis else 0

    def _place(x):
        shape = tuple(getattr(x, "shape", ()))
        spec = _coord_spec(shape, n, axis_name, skip)
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(_place, blocks)
