"""Mesh + partition rules for the LLM path — DeepSpeed ZeRO-3 replaced by
``jax.sharding``.

Parity target: the reference's LLM distribution is DeepSpeed ZeRO-3 via HF
Trainer (``train/llm/distributed.py:8-64`` barrier/gather_parameter over
``deepspeed.comm``). TPU-native re-design (SURVEY §2.10): a named device
mesh with axes

    dp    — pure data parallelism (params replicated)
    fsdp  — ZeRO-3-style parameter/optimizer sharding (params split, batch split)
    ep    — expert parallelism (MoE expert dim split; XLA inserts the
            dispatch/combine all-to-alls)
    tp    — megatron-style tensor parallelism (heads/mlp/vocab split)
    sp    — sequence/context parallelism (ring attention, fedml_tpu/parallel)

Model code never mentions these axes: layers annotate *logical* axes
("embed", "heads", "mlp", "vocab") via ``nn.with_logical_partitioning``;
the rules below map logical→mesh, and XLA inserts the all-gathers /
reduce-scatters that DeepSpeed does by hand.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from flax import linen as nn
from flax.core import meta as flax_meta
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# logical axis → mesh axis (None = replicated). "embed" rides fsdp so every
# weight matrix has exactly one fsdp-sharded dimension → ZeRO-3 memory
# scaling; "heads"/"mlp"/"vocab" ride tp.
LOGICAL_RULES: Sequence[Tuple[str, Any]] = (
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("expert", "ep"),
)


def make_mesh(
    dp: int = 1,
    fsdp: int = -1,
    tp: int = 1,
    sp: int = 1,
    ep: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (dp, fsdp, ep, tp, sp) mesh; ``fsdp=-1`` absorbs the
    remainder.

    Axis order puts tp/sp innermost so they land on the fastest ICI hops;
    ep sits between fsdp and tp so expert all-to-alls stay within a slice.
    The ep axis always exists (size 1 when unused) so downstream sharding
    code never branches on mesh rank.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if fsdp == -1:
        fsdp = n // max(dp * tp * sp * ep, 1)
    assert dp * fsdp * ep * tp * sp == n, (
        f"mesh {dp}x{fsdp}x{ep}x{tp}x{sp} != {n} devices"
    )
    arr = np.asarray(devices).reshape(dp, fsdp, ep, tp, sp)
    return Mesh(arr, axis_names=("dp", "fsdp", "ep", "tp", "sp"))


def mesh_from_args(args: Any, devices=None) -> Mesh:
    return make_mesh(
        dp=int(getattr(args, "mesh_dp", 1)),
        fsdp=int(getattr(args, "mesh_fsdp", -1)),
        tp=int(getattr(args, "mesh_tp", 1)),
        sp=int(getattr(args, "mesh_sp", 1)),
        ep=int(getattr(args, "mesh_ep", 1)),
        devices=devices,
    )


def logical_shardings(abstract_tree: Any, mesh: Mesh) -> Any:
    """NamedShardings for a tree of ``nn.Partitioned``-annotated leaves."""
    specs = nn.get_partition_spec(abstract_tree)
    return nn.logical_to_mesh_sharding(specs, mesh, LOGICAL_RULES)


def unbox(tree: Any) -> Any:
    """Strip flax Partitioned metadata boxes → plain pytree of arrays."""
    return flax_meta.unbox(tree)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(("dp", "fsdp")))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_parallel_size(mesh: Mesh) -> int:
    """Devices the batch dimension is split over (the dp x fsdp extent)."""
    return int(mesh.shape["dp"] * mesh.shape["fsdp"])


def init_sharded_params(model, sample_tokens, mesh: Mesh, seed: int = 0,
                        zeros: bool = False):
    """Initialise parameters *already sharded* — no host-side full copy.

    Returns (params, shardings) with metadata boxes stripped.

    ``zeros=True`` skips the random-init program and materializes every
    leaf as a sharded zeros buffer (a memset, seconds instead of minutes
    at 7B scale on a CPU mesh) — for dryruns that validate the sharded
    train program's compile+execute, not training statistics.
    """
    key = jax.random.key(seed)
    # ONE bound method for both uses: JAX keeps a trace by the function
    # object, weakly, and a second ``model.init`` is a new object, so the
    # jit below would trace the whole model again (2 s at 24 layers)
    init = model.init
    abstract = jax.eval_shape(init, key, sample_tokens)
    shardings = logical_shardings(abstract, mesh)
    if zeros:
        ab, sh = unbox(abstract), unbox(shardings)
        import jax.numpy as jnp

        zeros_fn = jax.jit(
            lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), ab),
            out_shardings=sh)
        return zeros_fn(), sh
    from fedml_tpu.telemetry.profiling import wrap_jit

    # cataloged, so its first call's trace / lower / compile-or-load are
    # spans of their own (children of FedLLMAPI's llm/build)
    init_fn = wrap_jit("llm/init_params",
                       jax.jit(init, out_shardings=shardings))
    params = init_fn(key, sample_tokens)
    return unbox(params), unbox(shardings)
