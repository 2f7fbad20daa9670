#!/usr/bin/env python3
"""chip_smoke.py — the framework's flagship path on the chip, once.

``fedml_tpu.init`` → ``load_federated`` → ``FedLLMAPI(on_device_round:
true).train_one_round`` at the published Llama-2-7B widths (32 layers,
hidden 4096, FFN 11008, 32 heads, vocab 32000; bf16 frozen base, LoRA
r16, flash attention, B1/T512, 8 clients x 2 local steps, weights and
data from ``--seed``), two rounds, on ONE TPU chip. Every timing it
prints is a set-up fact (host clock around the blocking loss readback),
not a benchmark.

``--multichip`` (four chips; run by hand) runs ONLY the sharded path and
its comparison: the same round at full widths and 8 layers, global batch
4, on a one-device mesh and then on ``fsdp=4``, same seed and data.

One process, no children. Needs a TPU: exits non-zero without printing a
result when JAX finds none. The last line of stdout is
``{"ok": true, "device": {...}}``; everything else is on earlier JSON
lines. Rehearsed on the CPU by ``tests/test_chip_smoke.py`` (the phase
functions at tiny size) and ``tests/test_tpu_compile.py`` (the real
shape, compiled for a described v5e).
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import math
import re
import sys
import time

LAYERS_7B = 32
MULTICHIP_LAYERS = 8  # depth cut for --multichip; widths are never cut
ROUNDS = 2
LOCAL_STEPS = 2
TEST_SIZE = 32  # eval reads min(TEST_SIZE, 8 x global batch) sequences
COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
# |a - b| <= LOSS_RTOL * max(1, |a|): bf16 has 8 mantissa bits (eps 2^-8)
# and the two meshes reduce in different orders
LOSS_RTOL = 2e-2
BALANCE_TOL = 0.05


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def round_config(seed: int, *, model_size: str = "llama2_7b",
                 layers: int = LAYERS_7B, per_device_batch: int = 1,
                 seq_len: int = 512, vocab: int = 32000, lora_rank: int = 16,
                 clients: int = 8, samples_per_client: int = 8) -> dict:
    """The config a user would write for this run (yaml sections)."""
    return {
        "common_args": {"training_type": "simulation", "random_seed": seed},
        "data_args": {"dataset": "synthetic_lm", "max_seq_length": seq_len,
                      "vocab_size": vocab,
                      "train_size": clients * samples_per_client,
                      "test_size": TEST_SIZE},
        "model_args": {"model": "llama", "model_size": model_size,
                       "num_hidden_layers": layers, "lora_rank": lora_rank,
                       "base_params_bf16": True, "use_flash_attention": True,
                       "remat_policy": "none"},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": clients,
                       "client_num_per_round": clients,
                       "comm_round": ROUNDS,
                       "local_steps_per_round": LOCAL_STEPS,
                       "per_device_batch_size": per_device_batch,
                       "learning_rate": 1e-4, "frequency_of_the_test": 1,
                       "on_device_round": True},
    }


def cache_counts(*kinds: str) -> dict:
    """The ``jax/compile_cache_<kind>`` counters (a miss is an entry
    WRITTEN; a hit is a compile skipped)."""
    from fedml_tpu.telemetry import get_registry

    reg = get_registry()
    return {k: int(reg.counter(f"jax/compile_cache_{k}").value)
            for k in kinds}


def federated_rounds(config: dict, mesh=None, memory_probe=None) -> dict:
    """Drive ``comm_round`` fused rounds through the user entry points.

    Returns what the run showed; judges nothing (see :func:`check_rounds`).
    ``memory_probe()`` is read while the trained model is still resident.
    """
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments_from_dict
    from fedml_tpu.data import load_federated
    from fedml_tpu.telemetry import reset_catalog
    from fedml_tpu.telemetry.profiling import get_catalog
    from fedml_tpu.train.llm.run_fedllm import FedLLMAPI

    reset_catalog()  # this run's records count this run's calls only

    args = fedml_tpu.init(load_arguments_from_dict(config))
    dataset = load_federated(args)
    t0 = time.perf_counter()
    api = FedLLMAPI(args, None, dataset, mesh=mesh)
    init_s = time.perf_counter() - t0
    cfg = api.cfg

    before = cache_counts("hits", "misses")
    reports = [api.train_one_round(0)]
    after = cache_counts("hits", "misses")
    reports += [api.train_one_round(r)
                for r in range(1, int(args.comm_round))]

    memory = memory_probe() if memory_probe else None
    program = get_catalog().program("llm/fused_round")
    record = program.record.to_dict()
    # the executable the catalog already holds — no second compile
    text = program.last_compiled.as_text()
    engine = api.client.engine
    out = {
        "model": {"layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
                  "ffn": cfg.intermediate_size,
                  "heads": cfg.num_attention_heads, "vocab": cfg.vocab_size,
                  "lora_rank": cfg.lora_rank,
                  "base_dtype": cfg.param_dtype.__name__,
                  "base_quantize": engine.base_quantize or None},
        "mesh": {k: int(v) for k, v in engine.mesh.shape.items()},
        "global_batch": engine.batch_size, "seq_len": engine.seq_len,
        "clients": int(args.client_num_per_round),
        "local_steps": int(args.local_steps_per_round),
        "init_s": init_s,
        "compile_s": record["compile_wall_ms"] / 1e3,
        "round_s": [r["round_sec"] for r in reports],
        "train_loss": [r["train_loss"] for r in reports],
        "test_loss": [r.get("test_loss") for r in reports],
        # cache traffic of round 0 alone: fused round + eval step
        "round0_cache": {k: after[k] - before[k] for k in after},
        "catalog": {k: record[k] for k in (
            "name", "calls", "fallback_calls", "n_signatures",
            "compile_events", "compile_ms", "compile_wall_ms",
            "argument_bytes", "temp_bytes", "peak_hbm_bytes", "flops",
            "analysis_error", "mesh_spec")},
        "flash_kernel_calls": text.count("tpu_custom_call"),
        "collectives": dict(collections.Counter(
            COLLECTIVE_RE.findall(text))),
        "memory": memory,
    }
    # drop every reference to the 7B tree before the caller builds another
    del api, engine, program
    gc.collect()
    return out


def check_rounds(run: dict, require_kernel: bool) -> None:
    losses = run["train_loss"]
    if len(losses) < 2 or not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"chip_smoke: train loss not finite: {losses}")
    if losses[1] == losses[0]:
        raise SystemExit(f"chip_smoke: loss did not change: {losses}")
    if not all(x is not None and math.isfinite(x) for x in run["test_loss"]):
        raise SystemExit(f"chip_smoke: test loss: {run['test_loss']}")
    cat = run["catalog"]
    if cat["fallback_calls"] != 0 or cat["calls"] != len(losses):
        raise SystemExit(f"chip_smoke: catalog record off: {cat}")
    if require_kernel and run["flash_kernel_calls"] == 0:
        raise SystemExit(
            "chip_smoke: the compiled llm/fused_round holds no Pallas "
            "kernel (tpu_custom_call) — flash attention was replaced")


def losses_agree(a, b) -> bool:
    return all(abs(x - y) <= LOSS_RTOL * max(1.0, abs(x))
               for x, y in zip(a, b, strict=True))


def balanced(bytes_per_device) -> bool:
    """Every device within BALANCE_TOL of the mean — the base is sharded,
    not piled on device 0."""
    mean = sum(bytes_per_device) / len(bytes_per_device)
    return all(abs(b - mean) <= BALANCE_TOL * mean for b in bytes_per_device)


def multichip_compare(config_for, devices, memory_probe=None) -> dict:
    """The same round on ``devices[:1]`` and on ``fsdp=len(devices)``.

    ``config_for(per_device_batch)`` builds the config; the global batch
    is ``len(devices)`` on both meshes so both see the same data.
    """
    from fedml_tpu.train.llm.sharding import make_mesh

    n = len(devices)
    one = federated_rounds(config_for(n), mesh=make_mesh(devices=devices[:1]))
    emit({"phase": "one_device_mesh", **one})
    sharded = federated_rounds(
        config_for(1), mesh=make_mesh(fsdp=n, devices=devices),
        memory_probe=memory_probe)
    emit({"phase": f"fsdp{n}_mesh", **sharded})
    if one["global_batch"] != sharded["global_batch"]:
        raise SystemExit("chip_smoke: the two meshes saw different batches")
    for key in ("train_loss", "test_loss"):
        if not losses_agree(one[key], sharded[key]):
            raise SystemExit(
                f"chip_smoke: {key} differs between the one-device mesh "
                f"{one[key]} and fsdp={n} {sharded[key]}")
    if not sharded["collectives"]:
        raise SystemExit("chip_smoke: the sharded round holds no collective")
    return {"one": one, "sharded": sharded}


def memory_stats(devices) -> list:
    """Per-device allocator stats; a backend that reports none is an error."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise SystemExit(f"chip_smoke: {d} reports no memory_stats()")
        out.append({k: int(stats[k]) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit")})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the fsdp=4 round and the "
                         "one-device mesh it is compared with")
    cli = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    from fedml_tpu.telemetry import install_compile_cache_counters
    from fedml_tpu.telemetry.profiling.roofline import PEAK_FLOPS
    from fedml_tpu.utils.compile_cache import configure_compile_cache

    if dev.device_kind not in PEAK_FLOPS:
        raise SystemExit(
            f"chip_smoke: device kind {dev.device_kind!r} has no entry in "
            "roofline.PEAK_FLOPS")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}

    def probe():
        return memory_stats(devices)

    emit({"phase": "device", **device, "jax": jax.__version__,
          "memory": probe()})
    cache_dir = configure_compile_cache()
    install_compile_cache_counters()
    emit({"phase": "cache_dir", "dir": cache_dir})

    if cli.multichip:
        if len(devices) != 4:
            raise SystemExit(
                f"chip_smoke --multichip: needs 4 chips, found {len(devices)}")
        emit({"phase": "reduced", "layers": MULTICHIP_LAYERS,
              "of": LAYERS_7B, "why": "two meshes in one process; the "
              "one-device mesh holds the whole base at global batch 4"})
        both = multichip_compare(
            lambda b: round_config(cli.seed, layers=MULTICHIP_LAYERS,
                                   per_device_batch=b), devices, probe)
        for run in both.values():
            check_rounds(run, require_kernel=True)
        in_use = [m["bytes_in_use"] for m in both["sharded"]["memory"]]
        emit({"phase": "multichip",
              "train_loss_one": both["one"]["train_loss"],
              "train_loss_sharded": both["sharded"]["train_loss"],
              "bytes_in_use_per_device": in_use,
              "collectives": both["sharded"]["collectives"]})
        if not balanced(in_use):
            raise SystemExit(
                f"chip_smoke: per-device bytes not balanced: {in_use}")
    else:
        run = federated_rounds(round_config(cli.seed), memory_probe=probe)
        emit({"phase": "fused_round", **run})
        check_rounds(run, require_kernel=True)

    cache = cache_counts("hits", "misses", "requests")
    emit({"phase": "cache", "dir": cache_dir, **cache})
    if cache["hits"] + cache["misses"] == 0:
        raise SystemExit(
            f"chip_smoke: the compile cache at {cache_dir} saw no traffic")
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
