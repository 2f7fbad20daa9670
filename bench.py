#!/usr/bin/env python
"""Benchmark driver entry — prints ONE JSON line.

Flagship metric (BASELINE.json): **FedAvg rounds/sec/chip on the LLM path
(Llama-2-7B LoRA fine-tune, 8 clients)** — the federated round is 8
clients' compiled local steps + LoRA-dict FedAvg on the real chip, at the
TRUE 7B config (6.76B params, bf16 frozen base in 13.5 of 15.75 GB HBM).
FEDML_BENCH_MODEL=1b reruns the round-2/3 1.1B comparison shape.

vs_baseline: the reference (FedML, torch eager) cannot run on TPU at all —
its achievable throughput on this host is a torch-CPU step of the *same*
architecture/shape (transformers LlamaForCausalLM, fp32 eager, measured, then
scaled by tokens). vs_baseline = our measured round throughput ÷ the
reference engine's measured token throughput on identical work.

Timing methodology: JAX dispatch is asynchronous and XLA deletes work whose
outputs nobody reads, so every measurement here (a) chains real data
dependencies between iterations, (b) forces one device→host scalar readback
at the end, and (c) reports the *difference* between a long and a short chain
so the fixed cost of dispatch and readback cancels. (Whether long-minus-short
is still the right estimator is the benchmark PR's call — ROADMAP S0.)

The JSON line also carries (in "extra"):
  - llm_tokens_per_sec and mfu — model-FLOPs utilization vs chip peak bf16.
    With LoRA, frozen-weight grads are dead-code-eliminated by XLA, so the
    model-FLOPs basis is 4N·tokens (fwd 2N + activation-grad 2N) + 6N_lora +
    causal attention term — NOT the dense-training 6N.
  - flash_vs_xla_speedup (Pallas flash attention vs plain-XLA attention,
    fwd+bwd, same shapes) — proves the kernel earns its keep.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# chip peak bf16 FLOP/s by device kind — owned by the profiling layer now
# (telemetry/profiling/roofline.py) so bench, report, doctor and the live
# watch all read ONE table; re-exported here for external callers
from fedml_tpu.telemetry.profiling.roofline import PEAK_BF16  # noqa: E402


def chain_time(run_chain, n_short: int, n_long: int, trials: int = 2) -> float:
    """Seconds/iteration via the long-minus-short chained-readback method.

    ``run_chain(n)`` must execute n *data-dependent* iterations ending in a
    device→host scalar readback, and return elapsed wall seconds.

    Non-positive estimates are discarded: a late compile (e.g. the first
    donated-buffer re-entry of a fused program recompiles for the new
    input layout) can inflate one t_short and make (long−short) negative
    — measured round 5; min() must never crown that artifact.
    """
    run_chain(n_short)  # throwaway: absorbs compile/transfer transients
    best = float("inf")
    last_long = None
    for _ in range(trials):
        t_short = run_chain(n_short)
        t_long = run_chain(n_long)
        last_long = t_long
        est = (t_long - t_short) / (n_long - n_short)
        if est > 0:
            best = min(best, est)
    if best == float("inf"):  # every trial polluted: report the upper bound
        best = last_long / n_long
    return best


def llm_shape(hbm_bytes: float):
    """Pick a Llama shape sized to the chip's HBM (fp32 masters + grads)."""
    from fedml_tpu.models.llm.llama import LlamaConfig

    which = os.environ.get("FEDML_BENCH_MODEL", "auto").lower()
    if which not in ("auto", "7b", "7b_qlora", "1b"):
        raise SystemExit(
            f"FEDML_BENCH_MODEL={which!r}: expected auto|7b|7b_qlora|1b — "
            "refusing to silently bench the tiny-dev model as the flagship")
    if hbm_bytes >= 12e9 and which == "7b_qlora":
        # QLoRA variant (opt-in): int8 frozen base frees ~6.6 GB → B=4
        # fits; measured MFU 0.786 vs 0.664 bf16 (PERF_NOTES r5 add. 6).
        # Not the default flagship so the metric stays comparable across
        # rounds (bf16 base, B1/T512).
        import jax.numpy as jnp

        cfg = LlamaConfig.llama2_7b(
            lora_rank=16, remat=False, remat_policy="none",
            param_dtype=jnp.bfloat16,
        )
        return cfg, 4, 512
    if hbm_bytes >= 12e9 and which in ("auto", "7b"):
        # The NORTH-STAR model (BASELINE.json: Llama-2-7B LoRA): true
        # 7B config — hidden 4096, inter 11008, 32 layers, 32 MHA heads,
        # 6.76B params. bf16 frozen base = 13.5 GB of the v5e's 15.75 GB
        # HBM; fits with LoRA-only fp32 masters at B=1/T=512, remat OFF
        # (honest step 105-107 ms / MFU 0.66-0.67 — short probe chains
        # read up to 8% fast, PERF_NOTES r5 addendum 5; B1/T1024
        # remat-off OOMs by 435 MB; base_quantize int8 [QLoRA] fits
        # B4/T512 at MFU 0.786 — tools/probe_7b.py reproduces all).
        import jax.numpy as jnp

        cfg = LlamaConfig.llama2_7b(
            lora_rank=16, remat=False, remat_policy="none",
            param_dtype=jnp.bfloat16,
        )
        return cfg, 1, 512  # batch, seq
    if hbm_bytes >= 12e9 and which == "1b":
        # ~1.1B (TinyLlama-class) comparison shape — the round-2/3
        # flagship, kept for cross-round regression tracking
        import jax.numpy as jnp

        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=22, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=2048,
            lora_rank=16, remat=False, remat_policy="none",
            param_dtype=jnp.bfloat16,
        )
        return cfg, 8, 1024
    # CPU / tiny-dev fallback so the bench always completes
    cfg = LlamaConfig.tiny(lora_rank=8)
    return cfg, 4, 128


def catalog_flops(name: str):
    """XLA-cost FLOPs of a cataloged program, or None.

    The per-program ``cost_analysis()`` extraction that used to live here
    as a private ``xla_cost_flops`` helper moved into the program catalog
    (``telemetry/profiling/catalog.py``): the hot-path programs register
    there at first compile, the AOT executable is reused for the
    measurement chain (no second compile), and every consumer — this
    bench, ``telemetry report``, the doctor, ``tools/bench_compare`` —
    reads the SAME record. None where cost analysis was unavailable on
    this backend; callers fall back to the analytic model and stamp
    ``mfu_source: "analytic"``.
    """
    from fedml_tpu.telemetry.profiling import get_catalog

    for rec in get_catalog().records():
        if rec.name == name and rec.flops > 0:
            return rec.flops
    return None


def lora_flops_model(params, cfg, batch: int, seq: int):
    """(model FLOPs per LoRA optimizer step, total param count) — see module
    docstring for the FLOPs basis."""
    import jax

    from fedml_tpu.train.llm.trainer import is_lora_path

    n_total = sum(x.size for x in jax.tree.leaves(params))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    n_lora = sum(v.size for p, v in flat if is_lora_path(p))
    tokens = batch * seq
    matmul = (4.0 * (n_total - n_lora) + 6.0 * n_lora) * tokens
    # causal attention: fwd 2·B·T²·h per layer (QKᵀ+AV halved), bwd ≈ 2×
    attn = 6.0 * cfg.num_hidden_layers * cfg.hidden_size * seq * tokens * 0.5
    return matmul + attn, n_total


def bench_flash(batch=2, heads=16, seq=4096, head_dim=64):
    """Pallas flash vs plain-XLA attention, fwd+bwd, chained timing.

    T=4096 is the long-context regime the kernel exists for (measured sweep
    on v5e: flash 2.4× at T=2048, 5× at 4096, >100× at 8192, and the naive
    path OOMs at 16384 where flash still runs)."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.flash_attention import flash_attention, reference_attention

    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    shape = (batch, heads, seq, head_dim)
    q0 = jax.random.normal(k1, shape, jnp.bfloat16)
    k = jax.random.normal(k2, shape, jnp.bfloat16)
    v = jax.random.normal(k3, shape, jnp.bfloat16)

    def make(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32))

        grad = jax.jit(jax.grad(loss))

        def run_chain(n):
            t0 = time.perf_counter()
            q = q0
            for _ in range(n):
                q = q - 1e-6 * grad(q, k, v)  # real data dependency
            float(jnp.sum(q.astype(jnp.float32)))
            return time.perf_counter() - t0

        return run_chain

    try:
        # the kernel is ~4 ms/iter at this shape — the chain must be long
        # enough that (long-short) clears the host clock's noise
        t_flash = chain_time(make(flash_attention), 4, 64, trials=3)
    except Exception:
        return None  # no TPU pallas path on this backend
    t_ref = chain_time(make(reference_attention), 2, 10, trials=3)
    return {
        "flash_ms": round(t_flash * 1e3, 3),
        "xla_ms": round(t_ref * 1e3, 3),
        "flash_vs_xla_speedup": round(t_ref / t_flash, 3),
    }


def bench_reference_torch(cfg):
    """Measured throughput of the reference engine (torch eager, CPU — the
    only hardware it runs on here) on the same architecture.

    Times one fwd+bwd on a reduced token count and scales linearly in
    tokens (eager torch CPU is compute-bound; linear scaling flatters it if
    anything, since bigger batches amortize dispatch).
    Returns reference tokens/sec, or None if torch is unusable.
    """
    try:
        import torch
        from transformers import LlamaConfig as HFConfig
        from transformers import LlamaForCausalLM as HFModel
    except Exception:
        return None, "reference engine unavailable"
    try:
        torch.set_num_threads(os.cpu_count() or 8)
        # at 7B scale a full-depth fp32 torch step takes many minutes on
        # this host's CPU: measure a reduced-depth model with the SAME
        # per-layer shape and scale by depth (linear in layers — embed/lm
        # head overhead is ignored, which flatters the reference)
        layers = min(cfg.num_hidden_layers, 4)
        hf = HFConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            num_hidden_layers=layers,
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            max_position_embeddings=cfg.max_position_embeddings,
            use_cache=False,
        )
        model = HFModel(hf)
        b, t = 1, 128 if cfg.hidden_size >= 4096 else 256
        x = torch.randint(0, cfg.vocab_size, (b, t))
        out = model(input_ids=x, labels=x)  # warm once (allocations)
        out.loss.backward()
        model.zero_grad(set_to_none=True)
        t0 = time.perf_counter()
        out = model(input_ids=x, labels=x)
        out.loss.backward()
        dt = time.perf_counter() - t0
        kind = "reference torch-eager CPU, same arch/work, token-scaled"
        if layers < cfg.num_hidden_layers:
            # the 7B ratio is depth-EXTRAPOLATED, not measured-vs-measured
            # — carry that caveat in the emitted JSON (ADVICE r4)
            kind += (f", depth-extrapolated {layers}/"
                     f"{cfg.num_hidden_layers} layers")
        return (b * t) / dt * (layers / cfg.num_hidden_layers), kind
    except Exception:
        return None, "reference engine unavailable"


def main() -> None:
    if "--compare" in sys.argv:
        # regression gate: diff the newest two archived BENCH_*.json and
        # fail on >10% drop of the headline metric (tools/bench_compare)
        from tools.bench_compare import run_compare

        row = run_compare(os.path.dirname(os.path.abspath(__file__)))
        print(json.dumps(row))
        if not row["ok"]:
            raise SystemExit(1)
        return

    if "--wire" in sys.argv:
        # compressed-transport micro-bench: one JSON line per codec
        # (bytes before/after, encode/decode ms) on a resnet-sized
        # pytree — same ONE-line-per-record contract as --stage. The
        # 4-bit rows carry ratio gates (>=6x vs f32, >=1.8x vs int8);
        # a failed gate exits 1 like every other gated bench mode.
        from tools.wire_bench import apply_wire_gates, run_wire_bench

        rows = run_wire_bench()
        for row in rows:
            print(json.dumps(row))
        if not apply_wire_gates(rows):
            raise SystemExit(1)
        return

    if "--secagg" in sys.argv:
        # secure-aggregation gates: masked wire bytes ≤ 1.2× plain int8
        # on a resnet-sized delta, and a chaos-killed masked round
        # closing via seed-reveal recovery at ≤ 1 extra round-trip per
        # dropout — one JSON line (see tools/secagg_bench.py)
        from tools.secagg_bench import run_secagg_bench

        row = run_secagg_bench()
        print(json.dumps(row))
        if not row["ok"]:
            raise SystemExit(1)
        return

    if "--chaos" in sys.argv:
        # resilience micro-bench: seam overhead on the hot send path
        # (< 1% acceptance) + broker kill/restart recovery time — same
        # ONE-JSON-line contract as --wire/--stage
        from tools.chaos_bench import run_chaos_bench

        row = run_chaos_bench()
        print(json.dumps(row))
        if not (row["ok_overhead"] and row["recovered"]):
            raise SystemExit(1)
        return

    if "--recover" in sys.argv:
        # crash-anywhere durability gates: journal seam < 2% of a durable
        # round, kill-the-server MTTR within budget, every journaled
        # upload salvaged (none retrained), identity-codec final params
        # bit-identical to an uninterrupted run — one JSON line
        # (tools/recover_bench.py; FEDML_RECOVER_* env knobs)
        from tools.recover_bench import run_recover_bench

        row = run_recover_bench()
        print(json.dumps(row))
        if not row["ok"]:
            raise SystemExit(1)
        return

    if "--integrity" in sys.argv:
        # update-integrity gates: ring 1's screen seam < 2% of a round,
        # a poisoned same-seed federation (NaN + magnitude poison at the
        # comm seam) finishing within tolerance of clean with every
        # corrupt upload screened or rolled back, and a round rollback
        # (reject -> restore -> re-run) inside its MTTR budget — one
        # JSON line (tools/integrity_bench.py; FEDML_INTEGRITY_* env)
        from tools.integrity_bench import run_integrity_bench

        row = run_integrity_bench()
        print(json.dumps(row))
        if not row["ok"]:
            raise SystemExit(1)
        return

    if "--preempt" in sys.argv:
        # job-plane gates: deterministic crasher contained (bounded
        # attempts, bit-deterministic backoff), drained node's federation
        # finishes with salvaged uploads never retrained, identity-codec
        # final params bit-identical to an undisturbed run, and
        # preempt-to-resumed MTTR within budget — one JSON line
        # (tools/preempt_bench.py; FEDML_PREEMPT_* env knobs)
        from tools.preempt_bench import run_preempt_bench

        row = run_preempt_bench()
        print(json.dumps(row))
        if not row["ok"]:
            raise SystemExit(1)
        return

    if "--tree" in sys.argv:
        # hierarchical-federation bench: a seeded 3-tier 100k-client
        # aggregation tree on this machine — rounds/s, peak wire bytes
        # per tier, peak host RSS (one JSON line, env-tunable via
        # FEDML_TREE_*; see tools/tree_bench.py)
        from tools.tree_bench import run_tree_bench

        row = run_tree_bench()
        print(json.dumps(row))
        if not (row["completed"] and row["ok_no_f32_trees"]):
            raise SystemExit(1)
        return

    if "--fa" in sys.argv:
        # federated-analytics gates: masked sketch wire ≤ 1.2× the plain
        # int32 sketch, heavy-hitter recall/precision ≥ 0.95 vs the
        # plaintext reference on the same seeded data, and the
        # traced-client-sketch proof (no host-side per-client plaintext
        # in masked mode) — one JSON line, archived as FA_r01.json
        # (tools/fa_bench.py; FEDML_FA_* env knobs)
        from tools.fa_bench import run_fa_bench, write_artifact

        row = run_fa_bench()
        write_artifact(row)
        print(json.dumps(row))
        if not row["ok"]:
            raise SystemExit(1)
        return

    if "--live" in sys.argv:
        # live-telemetry overhead gate: the SAME in-proc federation run
        # with streaming on vs off (rounds/s within tolerance), the
        # micro-measured per-round streaming seam, and the steady-state
        # telemetry wire bytes per node per round (bounded) — one JSON
        # line (tools/live_bench.py; FEDML_LIVE_* env knobs)
        from tools.live_bench import run_live_bench

        row = run_live_bench()
        print(json.dumps(row))
        if not (row["completed"] and row["ok_overhead"] and row["ok_bytes"]
                and row["ok_rounds"]):
            raise SystemExit(1)
        return

    if "--tracepath" in sys.argv:
        # causal-tracing overhead gate: the SAME in-proc federation run
        # with span streaming on vs off (rounds/s within tolerance), the
        # micro-measured span-batch seam as a fraction of a round
        # (<1%), and the steady-state trace wire bytes per node per
        # round (bounded) — one JSON line (tools/tracepath_bench.py;
        # FEDML_TRACEPATH_* env knobs)
        from tools.tracepath_bench import run_tracepath_bench

        row = run_tracepath_bench()
        print(json.dumps(row))
        # ok_rounds (the end-to-end on/off rounds/s ratio) is reported
        # but not gated: at in-proc round walls the A/B diff is host
        # noise — the deterministic seam measurement is the gate
        if not (row["completed"] and row["ok_overhead"]
                and row["ok_bytes"]):
            raise SystemExit(1)
        return

    if "--serve" in sys.argv:
        # live-serving SLO gate: sustained concurrent HTTP load through
        # the OpenAI endpoint across N federation hot swaps — qps,
        # latency percentiles vs the no-swap baseline, swap stalls,
        # dropped MUST be 0 (tools/serve_bench.py; FEDML_SERVE_* env)
        from tools.serve_bench import run_serve_bench, write_artifact

        row = run_serve_bench()
        print(json.dumps(row))
        write_artifact(row)
        # ok_obs_overhead gates here (not inside `completed`): the
        # deterministic micro-measured request-observability seam must
        # stay under 2% of the inter-token latency
        if not (row["completed"] and row["ok_p99"]
                and row["ok_obs_overhead"]):
            raise SystemExit(1)
        return

    if "--profile" in sys.argv:
        # attribution-overhead gate: the SAME run with the program
        # catalog on vs off (interleaved trials) plus the deterministic
        # per-call wrapper seam — always-on profiling must cost < 1%
        # rounds/s (tools/profile_bench.py; FEDML_PROFILE_* env knobs)
        from tools.profile_bench import run_profile_bench

        row = run_profile_bench()
        print(json.dumps(row))
        if not (row["completed"] and row["ok_overhead"] and row["ok_rounds"]):
            raise SystemExit(1)
        return

    if "--multichip" in sys.argv:
        # mesh scale-out gates: fused-round scaling efficiency across
        # N = 1, 2, 4, … devices (client-parallel lanes on dp, base on
        # fsdp) and the per-shard HBM plan under the per-device limit —
        # one JSON line, archived as MULTICHIP_r06.json
        # (tools/multichip_bench.py; FEDML_MULTICHIP_* env knobs)
        from tools.multichip_bench import run_multichip_bench, write_artifact

        row = run_multichip_bench()
        write_artifact(row)
        print(json.dumps(row))
        if not row["ok"]:
            raise SystemExit(1)
        return

    if "--stage" in sys.argv:
        # staging-path micro-bench (pipelined round engine): staged
        # bytes/s, vectorized assembly ms, prefetch overlap ratio —
        # same ONE-JSON-line contract, orthogonal to the LLM metric
        from tools.stage_bench import run_stage_bench

        print(json.dumps(run_stage_bench(
            prefetch="--no-prefetch" not in sys.argv)))
        return

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    n_chips = jax.device_count()
    try:
        hbm = float(dev.memory_stats()["bytes_limit"])
    except Exception:
        hbm = 16e9 if dev.platform == "tpu" else 0.0

    from fedml_tpu.train.llm.trainer import LLMTrainer, extract_lora

    cfg, batch, seq = llm_shape(hbm)

    # flash kernel micro-bench FIRST: its XLA reference path materializes
    # multi-GB T×T score tensors, which cannot coexist with the 7B
    # trainer's 13.5 GB of live params later in this process.
    # FEDML_BENCH_SKIP_FLASH=1 skips it (A/B tool for memory-state
    # effects on the trainer sections; see PERF_NOTES MFU-variance note)
    skip_flash = os.environ.get("FEDML_BENCH_SKIP_FLASH") == "1"
    flash = (bench_flash()
             if dev.platform == "tpu" and not skip_flash else None)

    class Args:
        max_seq_length = seq
        per_device_batch_size = batch
        gradient_accumulation_steps = 1
        learning_rate = 1e-4
        mesh_dp = 1
        mesh_fsdp = -1  # absorb all devices → works on multi-chip hosts too
        mesh_tp = 1
        mesh_sp = 1
        random_seed = 0
        # FEDML_BENCH_QUANTIZE=int8|int4|nf4 picks the frozen-base
        # residency directly; 7b_qlora keeps its int8 default.
        # FEDML_BENCH_QUANTIZE_MIN_SIZE lowers the kernel-size floor so
        # the CPU tiny-dev model exercises the quantized-resident path.
        base_quantize = os.environ.get("FEDML_BENCH_QUANTIZE", "").lower() \
            or ("int8" if os.environ.get(
                "FEDML_BENCH_MODEL", "").lower() == "7b_qlora" else "")
        base_quantize_min_size = int(os.environ.get(
            "FEDML_BENCH_QUANTIZE_MIN_SIZE", 65536))

    trainer = LLMTrainer(cfg, Args())
    trainer.init(seed=0)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(batch, seq), dtype=np.int32)
    x = jnp.asarray(tokens)
    y = jnp.asarray((tokens + 1) % cfg.vocab_size)
    m = jnp.ones((batch,), jnp.float32)

    # --- A. single-step throughput: tokens/sec + MFU ----------------------
    # the train step donates (params, opt_state): iterations are chained by
    # construction; the final loss readback forces the whole queue.
    # FLOPs basis: XLA's own cost model via the program catalog — the
    # wrapped step AOT-compiles ONCE at its first (throwaway) call and
    # every later call runs that same executable, so the chain pays no
    # second compile and the catalog record carries the analysis.
    step_fn = trainer._train_step  # cataloged as "llm/train_step"

    def step_chain(n):
        t0 = time.perf_counter()
        p, o = trainer.params, trainer.opt_state
        loss = None
        for _ in range(n):
            p, o, loss = step_fn(p, o, x[None], y[None], m[None])
        trainer.params, trainer.opt_state = p, o
        float(loss)
        return time.perf_counter() - t0

    sec_per_step = chain_time(step_chain, 2, 22, trials=3)
    step_xla_flops = catalog_flops("llm/train_step")
    tok_per_sec = batch * seq / sec_per_step
    flops_analytic, n_params = lora_flops_model(trainer.params, cfg, batch, seq)
    flops = step_xla_flops if step_xla_flops is not None else flops_analytic
    mfu_source = "xla" if step_xla_flops is not None else "analytic"
    peak = PEAK_BF16.get(dev.device_kind)
    mfu = (flops / sec_per_step / peak) if peak else None

    # --- B. federated LLM round: 8 clients, LoRA FedAvg -------------------
    # the ENTIRE round is one XLA program (compile_federated_round):
    # client-switch, local steps, and the adapter FedAvg run on device with
    # donated buffers — round 4 lost ~22% of this metric to host-Python
    # LoRA merge/extract interleaved between the device steps
    n_clients, local_steps = 8, 2

    fed_round = trainer.compile_federated_round(n_clients, local_steps)
    crng = np.random.default_rng(1)
    xs = np.repeat(  # each client reuses its batch for both local steps
        crng.integers(0, cfg.vocab_size,
                      size=(n_clients, 1, batch, seq), dtype=np.int32),
        local_steps, axis=1)
    ys_r = (xs + 1) % cfg.vocab_size
    ms_r = np.ones((n_clients, local_steps, batch), np.float32)
    wts = np.ones((n_clients,), np.float32)

    # XLA cost model of the WHOLE fused round (client-switch + local
    # steps + FedAvg): flops_per_round comes from the catalog record of
    # the compiled program ("llm/fused_round"), not the analytic 4N
    # approximation; the catalog's AOT executable runs the chain so the
    # cost analysis costs no extra compile
    round_fn = fed_round

    def round_chain(n_rounds):
        t0 = time.perf_counter()
        p, o = trainer.params, trainer.opt_state
        # fresh copy per chain: the donated global-lora buffers from the
        # previous chain are dead
        g = jax.tree.map(jnp.copy, extract_lora(p))
        loss = None
        for _ in range(n_rounds):
            p, o, g, loss = round_fn(p, o, g, xs, ys_r, ms_r, wts)
        trainer.params, trainer.opt_state = p, o
        float(loss)  # readback forces the whole donated chain
        return time.perf_counter() - t0

    round_sec = chain_time(round_chain, 1, 5, trials=3)
    round_xla_flops = catalog_flops("llm/fused_round")
    rounds_per_sec_per_chip = 1.0 / round_sec / n_chips
    round_tokens = n_clients * local_steps * batch * seq

    # --trace-rounds r1,r2: capture a deep device trace of N extra fused
    # rounds AFTER the measurement (tracing inside the timed chain would
    # perturb it) through the budgeted TraceController
    from fedml_tpu.telemetry.profiling import parse_rounds

    trace_rounds = []
    for i, a in enumerate(sys.argv):
        if a == "--trace-rounds" and i + 1 < len(sys.argv):
            trace_rounds = parse_rounds(sys.argv[i + 1])
    if trace_rounds:
        from fedml_tpu.telemetry.profiling import get_trace_controller

        tc = get_trace_controller()
        tc.arm_rounds(trace_rounds,
                      trace_dir=os.environ.get("FEDML_TRACE_DIR",
                                               ".fedml_logs/bench_traces"))
        g = jax.tree.map(jnp.copy, extract_lora(trainer.params))
        p, o = trainer.params, trainer.opt_state
        for r in trace_rounds:
            tc.on_round_start(r)
            p, o, g, loss = round_fn(p, o, g, xs, ys_r, ms_r, wts)
            float(loss)  # drain before stop_trace so the trace sees it
            tc.on_round_end(r)
        trainer.params, trainer.opt_state = p, o

    # --- C. reference engine measured on same work -------------------------
    ref_tps, baseline_kind = bench_reference_torch(cfg)
    if ref_tps is not None:
        ref_round_sec = round_tokens / ref_tps
        vs_baseline = ref_round_sec / round_sec
    else:
        vs_baseline = 0.0

    extra = {
        "device": dev.device_kind,
        "n_chips": n_chips,
        "model": {
            "params": int(n_params),
            "base_quantize": Args.base_quantize or None,
            **{k: getattr(cfg, k) for k in (
                "hidden_size", "intermediate_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "lora_rank")},
        },
        "batch": batch,
        "seq_len": seq,
        "llm_tokens_per_sec": round(tok_per_sec, 1),
        "llm_step_ms": round(sec_per_step * 1e3, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        # FLOPs provenance: "xla" = lowered.compile().cost_analysis() on
        # the compiled programs themselves; "analytic" = the hand model
        # (4N + 6N_lora + attn; frozen wgrads DCE'd) where XLA's cost
        # model is unavailable on this backend
        "mfu_source": mfu_source,
        "flops_per_step": round(flops, 1),
        "flops_per_round": round(
            round_xla_flops if round_xla_flops is not None
            else flops_analytic * n_clients * local_steps, 1),
        "flops_per_round_source": ("xla" if round_xla_flops is not None
                                   else "analytic"),
        "mfu_basis": (
            "XLA cost_analysis() flops of the compiled train step"
            if mfu_source == "xla" else
            "LoRA model-flops (4N + 6N_lora + attn); frozen wgrads are DCE'd"),
        "round_shape": {"clients": n_clients, "local_steps": local_steps,
                        "round_tokens": round_tokens},
        "round_path": "fused on-device round: client-switch + local steps "
                      "+ LoRA FedAvg in ONE donated-buffer XLA program",
        "reference_tokens_per_sec": round(ref_tps, 1) if ref_tps else None,
        "baseline_kind": baseline_kind,
        "timing": "chained-dependency, long-minus-short readback",
    }
    # per-program catalog summary (name → flops/bytes/peak-HBM/compile):
    # tools/bench_compare.py diffs these across BENCH files so an MFU or
    # HBM regression is attributed to a PROGRAM, not just whole-run
    # rounds/s
    from fedml_tpu.telemetry.profiling import get_catalog

    extra["programs"] = get_catalog().programs_summary()
    if flash:
        extra.update(flash)

    print(json.dumps({
        "metric": "fedavg_llm_rounds_per_sec_per_chip",
        "value": round(rounds_per_sec_per_chip, 5),
        "unit": "rounds/s/chip",
        "vs_baseline": round(vs_baseline, 3),
        "extra": extra,
    }))


if __name__ == "__main__":
    main()
