"""Arrival-driven serving benchmark (VERDICT r4 task 4).

Drives the continuous-batching engine the way vLLM-class engines are
judged: Poisson arrivals at an offered load, mixed prompt lengths
(64-1024) and output lengths, reporting TTFT p50/p99, inter-token
latency, completed-token throughput, and the measured prefill stall
decode streams suffer per admission. Reference capability this maps to:
the hf/vLLM serving template (`device_model_deployment.py:528`).

Run (1.1B bf16 on the chip):
  python tools/serving_load_bench.py --model 1b --loads 0.5,1,2,4
Run (dev-scale CPU sanity):
  JAX_PLATFORMS=cpu python tools/serving_load_bench.py --model tiny

Each load level runs `--requests` requests; arrivals are pre-scheduled
from a seeded RNG so runs are reproducible.
"""
import argparse
import json
import sys
import threading
import time

sys.path.insert(0, "/root/repo")

import numpy as np

ap = argparse.ArgumentParser()
ap.add_argument("--model", default="tiny", choices=["tiny", "1b", "7b"])
ap.add_argument("--loads", default="0.5,1,2",
                help="offered loads, requests/second, comma-separated")
ap.add_argument("--requests", type=int, default=32)
ap.add_argument("--slots", type=int, default=8)
ap.add_argument("--quantize", default=None)
ap.add_argument("--seed", type=int, default=0)
ap.add_argument("--platform", default=None,
                help="force a jax platform (e.g. cpu) through the config "
                     "API, whatever JAX_PLATFORMS says")
cli = ap.parse_args()

import jax

if cli.platform:
    jax.config.update("jax_platforms", cli.platform)
import jax.numpy as jnp

from fedml_tpu.models.llm.llama import LlamaConfig, LlamaForCausalLM
from fedml_tpu.serving.llm_engine import ContinuousBatchingEngine

if cli.model == "7b":
    # int8-only on one v5e: bf16 weights + KV cannot fit (PERF_NOTES r4)
    cli.quantize = cli.quantize or "int8"
    cfg = LlamaConfig.llama2_7b(
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        remat=False, remat_policy="none", use_flash=False,
    )
    max_len, prompt_hi = 768, 512
    cli.slots = min(cli.slots, 4)  # KV is ~1.07 GB/slot at max_len 768
elif cli.model == "1b":
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=22, num_attention_heads=32,
        num_key_value_heads=8, max_position_embeddings=2048,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        remat=False, remat_policy="none", use_flash=False,
    )
    max_len, prompt_hi = 1536, 1024
else:
    cfg = LlamaConfig.tiny(use_flash=False)
    max_len, prompt_hi = 128, 64

model = LlamaForCausalLM(cfg)
rng = np.random.default_rng(cli.seed)
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 8)))
params = jax.jit(model.init)(jax.random.key(0), toks)
n_params = sum(x.size for x in jax.tree.leaves(params))
print(f"model={cli.model} ({n_params/1e9:.2f}B) slots={cli.slots} "
      f"max_len={max_len} quantize={cli.quantize}", flush=True)

engine = ContinuousBatchingEngine(
    model, params, batch_slots=cli.slots, max_len=max_len,
    quantize=cli.quantize, quantize_donate=bool(cli.quantize),
).start()


def one_level(offered_rps: float) -> dict:
    n_req = cli.requests
    r = np.random.default_rng(cli.seed + int(offered_rps * 1000))
    # mixed prompts: log-uniform in [64, prompt_hi]; outputs geometric-ish
    lo = min(64, prompt_hi)
    plens = np.exp(r.uniform(np.log(lo), np.log(prompt_hi), n_req)).astype(int)
    olens = np.clip(r.geometric(1 / 24.0, n_req), 4, 96)
    olens = np.minimum(olens, max_len - plens - 4)  # engine hard cap
    gaps = r.exponential(1.0 / offered_rps, n_req)
    arrivals = np.cumsum(gaps)

    results = [None] * n_req
    lock = threading.Lock()

    def consume(i, q, t_submit):
        first, last, count = None, None, 0
        while True:
            tok = q.get()
            now = time.perf_counter()
            if tok is None:
                break
            if first is None:
                first = now
            last = now
            count += 1
        with lock:
            results[i] = (t_submit, first, last, count)

    # warm the compile caches (every prompt bucket + decode) before timing
    for b in engine._buckets:
        if b <= prompt_hi:
            engine.generate(
                rng.integers(0, cfg.vocab_size, max(b - 1, 1)).tolist(),
                max_new_tokens=2)

    threads = []
    t0 = time.perf_counter()
    for i in range(n_req):
        delay = t0 + arrivals[i] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        prompt = r.integers(0, cfg.vocab_size, plens[i]).tolist()
        t_submit = time.perf_counter()
        q = engine.submit(prompt, max_new_tokens=int(olens[i]))
        th = threading.Thread(target=consume, args=(i, q, t_submit))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0

    ttft = np.asarray([f - s for s, f, _, c in results if f])
    itl = np.asarray([(e - f) / max(c - 1, 1)
                      for _, f, e, c in results if f and c > 1])
    total_tokens = sum(c for *_, c in results)
    return {
        "offered_rps": offered_rps,
        "achieved_rps": round(len(results) / wall, 2),
        "ttft_p50_ms": round(float(np.percentile(ttft, 50)) * 1e3, 1),
        "ttft_p99_ms": round(float(np.percentile(ttft, 99)) * 1e3, 1),
        "itl_p50_ms": round(float(np.percentile(itl, 50)) * 1e3, 1),
        "itl_p99_ms": round(float(np.percentile(itl, 99)) * 1e3, 1),
        "tok_per_s": round(total_tokens / wall, 1),
        "mean_prompt": int(plens.mean()),
        "mean_output": float(olens.mean()),
    }


# direct prefill-stall measurement: decode inter-token gap when an
# admission intervenes = one bucketed-prefill forward
def prefill_stall() -> dict:
    out = {}
    for p in (64, 512, 1024):
        if p > max_len - 8:
            continue
        prompt = rng.integers(0, cfg.vocab_size, p).tolist()
        t0 = time.perf_counter()
        engine.generate(prompt, max_new_tokens=1)
        out[f"prefill_ms_p{p}"] = round((time.perf_counter() - t0) * 1e3, 1)
    return out


levels = [one_level(float(x)) for x in cli.loads.split(",")]
stall = prefill_stall()
print(json.dumps({"levels": levels, "prefill_stall": stall,
                  "admit_per_step": engine.admit_per_step}, indent=1),
      flush=True)
engine.stop()
