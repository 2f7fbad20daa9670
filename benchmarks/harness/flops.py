"""Operations and bytes from shapes alone.

Two counts, which differ on purpose:

* ``model_flops`` is what the MFU divides by the chip's peak: the model's
  operations only. Forward through the frozen base and the head, backward
  with respect to ACTIVATIONS through them (a frozen weight gets no
  gradient, so 4 x parameters x tokens, not 6), LoRA forward, backward and
  its weight gradients (6 x adapter parameters x tokens), and causal
  attention at half of the square: QK^T and PV forward, dV, dP, dQ, dK
  backward = 6 products of ``2 T^2 D / 2`` each per head. Nothing that is
  computed a second time counts: not remat, not the flash backward's
  recomputation of the scores. Elementwise work (norms, rope, softmax,
  SwiGLU, Adam on the adapters) is left out, as is usual for an MFU.
* ``flash_work`` is what the kernels' roofline divides by the kernels'
  time: the flash ALGORITHM's own operations, its backward recomputation
  included, because a kernel cannot be faster than the work its algorithm
  states: forward 2 products; dq kernel 3 (scores again, dP, dQ); dkv
  kernel 4 (scores again, dP, dV, dK) = 9 products against the model's 6.
  Its bytes are the least the three kernels must move: every operand read
  once and every result written once per kernel, in the types they have.
"""
from __future__ import annotations


def _dims(cfg: dict):
    h = cfg["hidden_size"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    return h, cfg["intermediate_size"], heads, kv_heads, d


def base_matmul_params(cfg: dict) -> dict:
    """Weights that a token is multiplied by, per layer and in the head."""
    h, f, heads, kv_heads, d = _dims(cfg)
    layer = h * h * 2 + h * kv_heads * d * 2 + 3 * h * f
    return {"layer": layer, "head": h * cfg["vocab_size"]}


def lora_params_per_layer(cfg: dict) -> int:
    h, _, _, kv_heads, d = _dims(cfg)
    r = cfg["run"]["lora_rank"]
    outs = {"q_proj": h, "k_proj": kv_heads * d, "v_proj": kv_heads * d,
            "o_proj": h}
    return sum(r * (h + outs[t]) for t in cfg["run"]["lora_targets"])


def model_flops(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Model operations of ``tokens`` trained tokens in rows of ``seq_len``."""
    h, f, heads, kv_heads, d = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    p = base_matmul_params(cfg)
    base = 4 * (layers * p["layer"] + p["head"]) * tokens
    lora = 6 * layers * lora_params_per_layer(cfg) * tokens
    rows = tokens // seq_len
    attention = 6 * (2 * seq_len * seq_len * d // 2) * heads * layers * rows
    return {"base": base, "lora": lora, "attention": attention,
            "total": base + lora + attention}


def flash_work(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Operations and least bytes of flash fwd + dq + dkv for ``tokens``."""
    h, f, heads, kv_heads, d = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    rows = tokens // seq_len
    product = 2 * seq_len * seq_len * d // 2  # one causal T x T x D product
    flops = 9 * product * heads * layers * rows
    q = seq_len * heads * d * 2          # bf16 bytes of q, o, do, dq
    kv = seq_len * kv_heads * d * 2      # bf16 bytes of k (or v)
    stat = seq_len * heads * 4           # f32 row statistic (lse, delta)
    fwd = q + 2 * kv + q + stat                      # q k v -> o lse
    dq = q + 2 * kv + q + 2 * stat + q               # q k v do lse delta -> dq
    # dk, dv come out per QUERY head (summed over the group outside)
    dkv = q + 2 * kv + q + 2 * stat + 2 * q
    return {"flops": flops, "bytes": (fwd + dq + dkv) * layers * rows}
