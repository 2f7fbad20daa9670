"""Operations and bytes from shapes alone, for ``model_type: zaya``.

``model_flops`` counts by ACTIVE parameters: a token is multiplied by the
attention projections, the two convolutions, the router MLP and ONE of the
16 experts in each layer, and by the tied head — forward, and backward with
respect to activations (the base is frozen: 4 x parameters x tokens, not
6); 6 x the adapters' parameters; causal attention at half of the square,
6 products of ``2 T^2 D / 2`` a query head. Counting all 16 experts would
read 22 GF a token against about 4.1 GF of work done. Nothing computed a
second time counts (remat, the flash backward's scores), nor the padding
rows of the grouped product, nor elementwise work.

``flash_work`` is ``llama``'s statement at this family's head counts (the
flash ALGORITHM's own 9 products and the least bytes its three kernels
move). ``moe_gmm_work`` is the grouped products' own: see there.
"""
from __future__ import annotations


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def active_matmul_params(cfg: dict) -> dict:
    """Weights ONE token is multiplied by, per layer and in the head."""
    h, heads, kv_heads, d = _dims(cfg)
    q, kv = heads * d, kv_heads * d
    r, e = cfg["router_hidden_size"], cfg["num_experts"]
    projections = h * q + h * kv + 2 * h * (kv // 2) + q * h
    convolutions = (cfg["cca_time0"] * (q + kv)
                    + cfg["cca_time1"] * (heads + kv_heads) * d * d)
    router = h * r + 2 * r * r + r * e
    one_expert = 3 * h * cfg["moe_intermediate_size"]
    return {"layer": projections + convolutions + router
            + cfg["num_experts_per_tok"] * one_expert,
            "one_expert": one_expert, "head": h * cfg["vocab_size"]}


def lora_params_per_layer(cfg: dict) -> int:
    h, heads, kv_heads, d = _dims(cfg)
    q, kv = heads * d, kv_heads * d
    r = cfg["run"]["lora_rank"]
    shapes = {"q_proj": (h, q), "k_proj": (h, kv), "v_proj": (h, kv // 2),
              "v_prev_proj": (h, kv // 2), "o_proj": (q, h)}
    return sum(r * sum(shapes[t]) for t in cfg["run"]["lora_targets"])


def model_flops(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Model operations of ``tokens`` trained tokens in rows of ``seq_len``."""
    h, heads, kv_heads, d = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    p = active_matmul_params(cfg)
    base = 4 * (layers * p["layer"] + p["head"]) * tokens
    lora = 6 * layers * lora_params_per_layer(cfg) * tokens
    rows = tokens // seq_len
    attention = 6 * (2 * seq_len * seq_len * d // 2) * heads * layers * rows
    return {"base": base, "lora": lora, "attention": attention,
            "total": base + lora + attention}


def flash_work(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Operations and least bytes of flash fwd + dq + dkv for ``tokens``."""
    h, heads, kv_heads, d = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    rows = tokens // seq_len
    product = 2 * seq_len * seq_len * d // 2  # one causal T x T x D product
    flops = 9 * product * heads * layers * rows
    q = seq_len * heads * d * 2          # bf16 bytes of q, o, do, dq
    kv = seq_len * kv_heads * d * 2      # bf16 bytes of k (or v)
    stat = seq_len * heads * 4           # f32 row statistic (lse, delta)
    fwd = q + 2 * kv + q + stat
    dq = q + 2 * kv + q + 2 * stat + q
    dkv = q + 2 * kv + q + 2 * stat + 2 * q
    return {"flops": flops, "bytes": (fwd + dq + dkv) * layers * rows}


def moe_gmm_work(cfg: dict, tokens: int, seq_len: int,
                 live_share: float = 1.0) -> dict:
    """Operations and least bytes of the grouped products (``moe_gmm``
    forward, ``moe_gmm_t`` backward with respect to the rows) for
    ``tokens``: three products a layer each way, ``2 x rows x K x N`` over
    the rows that hold a token (a padding row is no work the algorithm
    states). Bytes: the matrix of every expert that got a token read once a
    product and a step — ``live_share`` of the experts, which the program
    counts (``round/<n>/moe``): seeded routers herd, so it is under 1 —
    and the rows read and written once in bfloat16. A step is one row of
    ``seq_len`` tokens (B1, as the cells have it): a larger batch would
    need fewer reads of the matrices a token."""
    h, m, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    layers, steps = cfg["num_hidden_layers"], tokens // seq_len
    products = 6 * layers                     # gate, up, down: fwd and bwd
    flops = products * 2 * h * m * tokens
    weights = products * e * live_share * h * m * 2 * steps
    rows = products * (h + m) * 2 * tokens
    return {"flops": flops, "bytes": weights + rows}
