"""Operations and bytes from shapes alone, for ``model_type:
glm4_moe_lite``.

``model_flops`` counts by ACTIVE parameters: a token is multiplied by the
attention's five projections, by its layer's FFN — the dense SwiGLU, or the
router, the shared expert and the ``num_experts_per_tok`` routed experts it
chose of ``n_routed_experts`` (4 of 64) — and by the untied head: forward,
and backward with respect to activations (the base is frozen: 4 x
parameters x tokens); 6 x the adapters' parameters; causal attention at
half of the square, 6 products a head and layer, the score product over
``nope + rope`` lanes and the value product over ``v_head_dim``. Nothing
computed a second time counts (remat, the flash backward's scores), nor the
padding rows of the grouped product, nor elementwise work.

``flash_work`` is the kernels' own 9 products at the heads' size (D256,
as many key-value heads as query heads: the one rotary key reaches the
kernels as a copy a head). ``moe_gated_gmm_work``: see there.
"""
from __future__ import annotations


def _layers(cfg: dict) -> dict:
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return {"dense": dense, "expert": cfg["num_hidden_layers"] - dense}


def _heads(cfg: dict) -> tuple:
    return (cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _projections(cfg: dict) -> dict:
    """``{name: (in, out)}`` of the attention's five projections."""
    hid, (h, d, dv) = cfg["hidden_size"], _heads(cfg)
    q_lat, kv_lat = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return {"q_a_proj": (hid, q_lat), "q_b_proj": (q_lat, h * d),
            "kv_a_proj": (hid, kv_lat + cfg["qk_rope_head_dim"]),
            "kv_b_proj": (kv_lat, h * (cfg["qk_nope_head_dim"] + dv)),
            "o_proj": (h * dv, hid)}


def active_matmul_params(cfg: dict) -> dict:
    """Weights ONE token is multiplied by: in the attention of any layer,
    in the FFN of a layer of each kind, and in the head."""
    hid = cfg["hidden_size"]
    one_expert = 3 * hid * cfg["moe_intermediate_size"]
    return {
        "attn": sum(i * o for i, o in _projections(cfg).values()),
        "dense": 3 * hid * cfg["intermediate_size"],
        "expert": hid * cfg["n_routed_experts"]
        + (cfg["num_experts_per_tok"] + cfg["n_shared_experts"]) * one_expert,
        "one_expert": one_expert, "head": hid * cfg["vocab_size"]}


def lora_params_per_layer(cfg: dict) -> int:
    r = cfg["run"]["lora_rank"]
    return sum(r * (i + o) for name, (i, o) in _projections(cfg).items()
               if name in cfg["run"]["lora_targets"])


def model_flops(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Model operations of ``tokens`` trained tokens in rows of ``seq_len``."""
    kinds, p = _layers(cfg), active_matmul_params(cfg)
    layers = cfg["num_hidden_layers"]
    base = 4 * (layers * p["attn"] + kinds["dense"] * p["dense"]
                + kinds["expert"] * p["expert"] + p["head"]) * tokens
    adapters = 6 * layers * lora_params_per_layer(cfg) * tokens
    rows, (h, d, dv) = tokens // seq_len, _heads(cfg)
    # forward QK^T and PV, backward twice each: 3 products over the score
    # lanes and 3 over the value lanes, at half of the square
    attention = 3 * (2 * seq_len * seq_len * (d + dv) // 2) * h * layers * rows
    return {"base": int(base), "lora": adapters, "attention": attention,
            "total": int(base) + adapters + attention}


def flash_work(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Operations and least bytes of flash fwd + dq + dkv for ``tokens``,
    over every layer (the statement of ``llama``'s, with a head of ``nope +
    rope`` = ``v_head_dim`` lanes and a key-value head a query head)."""
    h, d, _ = _heads(cfg)
    layers, rows = cfg["num_hidden_layers"], tokens // seq_len
    product = 2 * seq_len * seq_len * d // 2  # one causal T x T x D product
    flops = 9 * product * h * layers * rows
    q = seq_len * h * d * 2          # bf16 bytes of q, o, do, dq; k, v too
    stat = seq_len * h * 4           # f32 row statistic (lse, delta)
    fwd = q + 2 * q + q + stat
    dq = q + 2 * q + q + 2 * stat + q
    dkv = q + 2 * q + q + 2 * stat + 2 * q
    return {"flops": flops, "bytes": (fwd + dq + dkv) * layers * rows}


def moe_gated_gmm_work(cfg: dict, tokens: int, seq_len: int,
                       live_share: float = 1.0) -> dict:
    """Operations and least bytes of the grouped products (``moe_gmm``
    forward, ``moe_gmm_t`` backward with respect to the rows) for
    ``tokens``: three products a layer each way (gate, up, down), ``2 x rows
    x K x N`` over the rows that hold an assignment — all ``tokens x
    num_experts_per_tok`` of them: every expert is held here. Bytes: the
    three matrices of every expert that got a row, read once a product and
    a step — ``live_share`` of them, which the program counts
    (``round/<n>/moe``) — and the rows read and written once in bfloat16.
    A step is one row of ``seq_len`` tokens (B1, as the cell has it)."""
    hid, mid = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers, steps = _layers(cfg)["expert"], tokens // seq_len
    rows = tokens * cfg["num_experts_per_tok"]
    products = 6 * layers                      # gate, up, down: fwd and bwd
    flops = products * 2 * hid * mid * rows
    weights = (products * cfg["n_routed_experts"] * live_share
               * hid * mid * 2 * steps)
    moved = products * (hid + mid) * 2 * rows
    return {"flops": flops, "bytes": weights + moved}
