"""Operations and bytes from shapes alone, for ``model_type:
longcat_flash``.

``model_flops`` counts by ACTIVE parameters HERE: a token is multiplied, in
every double layer, by both attentions' five projections, both dense FFNs,
the router, and the FFN experts it chose AMONG THOSE HELD HERE (``moe_topk
x held / outputs`` = 12 x 16 / 768 = 0.25 of 16 on average under an even
router: what this chip computes); an identity expert costs nothing; then by
the untied head: forward, and backward with respect to activations (the
base is frozen: 4 x parameters x tokens); 6 x the adapters' parameters;
causal attention at half of the square, 6 products a head and attention,
the score product over ``nope + rope`` lanes and the value product over
``v_head_dim``. Nothing computed a second time counts (the recomputed
double layers of ``remat_policy: full``, the flash backward's scores), nor
the padding rows of the grouped product, nor elementwise work.

``flash_work`` is the kernels' own 9 products at a head of ``nope + rope``
score lanes and ``v_head_dim`` value lanes (as many key-value heads as
query heads: the one rotary key reaches the kernels as a copy a head).
``moe_held_gmm_work``: see there. Neither counts the forward products that
``remat_policy: full`` runs a second time in the backward, while a trace
times both runs: in a cell that recomputes, a share of a roofline read
with them leaves that second run's time unmatched by work.
"""
from __future__ import annotations


def _heads(cfg: dict) -> tuple:
    return (cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _projections(cfg: dict) -> dict:
    """``{name: (in, out)}`` of an attention's five projections."""
    hid, (h, d, dv) = cfg["hidden_size"], _heads(cfg)
    q_lat, kv_lat = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return {"q_a_proj": (hid, q_lat), "q_b_proj": (q_lat, h * d),
            "kv_a_proj": (hid, kv_lat + cfg["qk_rope_head_dim"]),
            "kv_b_proj": (kv_lat, h * (cfg["qk_nope_head_dim"] + dv)),
            "o_proj": (h * dv, hid)}


def router_outputs(cfg: dict) -> int:
    return cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]


def active_matmul_params(cfg: dict) -> dict:
    """Weights ONE token is multiplied by in a double layer, and in the
    head."""
    hid = cfg["hidden_size"]
    one_expert = 3 * hid * cfg["expert_ffn_hidden_size"]
    chosen_here = cfg["moe_topk"] * cfg["n_routed_experts"] \
        / router_outputs(cfg)
    return {
        "double_layer": 2 * sum(i * o for i, o in _projections(cfg).values())
        + 2 * 3 * hid * cfg["ffn_hidden_size"] + hid * router_outputs(cfg)
        + chosen_here * one_expert,
        "one_expert": one_expert, "head": hid * cfg["vocab_size"]}


def lora_params_per_layer(cfg: dict) -> int:
    r = cfg["run"]["lora_rank"]
    return 2 * sum(r * (i + o) for name, (i, o) in _projections(cfg).items()
                   if name in cfg["run"]["lora_targets"])


def model_flops(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Model operations of ``tokens`` trained tokens in rows of ``seq_len``."""
    p, layers = active_matmul_params(cfg), cfg["num_layers"]
    base = 4 * (layers * p["double_layer"] + p["head"]) * tokens
    adapters = 6 * layers * lora_params_per_layer(cfg) * tokens
    rows, (h, d, dv) = tokens // seq_len, _heads(cfg)
    # forward QK^T and PV, backward twice each: 3 products over the score
    # lanes and 3 over the value lanes, at half of the square, in both
    # attentions of a double layer
    attention = 3 * (2 * seq_len * seq_len * (d + dv) // 2) * h \
        * 2 * layers * rows
    return {"base": int(base), "lora": adapters, "attention": attention,
            "total": int(base) + adapters + attention}


def flash_work(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Operations and least bytes of flash fwd + dq + dkv for ``tokens``,
    over both attentions of every double layer. Of the 9 products, 5 run
    over the score lanes ``d`` (fwd's QK^T; dq's QK^T and dS K; dkv's QK^T
    and dS^T Q) and 4 over the value lanes ``dv`` (fwd's PV; dq's dO V^T;
    dkv's dO V^T and P^T dO). Bytes: q, k read at ``d``; v, o, do read at
    ``dv``; dq, dk written at ``d`` and dv at ``dv``; the row statistics in
    float32."""
    h, d, dv = _heads(cfg)
    attentions, rows = 2 * cfg["num_layers"], tokens // seq_len
    half = 2 * seq_len * seq_len // 2     # one causal T x T product a lane
    flops = (5 * d + 4 * dv) * half * h * attentions * rows
    at_d = seq_len * h * d * 2            # bf16 bytes of q (k, dq, dk)
    at_v = seq_len * h * dv * 2           # bf16 bytes of v (o, do, dv)
    stat = seq_len * h * 4                # f32 row statistic (lse, delta)
    fwd = 2 * at_d + 2 * at_v + stat
    dq = 2 * at_d + 2 * at_v + 2 * stat + at_d
    dkv = 2 * at_d + 2 * at_v + 2 * stat + at_d + at_v
    return {"flops": flops, "bytes": (fwd + dq + dkv) * attentions * rows}


def moe_held_gmm_work(cfg: dict, tokens: int, seq_len: int,
                      live_share: float = 1.0, held=None) -> dict:
    """Operations and least bytes of the held experts' grouped products
    (``moe_gmm`` forward, ``moe_gmm_t`` backward with respect to the rows)
    for ``tokens``: three products a double layer each way (gate, up,
    down), ``2 x rows x K x N`` over the rows that hold an assignment —
    ``held`` of all ``tokens x moe_topk`` assignments, identity choices
    among them (they take no row), which the program counts
    (``round/<n>/moe``: ``held_share``; by default the held experts' share
    of the router's outputs, 16 / 768). Bytes: the three matrices of every
    HELD expert that got a row, read once a product and a step —
    ``live_share`` of them, which the program counts too — and the rows
    read and written once in bfloat16. A step is one row of ``seq_len``
    tokens (B1, as the cell has it)."""
    hid, mid = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    layers, steps = cfg["num_layers"], tokens // seq_len
    held = cfg["n_routed_experts"] / router_outputs(cfg) \
        if held is None else held
    rows = tokens * cfg["moe_topk"] * held
    products = 6 * layers                      # gate, up, down: fwd and bwd
    flops = products * 2 * hid * mid * rows
    weights = (products * cfg["n_routed_experts"] * live_share
               * hid * mid * 2 * steps)
    moved = products * (hid + mid) * 2 * rows
    return {"flops": flops, "bytes": weights + moved}
