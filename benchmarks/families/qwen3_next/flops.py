"""Operations and bytes from shapes alone, for ``model_type: qwen3_next``.

``model_flops`` counts by ACTIVE parameters HERE: a token is multiplied by
its layer's mixer — a Gated DeltaNet layer's three projections and
convolution, or the attention layer's four projections — then by every
layer's router, shared expert and its gate, and the routed experts it
chose AMONG THOSE HELD HERE (``num_experts_per_tok x held / published`` =
10 x 128 / 512 = 2.5 of 128 on average: what this chip computes), and by
the untied head: forward, and backward with respect to activations (the
base is frozen: 4 x parameters x tokens); 6 x the adapters' parameters;
causal attention at half of the square, 6 products a query head of the
attention layers at ``head_dim``; the recurrence's own operations
(``gdn_work``). Nothing computed a second time counts (remat, the flash
backward's scores, the chunked form's inverse and recomputed inside), nor
the padding rows of the grouped product, nor elementwise work outside the
recurrence.

``flash_work`` is ``llama``'s statement over this family's attention
layers (query heads sharing key-value heads). ``gdn_work``: see there.
"""
from __future__ import annotations


def _kinds(cfg: dict) -> dict:
    layers = cfg["num_hidden_layers"]
    full = sum((i + 1) % cfg["full_attention_interval"] == 0
               for i in range(layers))
    return {"linear": layers - full, "full": full}


def held_share(cfg: dict) -> float:
    return cfg["num_experts"] / cfg["published"]["num_experts"]


def _linear_widths(cfg: dict) -> tuple:
    """``(key lanes, value lanes)`` of a Gated DeltaNet layer over all its
    heads."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def active_matmul_params(cfg: dict) -> dict:
    """Weights ONE token is multiplied by: in a layer's mixer of each kind,
    in every layer's expert layer, and in the head."""
    hid = cfg["hidden_size"]
    key, value = _linear_widths(cfg)
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    one_expert = 3 * hid * cfg["moe_intermediate_size"]
    chosen_here = cfg["num_experts_per_tok"] * held_share(cfg)
    return {
        "linear": hid * (2 * key + 2 * value) + hid * 2
        * cfg["linear_num_value_heads"] + value * hid
        + cfg["linear_conv_kernel_dim"] * (2 * key + value),
        "full": hid * 2 * q + 2 * hid * kv + q * hid,
        "moe": hid * cfg["published"]["num_experts"]
        + 3 * hid * cfg["shared_expert_intermediate_size"] + hid
        + chosen_here * one_expert,
        "one_expert": one_expert, "head": hid * cfg["vocab_size"]}


def lora_params(cfg: dict) -> dict:
    """Adapter parameters of a layer of each kind."""
    hid, r = cfg["hidden_size"], cfg["run"]["lora_rank"]
    key, value = _linear_widths(cfg)
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    shapes = {"linear": {"in_proj_qkvz": (hid, 2 * key + 2 * value),
                         "out_proj": (value, hid)},
              "full": {"q_proj": (hid, 2 * q), "k_proj": (hid, kv),
                       "v_proj": (hid, kv), "o_proj": (q, hid)}}
    return {kind: sum(r * sum(io) for name, io in of.items()
                      if name in cfg["run"]["lora_targets"])
            for kind, of in shapes.items()}


def _recurrence_flops_per_token(cfg: dict) -> int:
    """Forward operations a token of the delta rule itself, over every
    value head: the state's decay (1), its prediction ``S'^T k`` (2), the
    rank-one update (2) and the read-out ``S^T q`` (2), each over ``dk x
    dv``."""
    return 7 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def model_flops(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Model operations of ``tokens`` trained tokens in rows of ``seq_len``."""
    kinds, p, lora = _kinds(cfg), active_matmul_params(cfg), lora_params(cfg)
    layers = cfg["num_hidden_layers"]
    base = 4 * (sum(kinds[k] * p[k] for k in kinds) + layers * p["moe"]
                + p["head"]) * tokens
    adapters = 6 * sum(kinds[k] * lora[k] for k in kinds) * tokens
    rows, d = tokens // seq_len, cfg["head_dim"]
    attention = (6 * (2 * seq_len * seq_len * d // 2)
                 * cfg["num_attention_heads"] * kinds["full"] * rows)
    # forward, and backward with respect to q, k, v, g, beta: twice the
    # forward
    scan = 3 * _recurrence_flops_per_token(cfg) * kinds["linear"] * tokens
    return {"base": int(base), "lora": adapters, "attention": attention,
            "scan": scan, "total": int(base) + adapters + attention + scan}


def flash_work(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Operations and least bytes of flash fwd + dq + dkv for ``tokens``,
    over the attention layers."""
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    layers, rows = _kinds(cfg)["full"], tokens // seq_len
    product = 2 * seq_len * seq_len * d // 2  # one causal T x T x D product
    flops = 9 * product * heads * layers * rows
    q = seq_len * heads * d * 2          # bf16 bytes of q, o, do, dq
    kv = seq_len * kv_heads * d * 2      # bf16 bytes of k (or v)
    stat = seq_len * heads * 4           # f32 row statistic (lse, delta)
    fwd = q + 2 * kv + q + stat
    dq = q + 2 * kv + q + 2 * stat + q
    dkv = q + 2 * kv + q + 2 * stat + 2 * q
    return {"flops": flops, "bytes": (fwd + dq + dkv) * layers * rows}


def gdn_work(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Operations and least bytes of the delta rule (forward and backward)
    over the Gated DeltaNet layers, whatever computes it — a chunked form,
    a kernel or the sequential loop state the same work: the recurrence's
    own 7 x dk x dv operations a value head and a token forward and twice
    that backward; bytes: ``q``, ``k`` (a value head each, as the op takes
    them) and ``v`` in the compute type and ``g``, ``beta`` in float32 read
    and ``o`` written forward; the same five and ``do`` read and five
    gradients written backward. Nothing inside (the chunks' ``[C, C]``
    matrices and inverses, the chunk states, what the backward computes
    again) is work the recurrence states."""
    hv, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    layers = _kinds(cfg)["linear"]
    ins = (2 * hv * dk + hv * dv) * 2 + 2 * hv * 4
    out = hv * dv * 2
    flops = 3 * _recurrence_flops_per_token(cfg) * layers * tokens
    return {"flops": flops,
            "bytes": ((ins + out) + (ins + out + ins)) * layers * tokens}
