"""Operations and bytes from shapes alone, for ``model_type: nemotron_h``.

``model_flops`` counts by ACTIVE parameters HERE: a token is multiplied by
its layer's mixer — a Mamba layer's two projections and convolution, or
the attention layer's four projections, or an expert layer's router, both
latent projections, the shared expert and the routed experts it chose
AMONG THOSE HELD HERE (``num_experts_per_tok x held / published`` = 22 x
128 / 512 = 5.5 of 128 on average: what this chip computes) — and by the
untied head: forward, and backward with respect to activations (the base is
frozen: 4 x parameters x tokens); 6 x the adapters' parameters; causal
attention at half of the square, 6 products a query head of the one
attention layer; the recurrence's own operations (``ssd_work``). Nothing
computed a second time counts (remat, the flash backward's scores, the
scan's recomputed inside), nor the padding rows of the grouped product,
nor elementwise work outside the recurrence.

``flash_work`` is ``llama``'s statement over this family's attention
layers. ``ssd_work`` and ``moe_held_gmm_work``: see there.
"""
from __future__ import annotations


def _kinds(cfg: dict) -> dict:
    letters = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return {kind: letters.count(kind) for kind in "M*E"}


def held_share(cfg: dict) -> float:
    return cfg["n_routed_experts"] / cfg["published"]["n_routed_experts"]


def active_matmul_params(cfg: dict) -> dict:
    """Weights ONE token is multiplied by, per layer of each kind and in
    the head."""
    hid = cfg["hidden_size"]
    h, g, n = cfg["mamba_num_heads"], cfg["n_groups"], cfg["ssm_state_size"]
    d = h * cfg["mamba_head_dim"]
    conv = d + 2 * g * n
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    lat, mid = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    one_expert = 2 * lat * mid
    chosen_here = cfg["num_experts_per_tok"] * held_share(cfg)
    return {
        "M": hid * (d + conv + h) + d * hid + cfg["conv_kernel"] * conv,
        "*": hid * q + 2 * hid * kv + q * hid,
        "E": hid * cfg["published"]["n_routed_experts"] + 2 * hid * lat
        + 2 * hid * cfg["moe_shared_expert_intermediate_size"]
        + chosen_here * one_expert,
        "one_expert": one_expert, "head": hid * cfg["vocab_size"]}


def lora_params(cfg: dict) -> dict:
    """Adapter parameters of a layer of each kind."""
    hid, r = cfg["hidden_size"], cfg["run"]["lora_rank"]
    h, g, n = cfg["mamba_num_heads"], cfg["n_groups"], cfg["ssm_state_size"]
    d = h * cfg["mamba_head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    shapes = {"M": {"in_proj": (hid, 2 * d + 2 * g * n + h),
                    "out_proj": (d, hid)},
              "*": {"q_proj": (hid, q), "k_proj": (hid, kv),
                    "v_proj": (hid, kv), "o_proj": (q, hid)}, "E": {}}
    return {kind: sum(r * sum(io) for name, io in of.items()
                      if name in cfg["run"]["lora_targets"])
            for kind, of in shapes.items()}


def _recurrence_flops_per_token(cfg: dict) -> int:
    """Forward operations a token of the recurrence itself: the decay of
    the state (1), the outer product added to it (2) and the read-out
    against C (2), each over ``H x P x N``."""
    return 5 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]


def model_flops(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Model operations of ``tokens`` trained tokens in rows of ``seq_len``."""
    kinds, p, lora = _kinds(cfg), active_matmul_params(cfg), lora_params(cfg)
    base = 4 * (sum(kinds[k] * p[k] for k in kinds) + p["head"]) * tokens
    adapters = 6 * sum(kinds[k] * lora[k] for k in kinds) * tokens
    rows, d = tokens // seq_len, cfg["head_dim"]
    attention = (6 * (2 * seq_len * seq_len * d // 2)
                 * cfg["num_attention_heads"] * kinds["*"] * rows)
    # forward, and backward with respect to x, dt, B, C: twice the forward
    scan = 3 * _recurrence_flops_per_token(cfg) * kinds["M"] * tokens
    return {"base": int(base), "lora": adapters, "attention": attention,
            "scan": scan, "total": int(base) + adapters + attention + scan}


def flash_work(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Operations and least bytes of flash fwd + dq + dkv for ``tokens``,
    over the attention layers."""
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    layers, rows = _kinds(cfg)["*"], tokens // seq_len
    product = 2 * seq_len * seq_len * d // 2  # one causal T x T x D product
    flops = 9 * product * heads * layers * rows
    q = seq_len * heads * d * 2          # bf16 bytes of q, o, do, dq
    kv = seq_len * kv_heads * d * 2      # bf16 bytes of k (or v)
    stat = seq_len * heads * 4           # f32 row statistic (lse, delta)
    fwd = q + 2 * kv + q + stat
    dq = q + 2 * kv + q + 2 * stat + q
    dkv = q + 2 * kv + q + 2 * stat + 2 * q
    return {"flops": flops, "bytes": (fwd + dq + dkv) * layers * rows}


def ssd_work(cfg: dict, tokens: int, seq_len: int) -> dict:
    """Operations and least bytes of the recurrence (forward and backward)
    over the Mamba layers, whatever computes it — a chunked form, a kernel
    or the sequential loop state the same work: the recurrence's own 5 x H
    x P x N operations a token forward and twice that backward; bytes:
    ``x``, ``B``, ``C`` (compute type) and ``dt`` (float32) read and ``y``
    written forward; the same four and ``dy`` read and four gradients
    written backward. Nothing inside (the ``[H, Q, Q]`` decays, the chunk
    states, what the backward computes again) is work the recurrence
    states."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    bc = 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    layers = _kinds(cfg)["M"]
    ins = (h * p + bc) * 2 + h * 4       # x, B, C in bf16; dt in f32
    out = h * p * 2
    flops = 3 * _recurrence_flops_per_token(cfg) * layers * tokens
    return {"flops": flops,
            "bytes": ((ins + out) + (ins + out + ins)) * layers * tokens}


def moe_held_gmm_work(cfg: dict, tokens: int, seq_len: int,
                      live_share: float = 1.0, held=None) -> dict:
    """Operations and least bytes of the grouped products (``moe_gmm``
    forward, ``moe_gmm_t`` backward with respect to the rows) for
    ``tokens``: two products a layer each way, ``2 x rows x K x N`` over the
    rows that hold an assignment — ``held`` of all ``tokens x
    num_experts_per_tok`` assignments, which the program counts
    (``round/<n>/moe``: ``held_share``; by default the held experts' share
    of the published ones). Bytes: both matrices of every HELD expert that
    got a row, read once a product and a step — ``live_share`` of them,
    which the program counts too — and the rows read and written once in
    bfloat16. A step is one row of ``seq_len`` tokens (B1, as the cell has
    it)."""
    lat, mid = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    layers, steps = _kinds(cfg)["E"], tokens // seq_len
    held = held_share(cfg) if held is None else held
    rows = tokens * cfg["num_experts_per_tok"] * held
    products = 4 * layers                      # up, down: fwd and bwd
    flops = products * 2 * lat * mid * rows
    weights = (products * cfg["n_routed_experts"] * live_share
               * lat * mid * 2 * steps)
    moved = products * (lat + mid) * 2 * rows
    return {"flops": flops, "bytes": weights + moved}
