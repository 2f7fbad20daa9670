"""Llama-family causal LM — the flagship model of the LLM path.

Parity target: the reference fine-tunes HF Llama/GPT-NeoX checkpoints via
``train/llm`` (``configurations.py:140`` ModelArguments, flash-attn patch
``models/attention.py:30``). Here the architecture is implemented natively
in flax so the whole forward/backward is one XLA program:

- RMSNorm, rotary position embeddings, grouped-query attention, SwiGLU MLP
  (Llama-2/3 architecture);
- attention runs through the framework's Pallas flash kernel on TPU
  (``fedml_tpu/ops/flash_attention.py``) and plain XLA elsewhere;
- optional LoRA adapters on the attention projections (the federated LLM
  path exchanges *only* these — reference ``configurations.py:291``
  ``get_peft_config`` / ``peft_utils.py``);
- weights are stored with named axes that match the FSDP×TP partition
  rules in ``fedml_tpu/train/llm/sharding.py``.

Compute dtype is bf16 by default (MXU-native); params stay fp32 masters.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.llm import preset_from_args
from fedml_tpu.models.llm.causal_lm import CausalLM
from fedml_tpu.models.llm.layers import (RMSNorm, SwiGLU, apply_rope,
                                         causal_attention, lora_dense,
                                         merge_heads)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    # LoRA (0 = disabled)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # Mixture-of-experts FFN (0 = dense MLP). Experts shard over the
    # mesh's "ep" axis (expert parallelism).
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 1024  # routing group: dispatch memory is O(S·g·K)
    moe_aux_weight: float = 0.01  # load-balance pressure in the train loss
    # training knobs
    dtype: Any = jnp.bfloat16
    # storage dtype of the FROZEN base weights. fp32 default (full-FT
    # masters); LoRA fine-tuning can store the base in bf16 — frozen
    # weights need no master copy, and bf16 halves both HBM residency
    # and the per-step cast traffic (see PERF_NOTES.md)
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "full": recompute the whole block in backward (min memory, +1/3
    # forward flops); "dots": save matmul outputs, recompute elementwise
    # only (the XLA sweet spot — matmuls are the expensive part and HBM
    # usually fits their outputs); "none"/remat=False: save everything
    remat_policy: str = "full"
    use_flash: bool = True

    # what the round's program hands back beside the loss: nothing here
    round_stats = ()

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_dim(self) -> int:
        return self.head_dim

    @property
    def aux_loss_weight(self) -> float:
        """Weight of the sown load-balance terms in the train loss."""
        return float(self.moe_aux_weight) if self.num_experts > 0 else 0.0

    def module(self) -> "LlamaForCausalLM":
        """The flax module of this configuration: what the trainer, the
        aggregator and ``model_hub.create`` ask a configuration object for
        (``ZayaConfig.module`` answers with its own)."""
        return LlamaForCausalLM(self)

    # -- presets (kw overrides win — e.g. a reduced-depth 7B) ------------
    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        for k, v in dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=11008,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=32,
        ).items():
            kw.setdefault(k, v)
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        for k, v in dict(
            vocab_size=32000, hidden_size=5120, intermediate_size=13824,
            num_hidden_layers=40, num_attention_heads=40,
            num_key_value_heads=40,
        ).items():
            kw.setdefault(k, v)
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        for k, v in dict(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, rope_theta=500000.0,
        ).items():
            kw.setdefault(k, v)
        return LlamaConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Unit-test / dry-run scale (runs on CPU in milliseconds)."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("num_key_value_heads", 2)
        kw.setdefault("max_position_embeddings", 128)
        kw.setdefault("remat", False)
        return LlamaConfig(**kw)

    # what ``model_size`` may say, and the preset it means
    PRESETS = {"tiny": "tiny", "llama2_7b": "llama2_7b", "7b": "llama2_7b",
               "llama2_13b": "llama2_13b", "13b": "llama2_13b",
               "llama3_8b": "llama3_8b", "8b": "llama3_8b"}
    # the fields a user's yaml may override by name
    YAML_FIELDS = ("lora_rank", "lora_alpha", "max_position_embeddings",
                   "num_hidden_layers", "hidden_size", "num_experts",
                   "num_experts_per_tok", "moe_capacity_factor")

    @classmethod
    def from_args(cls, args: Any, vocab_size: Optional[int] = None) -> "LlamaConfig":
        return preset_from_args(cls, args, vocab_size)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------
class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, kv_cache=None, attention_fn=None):
        cfg = self.cfg
        b, t, _ = x.shape
        h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = lora_dense(cfg, h * d, "q_proj", ("embed", "heads"))(x)
        k = lora_dense(cfg, hkv * d, "k_proj", ("embed", "heads"))(x)
        v = lora_dense(cfg, hkv * d, "v_proj", ("embed", "heads"))(x)
        with jax.named_scope("attn_layout"):
            q = q.reshape(b, t, h, d).transpose(0, 2, 1, 3)
            k = k.reshape(b, t, hkv, d).transpose(0, 2, 1, 3)
            v = v.reshape(b, t, hkv, d).transpose(0, 2, 1, 3)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        new_cache = None
        if kv_cache is not None:
            # decode: append to cache, attend over full prefix. cache_len may
            # be a scalar (all rows aligned) or a [B] vector of per-row
            # lengths — the latter is what continuous batching needs: each
            # slot of the serving batch sits at its own position.
            ck, cv, cache_len = kv_cache
            lens = jnp.broadcast_to(jnp.asarray(cache_len), (b,))
            ck = jax.vmap(
                lambda c, kk, l: jax.lax.dynamic_update_slice(c, kk, (0, l, 0))
            )(ck, k, lens)
            cv = jax.vmap(
                lambda c, vv, l: jax.lax.dynamic_update_slice(c, vv, (0, l, 0))
            )(cv, v, lens)
            k, v = ck, cv
            new_cache = (ck, cv, cache_len + t)
            s_len = ck.shape[2]
            group = h // hkv
            kk = jnp.repeat(k, group, axis=1)
            vv = jnp.repeat(v, group, axis=1)
            scale = d ** -0.5
            logits = jnp.einsum(
                "bhtd,bhsd->bhts", q.astype(jnp.float32), kk.astype(jnp.float32)
            ) * scale
            pos = lens[:, None] + jnp.arange(t)[None, :]  # [B, T]
            mask = (
                jnp.arange(s_len)[None, None, :] <= pos[:, :, None]
            )  # causal over each row's prefix [B, T, S]
            logits = jnp.where(mask[:, None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhts,bhsd->bhtd", probs, vv.astype(jnp.float32))
            out = out.astype(cfg.dtype)
        else:
            out = causal_attention(q, k, v, cfg, attention_fn)
        out = lora_dense(cfg, cfg.hidden_size, "o_proj", ("heads", "embed"))(
            merge_heads(out))
        return out, new_cache


class LlamaMoE(nn.Module):
    """Mixture-of-experts FFN (Mixtral/Switch shape) with expert parallelism.

    Expert weights are stacked with a leading ``expert`` logical dim,
    mapped to the mesh's ``ep`` axis (``train/llm/sharding.py``): the
    dispatch/combine einsums below contract token-major tensors against
    expert-major ones, and XLA inserts the all-to-alls that a hand-written
    NCCL MoE would issue. Top-k routing with capacity dropping; aux
    load-balance loss is sown as an intermediate. No reference
    counterpart — the reference has no MoE anywhere (SURVEY §2.10).
    """

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        E, K = cfg.num_experts, cfg.num_experts_per_tok
        B, T, H = x.shape
        S = B * T
        # Route within fixed-size token groups (Switch/Mesh-TF grouping):
        # dispatch/combine are [G, g, E, cap] with cap ∝ g·K/E, so memory
        # is O(S·g·K) — linear in S — instead of O(S²·K) ungrouped.
        g = min(int(cfg.moe_group_size), S)
        S_pad = ((S + g - 1) // g) * g
        xs = x.reshape(S, H)
        if S_pad != S:
            # padding tokens route like zeros and are sliced off after the
            # combine; they only waste capacity in the tail group
            xs = jnp.concatenate(
                [xs, jnp.zeros((S_pad - S, H), xs.dtype)], axis=0
            )
        G = S_pad // g
        xg = xs.reshape(G, g, H)
        # router in f32 for numerically-stable softmax/top-k
        router_w = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("embed", None)
            ),
            (H, E), jnp.float32,
        )
        logits = xg.astype(jnp.float32) @ router_w              # [G, g, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(probs, K)             # [G, g, K]
        top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True)

        cap = max(4, int(cfg.moe_capacity_factor * g * K / E))
        counts = jnp.zeros((G, E), jnp.int32)
        dispatch = jnp.zeros((G, g, E, cap), cfg.dtype)
        combine = jnp.zeros((G, g, E, cap), jnp.float32)
        for j in range(K):  # K is tiny and static — unrolled at trace time
            oh = jax.nn.one_hot(top_idx[..., j], E, dtype=jnp.int32)  # [G,g,E]
            pos = counts[:, None, :] + jnp.cumsum(oh, 1) - oh         # [G,g,E]
            counts = counts + jnp.sum(oh, 1)
            keep = (pos < cap) & (oh > 0)                 # capacity dropping
            slot = jax.nn.one_hot(pos, cap, dtype=jnp.float32)  # [G,g,E,cap]
            sel = slot * keep[..., None].astype(jnp.float32)
            dispatch = dispatch + sel.astype(cfg.dtype)
            combine = combine + sel * top_vals[..., j, None, None]

        def experts(feats, name, in_axis, out_axis):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("expert", in_axis, out_axis)
                ),
                (E, *feats), cfg.param_dtype,
            )

        M = cfg.intermediate_size
        w_gate = experts((H, M), "gate_proj", "embed", "mlp")
        w_up = experts((H, M), "up_proj", "embed", "mlp")
        w_down = experts((M, H), "down_proj", "mlp", "embed")

        ein = xs.dtype
        expert_in = jnp.einsum("gsec,gsh->egch", dispatch, xg)   # all-to-all
        gate = jnp.einsum("egch,ehm->egcm", expert_in, w_gate.astype(ein))
        up = jnp.einsum("egch,ehm->egcm", expert_in, w_up.astype(ein))
        out = jnp.einsum("egcm,emh->egch",
                         nn.silu(gate) * up, w_down.astype(ein))
        ys = jnp.einsum("gsec,egch->gsh", combine.astype(ein), out)
        ys = ys.reshape(S_pad, H)[:S]                            # drop padding

        # Switch aux loss: E * Σ_e (fraction routed to e) * (mean prob of e),
        # over REAL tokens only — pad rows have uniform router probs whose
        # top-1 tie-breaks to expert 0 and would skew the statistics
        valid = (jnp.arange(S_pad) < S).astype(jnp.float32).reshape(G, g)
        n_valid = jnp.maximum(jnp.sum(valid), 1.0)
        top1 = jax.nn.one_hot(top_idx[..., 0], E, dtype=jnp.float32)
        frac = jnp.sum(top1 * valid[..., None], (0, 1)) / n_valid
        mean_prob = jnp.sum(probs * valid[..., None], (0, 1)) / n_valid
        aux = E * jnp.sum(frac * mean_prob)
        self.sow("intermediates", "moe_aux_loss", aux)
        return ys.reshape(B, T, H)


class LlamaBlock(nn.Module):
    """A layer under ``causal_lm.py``'s block protocol: nothing carried
    beside ``x``, nothing counted, a key-value cache taken."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, carry, cos, sin, kv_cache=None, attention_fn=None):
        cfg = self.cfg
        # pin the residual stream to (batch, seq, embed) so SPMD never
        # round-trips activations through a tp-sharded layout in the bwd pass
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        attn_out, new_cache = LlamaAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_norm")(x),
            cos, sin, kv_cache, attention_fn,
        )
        x = x + attn_out
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        ffn = (LlamaMoE(cfg, name="moe") if cfg.num_experts > 0
               else SwiGLU(cfg, cfg.intermediate_size, name="mlp"))
        x = x + ffn(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="post_attn_norm")(x)
        )
        return x, carry, new_cache, None


class LlamaForCausalLM(CausalLM):
    """:class:`CausalLM` over :class:`LlamaBlock`, and the caches its
    attention takes (``fedml_tpu/serving``)."""

    block = LlamaBlock

    def init_kv_caches(self, batch: int, max_len: int):
        cfg = self.cfg
        shape = (batch, cfg.num_key_value_heads, max_len, cfg.head_dim)
        return [
            (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype), 0)
            for _ in range(cfg.num_hidden_layers)
        ]
