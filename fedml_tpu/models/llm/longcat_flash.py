"""LongCat-Flash-family causal LM (``model_type: longcat_flash``) — the sixth
model of the LLM path: every layer is a DOUBLE layer of two latent
attentions and two dense FFNs, beside one shortcut-connected expert branch
(ScMoE) whose input is the first FFN's and whose output joins the stream
after the second; the router scores FFN experts and identity
("zero-computation") experts alike.

The configuration takes the keys of the model's public ``config.json`` by
their own names (``num_layers`` counts double layers). With ``x`` the
residual stream and ``N`` an RMS norm of its own each:

a double layer
1. for ``i`` in 0, 1: ``x <- x + MLA_i(N_in_i(x))``; ``u_i = N_post_i(x)``;
   where ``i == 0`` the expert branch reads ``u_0``: ``m = ScMoE(u_0)``;
   ``x <- x + FFN_i(u_i)``, a SwiGLU at ``ffn_hidden_size``;
2. ``x <- x + m``: the branch joins after the SECOND FFN (the shortcut).

latent attention ``MLA_i`` (``layers.LatentAttention``; 64 heads of 128
nope + 64 rope score lanes and 128 value lanes)
3. ``c_q = N(u W_qa)``; ``q = sqrt(hidden / q_lora_rank) (c_q W_qb)``
   (``mla_scale_q_lora``: 2 on all of ``q``) (a);
4. ``[c_kv | k_r] = u W_kva``; ``c_kv <- sqrt(hidden / kv_lora_rank)
   N(c_kv)`` (``mla_scale_kv_lora``: the normed latent, not ``k_r``) (a);
   ``[k_nope | v] = c_kv W_kvb`` a head;
5. rotary embedding (``rope_theta``, no scaling) on ``q``'s rope lanes and
   on the ONE ``k_r`` every head's key carries; ``o = softmax(q k^T /
   sqrt(nope + rope) + causal) v`` through the trainer's attention product
   (the flash kernels, at a value head narrower than the score lanes);
   ``o W_o``. A head lies ``[rope | nope]`` here (a fixed permutation of
   ``W_qb``'s columns from the published ``[nope | rope]``,
   ``layers.published_lanes``), and rotary pairs are the two halves of the
   rope lanes (the published code may pair neighbours: a permutation of
   ``W_qb``'s and ``W_kva``'s rope columns) (a).

the expert branch ``ScMoE`` (``n_routed_experts`` HELD here from
``held_experts_first`` on, of ``n_routed_experts_total`` FFN experts,
beside ``zero_expert_num`` identity experts)
6. ``p = softmax(u W_r)`` in float32 at full precision over the FFN
   experts and the identity experts (512 + 256 = 768 outputs; the router
   has no bias of its own (a)); chosen = the ``moe_topk`` largest of ``p +
   b`` (``e_score_correction_bias``: it picks, it does not weigh); ``w_e =
   routed_scaling_factor p_e`` with NO renormalisation over the chosen (a);
7. ``r = sum_{e chosen, e < total, e held} w_e (silu(u G_e) * (u U_e))
   D_e``: the held assignments sorted by expert, one grouped product a
   matrix (``ops/grouped_matmul.py``), none dropped; what the experts held
   elsewhere would add is left out (no exchange on one chip, nothing
   stands in for one);
8. ``r + (sum_{e chosen, e >= total} w_e) u``: an identity expert adds its
   weight times its input (a) and takes no row, no dispatch and no
   product; every chip computes them for its own tokens.

Then the shell's final norm and an untied head (a).

(a) marks what the public config does not fix. NOT built: multi-token
prediction (the config declares none), the PID controller that moves ``b``
during pre-training (a frozen buffer in a LoRA round), and serving (MLA
decodes from a latent cache: every block raises on a cache).

What a federated round trains: LoRA adapters on the five projections of
both attentions (``q_a``, ``q_b``, ``kv_a``, ``kv_b``, ``o``); the norms,
the router and ``b``, the experts and both dense FFNs are frozen and there
is no auxiliary loss. ``longcat_flash_reference.py`` is the plain float32
statement these modules are tested against.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.llm import preset_from_args
from fedml_tpu.models.llm.causal_lm import CausalLM
from fedml_tpu.models.llm.layers import (GatedExperts, LatentAttention,
                                         RMSNorm, SwiGLU, choice_weights,
                                         softmax_topk)
from fedml_tpu.ops import grouped_matmul as gmm


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    # double layers, each two attentions and two dense FFNs
    num_layers: int = 28
    ffn_hidden_size: int = 12288
    # latent attention
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    attention_method: str = "MLA"
    attention_bias: bool = False
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 131072
    # the expert branch: ``n_routed_experts`` FFN experts are HELD here,
    # from ``held_experts_first`` on, of ``n_routed_experts_total`` the
    # router scores (0 = all of them are held), beside ``zero_expert_num``
    # identity experts
    n_routed_experts: int = 512
    n_routed_experts_total: int = 0
    held_experts_first: int = 0
    expert_ffn_hidden_size: int = 2048
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # LoRA on both attentions' five projections (0 = disabled)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # training knobs, as LlamaConfig's
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    use_flash: bool = True
    # rows of a tile of the grouped product (ops/grouped_matmul.py)
    moe_block_rows: int = gmm.BLOCK_M

    # what the round's program hands back beside the loss, summed over the
    # round: per double layer the assignments placed with each held
    # expert, the held experts that got any, the sorted buffer's row tiles
    # that hold an assignment, the assignments to a held expert, the rows
    # that hold one, and the assignments to an identity expert
    STATS = ("moe_tokens", "moe_live", "moe_tiles", "moe_held",
             "moe_placed", "moe_zero")
    round_stats = STATS
    # nothing trains the router, so no load-balance term joins the loss
    aux_loss_weight = 0.0

    def __post_init__(self):
        unsupported = [
            why for bad, why in (
                (self.tie_word_embeddings, "a tied head"),
                (self.attention_bias, "attention_bias"),
                (self.attention_method != "MLA",
                 "an attention_method other than MLA"),
                (self.zero_expert_type != "identity",
                 "a zero_expert_type other than identity"),
                (not (self.mla_scale_q_lora and self.mla_scale_kv_lora),
                 "latent attention without its latents' scales"),
                (self.qk_rope_head_dim % 2, "an odd rotary width"),
                (self.moe_topk > self.experts_total + self.zero_expert_num,
                 "more experts a token than experts"),
                (not 0 <= self.held_experts_first
                 <= self.experts_total - self.n_routed_experts,
                 "a held range outside the FFN experts"),
            ) if bad]
        if unsupported:
            raise ValueError(
                f"LongcatFlashConfig: not implemented: {unsupported}")

    @property
    def num_hidden_layers(self) -> int:
        """Blocks of the shell: one a double layer."""
        return self.num_layers

    @property
    def head_dim(self) -> int:
        """Score lanes of a head (the softmax's scale is its root)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rotary_dim(self) -> int:
        return self.qk_rope_head_dim   # the shell's tables

    @property
    def moe_intermediate_size(self) -> int:
        return self.expert_ffn_hidden_size   # what GatedExperts reads

    @property
    def experts_total(self) -> int:
        """FFN experts the router scores."""
        return self.n_routed_experts_total or self.n_routed_experts

    @property
    def mla_scales(self) -> tuple:
        """``(on q, on the normed kv latent)``: the root of hidden over
        each latent (``mla_scale_q_lora``, ``mla_scale_kv_lora``)."""
        return (math.sqrt(self.hidden_size / self.q_lora_rank),
                math.sqrt(self.hidden_size / self.kv_lora_rank))

    def moe_capacity_rows(self, tokens: int) -> int:
        """Rows of a double layer's sorted buffer for a step of
        ``tokens``: every choice of every token that could be held, each
        run padded to whole tiles (``gmm.group_layout``'s)."""
        return gmm.padded_rows(
            tokens * min(self.moe_topk, self.n_routed_experts),
            self.n_routed_experts, self.moe_block_rows)

    @property
    def moe_static(self) -> dict:
        """What the ``round/<n>/moe`` event says that no count carries:
        the router's outputs, FFN and identity experts alike."""
        return {"experts": self.experts_total + self.zero_expert_num,
                "top_k": self.moe_topk}

    def module(self) -> nn.Module:
        return LongcatFlashForCausalLM(self)

    # -- presets -----------------------------------------------------------
    @staticmethod
    def longcat_flash_chat(**kw) -> "LongcatFlashConfig":
        return LongcatFlashConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "LongcatFlashConfig":
        """Unit-test scale with the published row's ratios where they fit:
        rope lanes a third of the score lanes and value lanes two thirds,
        latents of 3 : 1, dense FFNs of 2 x hidden, identity experts half
        as many as FFN experts."""
        for k, v in dict(
            vocab_size=256, hidden_size=32, num_layers=2,
            ffn_hidden_size=64, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, n_routed_experts=8, expert_ffn_hidden_size=16,
            moe_topk=3, zero_expert_num=4, max_position_embeddings=128,
            remat=False, moe_block_rows=8,
        ).items():
            kw.setdefault(k, v)
        return LongcatFlashConfig(**kw)

    # what ``model_size`` may say, and the preset it means
    PRESETS = {"tiny": "tiny", "longcat_flash_chat": "longcat_flash_chat",
               "longcat_flash": "longcat_flash_chat",
               "560b_a27b": "longcat_flash_chat"}
    # the fields a user's yaml may override by name
    YAML_FIELDS = ("lora_rank", "lora_alpha", "num_layers",
                   "n_routed_experts", "n_routed_experts_total",
                   "held_experts_first", "max_position_embeddings",
                   "moe_block_rows")

    @classmethod
    def from_args(cls, args: Any,
                  vocab_size: Optional[int] = None) -> "LongcatFlashConfig":
        """``model: longcat_flash`` in a user's yaml; ``model_size`` names
        a preset and the listed keys override it."""
        return preset_from_args(cls, args, vocab_size)


class LongcatFlashMoE(nn.Module):
    """Steps 6-8; also what the branch counted."""

    cfg: LongcatFlashConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, t, hid = u.shape
        total, held, first = (cfg.experts_total, cfg.n_routed_experts,
                              cfg.held_experts_first)
        chosen, weights = softmax_topk(
            self, u, total + cfg.zero_expert_num, cfg.moe_topk, bias=True,
            renormalize=False, scale=cfg.routed_scaling_factor)
        with jax.named_scope("moe_dispatch"):
            # an identity choice (>= total) is never in the held range, so
            # it takes no row
            layout = gmm.group_layout(chosen, held, cfg.moe_block_rows, first)
            w_held = choice_weights(chosen, weights, first, held)   # [m, k]
            xs = gmm.dispatch(u.reshape(b * t, hid), layout)
        ys = GatedExperts(cfg, held, name="experts")(xs, layout)
        with jax.named_scope("moe_combine"):
            # weighed as gathered: no [m, k, hid] array (1.2 GB a step in
            # float32 at k 12, hidden 6144)
            routed = gmm.combine_weighted(ys, layout, w_held)   # [m, hid]
        with jax.named_scope("zero_experts"):
            w_zero = jnp.sum(weights[:, total:], axis=-1, keepdims=True)
            out = (routed + w_zero * u.reshape(b * t, hid).astype(jnp.float32)
                   ).astype(cfg.dtype).reshape(b, t, hid)
        in_range = (chosen >= first) & (chosen < first + held)
        stats = {"moe_tokens": layout.counts,
                 "moe_live": jnp.sum(layout.counts > 0, dtype=jnp.int32),
                 "moe_tiles": layout.live_tiles[0],
                 "moe_held": jnp.sum(in_range, dtype=jnp.int32),
                 "moe_placed": jnp.sum(layout.valid, dtype=jnp.int32),
                 "moe_zero": jnp.sum(chosen >= total, dtype=jnp.int32)}
        return out, stats


class LongcatFlashBlock(nn.Module):
    """A double layer under ``causal_lm.py``'s block protocol (steps 1-2).
    Nothing is carried beside ``x``; the expert branch counts; no cache is
    taken."""

    cfg: LongcatFlashConfig

    @nn.compact
    def __call__(self, x, carry, cos, sin, cache=None, attention_fn=None):
        cfg = self.cfg
        if cache is not None:
            raise NotImplementedError(
                "longcat_flash: serving is not implemented (MLA decodes from "
                "a latent cache of kv_lora_rank + rope numbers a token with "
                "the up-projections absorbed into q and o); training only")
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        q_scale, kv_scale = cfg.mla_scales
        for i in (0, 1):
            x = x + LatentAttention(cfg, q_scale, kv_scale, name=f"attn_{i}")(
                norm(f"input_norm_{i}")(x), cos, sin, attention_fn)
            u = norm(f"post_attn_norm_{i}")(x)
            if i == 0:
                # the shortcut: the branch reads the first FFN's input ...
                shortcut, stats = LongcatFlashMoE(cfg, name="moe")(u)
            x = x + SwiGLU(cfg, cfg.ffn_hidden_size, name=f"mlp_{i}")(u)
        # ... and joins after the second FFN
        return x + shortcut, carry, None, stats


class LongcatFlashForCausalLM(CausalLM):
    """:class:`CausalLM` over :class:`LongcatFlashBlock`, one block a
    double layer. Every call sows, per double layer (first first),
    ``moe_tokens`` ``[layers, held experts]``, ``moe_live``, ``moe_tiles``,
    ``moe_held``, ``moe_placed`` and ``moe_zero`` ``[layers]``."""

    block = LongcatFlashBlock

    @nn.nowrap
    def layer_stats(self, stats):
        return {name: jnp.stack([s[name] for s in stats])
                for name in self.cfg.round_stats}
