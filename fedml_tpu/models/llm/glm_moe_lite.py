"""GLM-4.7-Flash-family causal LM (``model_type: glm4_moe_lite``) — the
fourth model of the LLM path: low-rank latent attention (MLA) in every
layer, ``first_k_dense_replace`` leading layers with a dense SwiGLU, then
layers of gated experts under sigmoid top-k routing beside a shared expert.

Every layer is ``x <- x + attn(RMSNorm_in(x))``, ``x <- x +
ffn(RMSNorm_post(x))``, then a final norm and an untied head. The
configuration takes the keys of the model's public ``config.json`` by their
own names. With ``u`` the normed stream, ``H`` heads, a head of ``nope +
rope`` score lanes and ``v`` value lanes:

attention (no bias anywhere)
1. ``c_q = RMSNorm(u W_qa)`` (the query latent, ``q_lora_rank`` wide);
   ``q = c_q W_qb`` as ``[T, H, nope | rope]``;
2. ``[c_kv | k_r] = u W_kva`` (``kv_lora_rank | rope``); ``c_kv <-
   RMSNorm(c_kv)``; ``[k_nope | v] = c_kv W_kvb`` as ``[T, H, nope | v]``;
3. rotary embedding (half-split pairs (a), ``rope_theta``, no scaling) on
   ``q``'s ``rope`` lanes and on the ONE ``k_r``; ``k = [k_nope | k_r]`` with
   ``k_r`` the same for all ``H`` heads;
4. ``o = softmax(q k^T / sqrt(nope + rope) + causal) v`` through the
   trainer's attention product (the flash kernels), then ``W_o``.

THE LANE ORDER (a). A head's score lanes are laid out ``[rope | nope]``
here, not the published ``[nope | rope]``: :func:`layers.apply_rope` turns
the leading lanes of whole heads in one pass and lets the rest through. A
score is a sum over lanes, so the order changes nothing as long as ``q``
and ``k`` agree; the map from a published checkpoint is a fixed permutation
of ``W_qb``'s columns (``layers.published_lanes``) and the key is assembled
``[k_r | k_nope]``. ``W_kvb``'s columns keep the published order. Steps
1-4 are ``layers.LatentAttention``, which ``longcat_flash`` shares.

dense FFN (layers before ``first_k_dense_replace``)
5. ``(silu(u W_g) * (u W_u)) W_d`` at ``intermediate_size``.

expert FFN (the other layers)
6. ``s = sigmoid(u W_r)`` in float32 at full precision over all
   ``n_routed_experts``; chosen = the ``num_experts_per_tok`` largest of ``s
   + b_sel`` (``noaux_tc``: the selection bias picks, it does not weigh;
   ``n_group = topk_group = 1`` is no group limit); ``w = s[chosen] / (sum
   s[chosen] + 1e-20) * routed_scaling_factor``;
7. ``r = sum_{e chosen} w_e (silu(u G_e) * (u U_e)) D_e``: the assignments
   sorted by expert, one grouped product a matrix
   (``ops/grouped_matmul.py``), none dropped whatever the routing; every
   expert is held here;
8. ``r + (silu(u S_g) * (u S_u)) S_d`` (the shared expert sees every token,
   unweighted (a)).

(a) marks what the public config does not fix. NOT built: multi-token
prediction (``num_nextn_predict_layers``; a module after the last layer
that shares the head) and serving (MLA decodes from a latent cache of
``kv_lora_rank + rope`` numbers a token with the up-projections absorbed
into ``q`` and ``o``, a path of its own: every block raises on a cache).

What a federated round trains: LoRA adapters on the attention's five
projections (``q_a``, ``q_b``, ``kv_a``, ``kv_b``, ``o``; two of them take
a latent as input); both latent norms, the router, the selection bias, the
experts, the shared expert, the dense MLP and the norm scales are frozen
and there is no auxiliary loss. ``glm_moe_lite_reference.py`` is the plain
float32 statement these modules are tested against.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.llm import preset_from_args
from fedml_tpu.models.llm.causal_lm import CausalLM
from fedml_tpu.models.llm.layers import (GatedExperts, LatentAttention,
                                         RMSNorm, SwiGLU, choice_weights,
                                         sigmoid_topk)
# the lane map by the name the glm-4.7-flash configuration's file gives it
from fedml_tpu.models.llm.layers import published_lanes  # noqa: F401
from fedml_tpu.ops import grouped_matmul as gmm


@dataclasses.dataclass(frozen=True)
class GlmMoeLiteConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    intermediate_size: int = 10240
    # latent attention
    num_attention_heads: int = 20
    num_key_value_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    attention_bias: bool = False
    rope_theta: float = 1000000.0
    rope_scaling: Any = None
    max_position_embeddings: int = 202752
    # expert layers: every expert is held here
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # LoRA on the attention's five projections (0 = disabled)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # training knobs, as LlamaConfig's
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    use_flash: bool = True
    # rows of a tile of the grouped product (ops/grouped_matmul.py): an
    # expert sees 256 rows a step at T4096; 64 read 0.8 % ahead of 128 and
    # 3.5 % ahead of 256 end to end on the chip (PERF.md, PR 37)
    moe_block_rows: int = gmm.BLOCK_M

    # what the round's program hands back beside the loss, summed over the
    # round: per expert layer the assignments placed with each expert, the
    # experts that got any, the sorted buffer's row tiles that hold an
    # assignment, the assignments made and the rows that hold one
    # (``dropped`` = their difference)
    STATS = ("moe_tokens", "moe_live", "moe_tiles", "moe_held",
             "moe_placed")
    # nothing trains the router, so no load-balance term joins the loss
    aux_loss_weight = 0.0

    def __post_init__(self):
        unsupported = [
            why for bad, why in (
                (self.tie_word_embeddings, "a tied head"),
                (self.attention_bias, "attention_bias"),
                (self.num_key_value_heads != self.num_attention_heads,
                 "fewer key-value heads than query heads (every head's keys "
                 "and values come from the one latent)"),
                (self.qk_rope_head_dim % 2, "an odd rotary width"),
                (self.rope_scaling is not None, "rope_scaling"),
                (self.hidden_act != "silu", "an activation other than silu"),
                (self.topk_method != "noaux_tc",
                 "a topk_method other than noaux_tc"),
                (self.n_group != 1 or self.topk_group != 1,
                 "group-limited routing (n_group, topk_group != 1)"),
                (not self.norm_topk_prob, "norm_topk_prob off"),
                (self.n_shared_experts != 1, "n_shared_experts != 1"),
                (self.num_experts_per_tok > self.n_routed_experts,
                 "more experts a token than experts"),
                (not 0 <= self.first_k_dense_replace
                 <= self.num_hidden_layers,
                 "more leading dense layers than layers"),
            ) if bad]
        if unsupported:
            raise ValueError(
                f"GlmMoeLiteConfig: not implemented: {unsupported}")

    @property
    def head_dim(self) -> int:
        """Score lanes of a head (the softmax's scale is its root)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rotary_dim(self) -> int:
        return self.qk_rope_head_dim   # the shell's tables

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def round_stats(self) -> tuple:
        return self.STATS if self.expert_layers else ()

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace

    def moe_capacity_rows(self, tokens: int) -> int:
        """Rows of an expert layer's sorted buffer for a step of
        ``tokens``: every choice of every token, each run padded to whole
        tiles."""
        return gmm.padded_rows(tokens * self.num_experts_per_tok,
                               self.n_routed_experts, self.moe_block_rows)

    @property
    def moe_static(self) -> dict:
        """What the ``round/<n>/moe`` event says that no count carries."""
        return {"experts": self.n_routed_experts,
                "top_k": self.num_experts_per_tok}

    def module(self) -> nn.Module:
        return GlmMoeLiteForCausalLM(self)

    # -- presets -----------------------------------------------------------
    @staticmethod
    def glm_4_7_flash(**kw) -> "GlmMoeLiteConfig":
        return GlmMoeLiteConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "GlmMoeLiteConfig":
        """Unit-test scale with the published row's ratios: one leading
        dense layer, rotary lanes a quarter of a head and values as wide as
        the score lanes, latents of 3 : 2, a dense FFN of 5 x hidden, an
        expert three quarters of hidden, top-k a sixteenth of the experts
        rounded up to 2."""
        for k, v in dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=3,
            intermediate_size=160, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
            n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=24, max_position_embeddings=128,
            remat=False, moe_block_rows=8,
        ).items():
            kw.setdefault(k, v)
        return GlmMoeLiteConfig(**kw)

    # what ``model_size`` may say, and the preset it means
    PRESETS = {"tiny": "tiny", "glm_4_7_flash": "glm_4_7_flash",
               "glm_4.7_flash": "glm_4_7_flash", "30b_a3b": "glm_4_7_flash"}
    # the fields a user's yaml may override by name
    YAML_FIELDS = ("lora_rank", "lora_alpha", "num_hidden_layers",
                   "first_k_dense_replace", "max_position_embeddings",
                   "moe_block_rows")

    @classmethod
    def from_args(cls, args: Any,
                  vocab_size: Optional[int] = None) -> "GlmMoeLiteConfig":
        """``model: glm4_moe_lite`` in a user's yaml; ``model_size`` names
        a preset and the listed keys override it."""
        return preset_from_args(cls, args, vocab_size)


class GlmMoeLiteMoE(nn.Module):
    """Steps 6-8; also what the layer counted."""

    cfg: GlmMoeLiteConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, t, hid = u.shape
        e, k = cfg.n_routed_experts, cfg.num_experts_per_tok
        chosen, weights = sigmoid_topk(self, u, e, k,
                                       cfg.routed_scaling_factor)
        with jax.named_scope("moe_dispatch"):
            # every expert is held here: the held range is all of them
            layout = gmm.group_layout(chosen, e, cfg.moe_block_rows)
            w = choice_weights(chosen, weights, 0, e)             # [m, k]
            xs = gmm.dispatch(u.reshape(b * t, hid), layout)
        ys = GatedExperts(cfg, e, name="experts")(xs, layout)
        with jax.named_scope("moe_combine"):
            routed = gmm.combine_weighted(ys, layout, w).astype(
                cfg.dtype).reshape(b, t, hid)
        out = routed + SwiGLU(
            cfg, cfg.moe_intermediate_size * cfg.n_shared_experts,
            name="shared")(u)
        stats = {"moe_tokens": layout.counts,
                 "moe_live": jnp.sum(layout.counts > 0, dtype=jnp.int32),
                 "moe_tiles": layout.live_tiles[0],
                 "moe_held": jnp.sum(layout.held, dtype=jnp.int32),
                 "moe_placed": jnp.sum(layout.valid, dtype=jnp.int32)}
        return out, stats


class GlmMoeLiteBlock(nn.Module):
    """A layer under ``causal_lm.py``'s block protocol; ``dense`` says
    which FFN it has. Nothing is carried beside ``x``; an expert layer
    counts, a dense one does not; no cache is taken."""

    cfg: GlmMoeLiteConfig
    dense = False

    @nn.compact
    def __call__(self, x, carry, cos, sin, cache=None, attention_fn=None):
        cfg = self.cfg
        if cache is not None:
            raise NotImplementedError(
                "glm4_moe_lite: serving is not implemented (MLA decodes from "
                "a latent cache of kv_lora_rank + rope numbers a token with "
                "the up-projections absorbed into q and o); training only")
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + LatentAttention(cfg, name="attn")(
            norm("input_norm")(x), cos, sin, attention_fn)
        u, stats = norm("post_attn_norm")(x), None
        if self.dense:
            y = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(u)
        else:
            y, stats = GlmMoeLiteMoE(cfg, name="moe")(u)
        return x + y, carry, None, stats


class GlmMoeLiteDenseBlock(GlmMoeLiteBlock):
    dense = True


class GlmMoeLiteForCausalLM(CausalLM):
    """:class:`CausalLM` over :class:`GlmMoeLiteBlock`: dense before
    ``first_k_dense_replace``, expert after. Every call sows, per EXPERT
    layer (first first), ``moe_tokens`` ``[layers, experts]``, ``moe_live``,
    ``moe_tiles``, ``moe_held`` and ``moe_placed`` ``[layers]``."""

    block = GlmMoeLiteBlock

    @nn.nowrap
    def layer_block(self, i):
        return GlmMoeLiteDenseBlock if self.cfg.is_dense(i) \
            else GlmMoeLiteBlock

    @nn.nowrap
    def layer_stats(self, stats):
        counted = [s for s in stats if s is not None]
        return {name: jnp.stack([s[name] for s in counted])
                for name in self.cfg.round_stats}
