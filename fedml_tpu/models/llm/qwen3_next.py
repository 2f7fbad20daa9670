"""Qwen3-Next-family causal LM (``model_type: qwen3_next``) — the fifth
model of the LLM path: Gated DeltaNet linear attention in three layers of
every four, gated softmax attention in the fourth, and routed experts with
a gated shared expert in every layer.

Layer ``i`` is full attention where ``(i + 1) % full_attention_interval ==
0``, else Gated DeltaNet. Every layer is ``x <- x + mixer(N_in(x))``, ``x <-
x + moe(N_post(x))``, then a final norm and an untied head; ``N`` is the
ZERO-CENTRED RMS norm ``x * rsqrt(mean(x^2) + eps) * (1 + w)``
(``layers.ZeroCenteredRMSNorm``; the shell's final norm is the plain one,
whose scale holds ``1 + w``). The configuration takes the keys of the
model's public ``config.json`` by their own names. With ``u`` the normed
stream:

Gated DeltaNet (``hk`` key heads of ``dk``, ``hv`` value heads of ``dv``,
``r = hv / hk``; no bias anywhere)
1. ``[q | k | v | z] = u W_qkvz``, the columns grouped a KEY head ``h`` as
   ``[q_h dk | k_h dk | v_{rh} .. v_{rh + r - 1} r dv | z_{rh} .. r dv]``;
2. ``[b | a] = u W_ba``, grouped a key head as ``[b r | a r]``;
3. ``[q | k | v] <- silu(conv([q | k | v]))``: a causal depthwise
   convolution of ``linear_conv_kernel_dim`` taps over the ``2 hk dk + hv
   dv`` channels, no bias;
4. ``q, k <- x * rsqrt(sum x^2 + 1e-6)`` over ``dk`` (a), ``q <- q / sqrt(dk)``;
   key head ``h`` serves value heads ``rh .. rh + r - 1``;
5. ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``, float32,
   a value head;
6. the gated delta rule a value head, the state ``[dk, dv]`` zero before
   the first token: ``S' = exp(g_t) S``, ``delta = beta_t (v_t - S'^T
   k_t)``, ``S = S' + k_t delta^T``, ``o_t = S^T q_t`` — computed in chunks
   of ``chunk_size`` by ``ops/gated_delta.py``;
7. ``y = RMSNorm_dv(o) * w_n * silu(z)`` a value head (``w_n`` is NOT
   zero-centred), then ``y W_out``.

Gated attention (``H`` heads and ``Hkv`` key-value heads of ``D``)
8. ``u W_q`` holds ``[q_h D | gate_h D]`` a head; ``k = u W_k``, ``v = u W_v``;
9. ``q, k <- N_D(q), N_D(k)`` a head (zero-centred);
10. rotary embedding (``rope_theta``) on the leading ``D x
    partial_rotary_factor`` lanes of each head, lane ``j`` paired with lane
    ``j + rotary / 2`` (the published order: nothing is permuted);
11. ``o = softmax(q k^T / sqrt(D) + causal) v`` through the trainer's
    attention product (the flash kernels); ``o <- o * sigmoid(gate)``; ``o
    W_o``.

Experts (``num_experts`` HELD here from ``held_experts_first`` on, of
``num_experts_total`` the router scores)
12. ``p = softmax(u W_r)`` in float32 at full precision over all experts;
    chosen = the ``num_experts_per_tok`` largest; ``w = p[chosen] / sum
    p[chosen]`` (``norm_topk_prob``; off is refused), no scaling and no
    selection bias;
13. ``r = sum_{e chosen AND held} w_e (silu(u G_e) * (u U_e)) D_e``: the
    held assignments sorted by expert, one grouped product a matrix
    (``ops/grouped_matmul.py``), none dropped whatever the routing; what the
    experts held elsewhere would add is left out;
14. ``r + sigmoid(u w_sg) * (silu(u S_g) * (u S_u)) S_d``: the shared expert,
    scaled by a per-token gate.

(a) marks what the public config does not fix. NOT built: multi-token
prediction (a module after the last layer; the round's loss is next-token
cross-entropy) and serving (a slot would hold a convolution tail and a
``[hv, dk, dv]`` state beside keys and values: every block raises on a
cache).

What a federated round trains: LoRA adapters on the attention layers'
``q/k/v/o_proj`` and on every Gated DeltaNet layer's ``in_proj_qkvz`` and
``out_proj``; ``in_proj_ba``, the convolution, ``A_log``, ``dt_bias``, every
norm, the router, the experts, the shared expert and its gate are frozen,
and there is no auxiliary loss. ``qwen3_next_reference.py`` is the plain
float32 statement these modules are tested against.
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
from fedml_tpu.models.llm.layers import (GatedExperts, SwiGLU,
                                         ZeroCenteredRMSNorm, apply_rope,
                                         causal_attention, choice_weights,
                                         lora_dense, merge_heads,
                                         softmax_topk)
from fedml_tpu.ops import gated_delta as gd
from fedml_tpu.ops import grouped_matmul as gmm
from fedml_tpu.telemetry import get_tracer

L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # every layer's FFN is the expert layer; ``intermediate_size`` is the
    # dense FFN's width the row gives, which no layer then has
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    intermediate_size: int = 5120
    # gated attention layers
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rope_scaling: Any = None
    attention_bias: bool = False
    use_sliding_window: bool = False
    max_position_embeddings: int = 262144
    # Gated DeltaNet layers
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # tokens a chunk of ops/gated_delta.py (the program's, not the row's)
    chunk_size: int = 128
    # expert layers: ``num_experts`` are HELD here, from
    # ``held_experts_first`` on, of ``num_experts_total`` the router scores
    # (0 = all of them are held)
    num_experts: int = 512
    num_experts_total: int = 0
    held_experts_first: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    # LoRA on the attention and Gated DeltaNet projections (0 = disabled)
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
    # round: per expert layer the assignments placed with each held expert,
    # the held experts that got any, the sorted buffer's row tiles that
    # hold an assignment, the assignments whose expert is held here and the
    # rows that hold one (``dropped`` = their difference)
    STATS = ("moe_tokens", "moe_live", "moe_tiles", "moe_held",
             "moe_placed")
    round_stats = STATS
    # nothing trains the router, so no load-balance term joins the loss
    aux_loss_weight = 0.0

    def __post_init__(self):
        unsupported = [
            why for bad, why in (
                (self.tie_word_embeddings, "a tied head"),
                (self.attention_bias, "attention_bias"),
                (self.use_sliding_window, "a sliding window"),
                (self.rope_scaling is not None, "rope_scaling"),
                (self.hidden_act != "silu", "an activation other than silu"),
                (self.decoder_sparse_step != 1 or self.mlp_only_layers,
                 "a layer with a dense FFN (decoder_sparse_step != 1 or "
                 "mlp_only_layers)"),
                (self.num_attention_heads % self.num_key_value_heads,
                 "query heads not a multiple of key-value heads"),
                (self.linear_num_value_heads % self.linear_num_key_heads,
                 "linear value heads not a multiple of key heads"),
                (self.rotary_dim % 2 or not 0 < self.rotary_dim
                 <= self.head_dim or self.rotary_dim
                 != self.head_dim * self.partial_rotary_factor,
                 "a rotary width that is odd, not whole or not in the head"),
                (not self.norm_topk_prob, "norm_topk_prob off"),
                (self.num_experts_per_tok > self.experts_total,
                 "more experts a token than experts"),
                (self.held_experts_first + self.num_experts
                 > self.experts_total, "a held range past the last expert"),
            ) if bad]
        if unsupported:
            raise ValueError(
                f"Qwen3NextConfig: not implemented: {unsupported}")

    @property
    def experts_total(self) -> int:
        return self.num_experts_total or self.num_experts

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    def is_linear(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval != 0

    def moe_capacity_rows(self, tokens: int) -> int:
        """Rows of an expert layer's sorted buffer for a step of
        ``tokens``: every choice of every token held here, each run padded
        to whole tiles."""
        return gmm.padded_rows(
            tokens * min(self.num_experts_per_tok, self.num_experts),
            self.num_experts, self.moe_block_rows)

    @property
    def moe_static(self) -> dict:
        """What the ``round/<n>/moe`` event says that no count carries."""
        return {"experts": self.experts_total,
                "top_k": self.num_experts_per_tok}

    def module(self) -> nn.Module:
        return Qwen3NextForCausalLM(self)

    # -- presets -----------------------------------------------------------
    @staticmethod
    def qwen3_next_80b_a3b(**kw) -> "Qwen3NextConfig":
        return Qwen3NextConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        """Unit-test scale with the published row's ratios: one whole
        period (three Gated DeltaNet layers, then attention), 8 query heads
        a key-value head with a quarter of a head's lanes rotary, two value
        heads a key head, a quarter of the experts held, experts and the
        shared one a quarter of hidden."""
        for k, v in dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=8, num_key_value_heads=1, head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16, chunk_size=8,
            num_experts=4, num_experts_total=16, num_experts_per_tok=3,
            moe_intermediate_size=8, shared_expert_intermediate_size=8,
            intermediate_size=80, max_position_embeddings=128, remat=False,
            moe_block_rows=8,
        ).items():
            kw.setdefault(k, v)
        return Qwen3NextConfig(**kw)

    # what ``model_size`` may say, and the preset it means
    PRESETS = {"tiny": "tiny", "qwen3_next_80b_a3b": "qwen3_next_80b_a3b",
               "qwen3_next_80b_a3b_instruct": "qwen3_next_80b_a3b",
               "80b_a3b": "qwen3_next_80b_a3b"}
    # the fields a user's yaml may override by name
    YAML_FIELDS = ("lora_rank", "lora_alpha", "num_hidden_layers",
                   "num_experts", "num_experts_total", "held_experts_first",
                   "max_position_embeddings", "moe_block_rows")

    @classmethod
    def from_args(cls, args: Any,
                  vocab_size: Optional[int] = None) -> "Qwen3NextConfig":
        """``model: qwen3_next`` in a user's yaml; ``model_size`` names a
        preset and the listed keys override it."""
        return preset_from_args(cls, args, vocab_size)


def _causal_conv(x, kernel):
    """Depthwise causal convolution over the tokens of ``x [B, T, C]``
    with ``kernel [taps, C]``, float32."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j] * padded[:, j:j + t] for j in range(taps))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


class Qwen3NextGatedDeltaNet(nn.Module):
    """Steps 1-7."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, t, _ = u.shape
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv, taps = (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                        cfg.linear_conv_kernel_dim)
        r, f32, chunk = hv // hk, jnp.float32, cfg.chunk_size
        qkvz = lora_dense(cfg, hk * (2 * dk + 2 * r * dv), "in_proj_qkvz",
                          ("embed", "mlp"))(u)
        ba = lora_dense(cfg, 2 * hv, "in_proj_ba", ("embed", "mlp"),
                        adapters=False)(u)
        conv = self.param("conv_kernel",
                          nn.initializers.normal(1.0 / math.sqrt(taps)),
                          (taps, cfg.conv_dim), f32)
        # dt = softplus(dt_bias) = 0.01 and a = -1 at init
        dt_bias = self.param("dt_bias", nn.initializers.constant(-4.6), (hv,),
                             f32)
        a_log = self.param("A_log", nn.initializers.zeros, (hv,), f32)
        scale = self.param("gate_norm_scale", nn.initializers.ones, (dv,),
                           f32)
        pad = -t % chunk
        get_tracer().event(
            "gdn/plan", rows=b * t, key_heads=hk, value_heads=hv,
            key_dim=dk, value_dim=dv,
            **gd.plan(b, t + pad, hv, dk, dv, chunk),
            dtype=jnp.dtype(cfg.dtype).name)

        with jax.named_scope("gdn_conv"):
            parts = qkvz.reshape(b, t, hk, 2 * dk + 2 * r * dv)
            q, k, v = (parts[..., lo:hi].reshape(b, t, -1).astype(f32)
                       for lo, hi in ((0, dk), (dk, 2 * dk),
                                      (2 * dk, 2 * dk + r * dv)))
            z = parts[..., 2 * dk + r * dv:].reshape(b, t, hv, dv)
            # one convolution over [q | k | v]: each part with its taps
            edges = (0, cfg.key_dim, 2 * cfg.key_dim, cfg.conv_dim)
            q, k, v = (nn.silu(_causal_conv(x, conv[:, lo:hi]))
                       for x, lo, hi in zip((q, k, v), edges, edges[1:]))
        with jax.named_scope("gdn_gates"):
            q = _l2(q.reshape(b, t, hk, dk)) * dk ** -0.5
            k = _l2(k.reshape(b, t, hk, dk))
            q, k = (jnp.repeat(x, r, axis=2).astype(cfg.dtype) for x in (q, k))
            v = v.reshape(b, t, hv, dv).astype(cfg.dtype)
            ba = ba.reshape(b, t, hk, 2 * r).astype(f32)
            beta = jax.nn.sigmoid(ba[..., :r].reshape(b, t, hv))
            g = -jnp.exp(a_log) * nn.softplus(ba[..., r:].reshape(b, t, hv)
                                               + dt_bias)
            if pad:   # tokens that neither decay nor write the state
                grow = lambda x: jnp.pad(
                    x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                q, k, v, g, beta = (grow(x) for x in (q, k, v, g, beta))
        o = gd.gated_delta(q, k, v, g, beta, chunk)[:, :t]
        with jax.named_scope("gdn_gate_norm"):
            o = o.astype(f32)
            normed = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                       + cfg.rms_norm_eps)
            y = (normed * scale * nn.silu(z.astype(f32))).astype(
                cfg.dtype).reshape(b, t, hv * dv)
        return lora_dense(cfg, cfg.hidden_size, "out_proj",
                          ("mlp", "embed"))(y)


class Qwen3NextAttention(nn.Module):
    """Steps 8-11."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, u, cos, sin, attention_fn=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        q_gate = lora_dense(cfg, h * 2 * d, "q_proj", ("embed", "heads"))(u)
        k = lora_dense(cfg, hkv * d, "k_proj", ("embed", "heads"))(u)
        v = lora_dense(cfg, hkv * d, "v_proj", ("embed", "heads"))(u)
        with jax.named_scope("attn_layout"):
            q_gate = q_gate.reshape(b, t, h, 2 * d)
            q, gate = q_gate[..., :d], q_gate[..., d:]
        norm = lambda name: ZeroCenteredRMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                                name=name)
        q = norm("q_norm")(q)
        k = norm("k_norm")(k.reshape(b, t, hkv, d))
        with jax.named_scope("attn_layout"):
            q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
            v = v.reshape(b, t, hkv, d).transpose(0, 2, 1, 3)
        # the leading rotary_dim lanes of every head, the rest as they are
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        out = merge_heads(causal_attention(q, k, v, cfg, attention_fn))
        with jax.named_scope("attn_gate"):
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.reshape(b, t, h * d).astype(jnp.float32))).astype(
                    cfg.dtype)
        return lora_dense(cfg, cfg.hidden_size, "o_proj", ("heads", "embed"))(
            out)


class Qwen3NextMoE(nn.Module):
    """Steps 12-14; also what the layer counted."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, t, hid = u.shape
        total, held, first, k = (cfg.experts_total, cfg.num_experts,
                                 cfg.held_experts_first,
                                 cfg.num_experts_per_tok)
        chosen, weights = softmax_topk(self, u, total, k)
        with jax.named_scope("moe_dispatch"):
            layout = gmm.group_layout(chosen, held, cfg.moe_block_rows, first)
            w_held = choice_weights(chosen, weights, first, held)   # [m, k]
            xs = gmm.dispatch(u.reshape(b * t, hid), layout)
        ys = GatedExperts(cfg, held, name="experts")(xs, layout)
        with jax.named_scope("moe_combine"):
            routed = gmm.combine_weighted(ys, layout, w_held).astype(
                cfg.dtype).reshape(b, t, hid)
        shared = SwiGLU(cfg, cfg.shared_expert_intermediate_size,
                        name="shared")(u)
        gate = lora_dense(cfg, 1, "shared_gate", ("embed", None),
                          adapters=False)(u)
        out = routed + (jax.nn.sigmoid(gate.astype(jnp.float32))
                        * shared.astype(jnp.float32)).astype(cfg.dtype)
        in_range = (chosen >= first) & (chosen < first + held)
        stats = {"moe_tokens": layout.counts,
                 "moe_live": jnp.sum(layout.counts > 0, dtype=jnp.int32),
                 "moe_tiles": layout.live_tiles[0],
                 "moe_held": jnp.sum(in_range, dtype=jnp.int32),
                 "moe_placed": jnp.sum(layout.valid, dtype=jnp.int32)}
        return out, stats


class Qwen3NextBlock(nn.Module):
    """A layer under ``causal_lm.py``'s block protocol; ``linear`` says
    which mixer it has. Nothing is carried beside ``x``; every layer
    counts its experts; no cache is taken."""

    cfg: Qwen3NextConfig
    linear = True

    @nn.compact
    def __call__(self, x, carry, cos, sin, cache=None, attention_fn=None):
        cfg = self.cfg
        if cache is not None:
            raise NotImplementedError(
                "qwen3_next: serving is not implemented (a slot would hold "
                "a convolution tail and a delta-rule state beside keys and "
                "values); training only")
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        norm = lambda name: ZeroCenteredRMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                                name=name)
        u = norm("input_norm")(x)
        if self.linear:
            y = Qwen3NextGatedDeltaNet(cfg, name="linear_attn")(u)
        else:
            y = Qwen3NextAttention(cfg, name="attn")(u, cos, sin,
                                                     attention_fn)
        x = x + y
        y, stats = Qwen3NextMoE(cfg, name="moe")(norm("post_attn_norm")(x))
        return x + y, carry, None, stats


class Qwen3NextAttentionBlock(Qwen3NextBlock):
    linear = False


class Qwen3NextForCausalLM(CausalLM):
    """:class:`CausalLM` over :class:`Qwen3NextBlock`: Gated DeltaNet, or
    attention every ``full_attention_interval``-th layer. Every call sows,
    per layer (first first), ``moe_tokens`` ``[layers, held experts]``,
    ``moe_live``, ``moe_tiles``, ``moe_held`` and ``moe_placed``
    ``[layers]``."""

    block = Qwen3NextBlock

    @nn.nowrap
    def layer_block(self, i):
        return Qwen3NextBlock if self.cfg.is_linear(i) \
            else Qwen3NextAttentionBlock

    @nn.nowrap
    def layer_stats(self, stats):
        return {name: jnp.stack([s[name] for s in stats])
                for name in self.cfg.round_stats}
