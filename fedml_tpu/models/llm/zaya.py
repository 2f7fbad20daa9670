"""ZAYA1-family causal LM — the second model of the LLM path.

Forty layers of one kind (``hybrid``): an attention sublayer computed in a
compressed latent with convolutional mixing of q and k (CCA,
arXiv:2510.04476), then an expert sublayer whose router is a small MLP
that hands its state down the stack and sends every token to ONE of 16
experts, none dropped (ZAYA1 report, arXiv:2511.17127). Tied embedding.
The configuration takes the keys of the model's public ``config.json`` by
their own names.

Where the public config fixes a step it is followed; where it does not,
the step is the one written here and marked (a) — the same list is in
``zaya_reference.py`` (the plain float32 statement these modules are
tested against) and in the benchmark configuration's ``assumed``. With
``h = RMSNorm(x)`` before each sublayer and a residual add after it:

Attention, ``Hq`` query / ``Hk`` key-value heads of size ``D``, ``g = Hq/Hk``:

1. ``q~ = h Wq`` ``[T, Hq, D]``, ``k~ = h Wk`` ``[T, Hk, D]``, no bias.
2. mixing on ``c = concat(q~, k~)``: a depthwise causal convolution of
   ``cca_time0`` taps with bias, then one of ``cca_time1`` taps whose tap
   matrices are block-diagonal over the ``Hq + Hk`` heads, with bias (a).
3. ``mq = (q~ + repeat(k~, g)) / 2``, ``mk`` = its mean over each group;
   ``q = qc + mq``, ``k = kc + mk`` (a).
4. ``v = concat(h Wv, shift(h) Wv')``: the first half of the key-value
   heads holds the current token's value, the second the previous
   token's (a).
5. ``q <- sqrt(D) q / |q|``, ``k <- tau_head sqrt(D) k / |k|`` (a).
6. rope (halves convention) on the first ``partial_rotary_factor * D``
   dimensions of each head.
7. causal softmax attention at scale ``1/sqrt(D)`` through the repo's
   flash kernels (``layers.causal_attention``), then ``o_proj``.

Experts:

8. router, in float32 whatever the compute dtype: ``r = h Wd``;
   ``s_l = r + gamma_l * s_{l-1}`` (the state handed to the next layer);
   ``z = W3 gelu(W2 gelu(W1 RMSNorm(s_l)))``; ``p = softmax(z)``;
   ``e = argmax p`` (a).
9. ``y = p_e * Wdown_e (silu(h Wgate_e) * (h Wup_e))``: the step's tokens
   sorted by expert, one grouped matrix product a projection
   (``ops/grouped_matmul.py``), every token computed.

What a federated round trains: LoRA adapters on the five attention
projections (``attn/{q,k,v,v_prev,o}_proj``), nothing else — router and
experts are frozen and there is no auxiliary loss. Serving (a latent
cache, the previous token's ``h`` and the router's state per slot) is not
implemented: the block raises on a cache.
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
from fedml_tpu.models.llm.layers import (GatedExperts, RMSNorm, apply_rope,
                                         causal_attention, lora_dense,
                                         merge_heads)
from fedml_tpu.ops import grouped_matmul as gmm

L2_EPS = 1e-6  # under the root of a head's squared norm (step 5)
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    max_position_embeddings: int = 131072
    # LoRA on the attention projections (0 = disabled)
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
    # round (LLMTrainer.compile_federated_round): tokens per layer and
    # expert, per layer the experts that got any (a step's grouped
    # products read only those experts' matrices) and the row tiles that
    # hold a token (a weight copy hides under tiles / live products)
    round_stats = ("moe_tokens", "moe_live", "moe_tiles")
    # nothing trains the router, so no load-balance term joins the loss
    aux_loss_weight = 0.0

    def __post_init__(self):
        unsupported = [
            why for bad, why in (
                (self.num_experts_per_tok != 1, "num_experts_per_tok != 1"),
                (not self.tie_word_embeddings, "an untied head"),
                (self.attention_bias, "attention_bias"),
                (self.num_attention_heads % self.num_key_value_heads,
                 "query heads not a multiple of key-value heads"),
                (self.num_key_value_heads % 2,
                 "an odd number of key-value heads (half hold the current "
                 "token's value, half the previous token's)"),
                (self.rotary_dim % 2, "an odd rotary width"),
            ) if bad]
        if unsupported:
            raise ValueError(f"ZayaConfig: not implemented: {unsupported}")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def moe_capacity_rows(self, tokens: int) -> int:
        """Rows of a layer's sorted buffer for a step of ``tokens``."""
        return gmm.padded_rows(tokens, self.num_experts, self.moe_block_rows)

    @property
    def moe_static(self) -> dict:
        """What the ``round/<n>/moe`` event says that no count carries."""
        return {"experts": self.num_experts,
                "top_k": self.num_experts_per_tok}

    def module(self) -> nn.Module:
        return ZayaForCausalLM(self)

    # -- presets -----------------------------------------------------------
    @staticmethod
    def zaya1_8b(**kw) -> "ZayaConfig":
        return ZayaConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "ZayaConfig":
        """Unit-test scale that keeps every ratio of the published row:
        8/2 heads, latent = hidden / 2, 16 experts of the hidden width,
        top-1, two taps and two, rope on half of a head, tied head."""
        for k, v in dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2, head_dim=4,
            moe_intermediate_size=64, router_hidden_size=8,
            max_position_embeddings=128, remat=False, moe_block_rows=8,
        ).items():
            kw.setdefault(k, v)
        return ZayaConfig(**kw)

    # what ``model_size`` may say, and the preset it means
    PRESETS = {"tiny": "tiny", "zaya1_8b": "zaya1_8b", "8b": "zaya1_8b"}
    # the fields a user's yaml may override by name
    YAML_FIELDS = ("lora_rank", "lora_alpha", "num_hidden_layers",
                   "max_position_embeddings", "moe_block_rows")

    @classmethod
    def from_args(cls, args: Any, vocab_size: Optional[int] = None) -> "ZayaConfig":
        """``model: zaya`` in a user's yaml; ``model_size`` names a preset
        and the listed keys override it."""
        return preset_from_args(cls, args, vocab_size)


def shift_tokens(x: jax.Array, by: int = 1) -> jax.Array:
    """``y[:, t] = x[:, t - by]``, zeros before ``t = 0``; x ``[B, T, ...]``."""
    if by == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (by, 0)
    return jnp.pad(x[:, :x.shape[1] - by], pad)


def _l2_normalise(x: jax.Array, d: int) -> jax.Array:
    return x * (math.sqrt(d) * jax.lax.rsqrt(
        jnp.sum(x * x, -1, keepdims=True) + L2_EPS))


class ZayaAttention(nn.Module):
    cfg: ZayaConfig

    @nn.compact
    def __call__(self, x, cos, sin, attention_fn=None):
        cfg = self.cfg
        b, t, _ = x.shape
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        heads, group = h + hkv, h // hkv
        q_lat = lora_dense(cfg, h * d, "q_proj", ("embed", "heads"))(x)
        k_lat = lora_dense(cfg, hkv * d, "k_proj", ("embed", "heads"))(x)
        v_now = lora_dense(cfg, hkv // 2 * d, "v_proj", ("embed", "heads"))(x)
        v_prev = lora_dense(cfg, hkv // 2 * d, "v_prev_proj",
                            ("embed", "heads"))(shift_tokens(x))

        taps = nn.initializers.normal(1.0 / cfg.cca_time0)
        conv0 = self.param("conv0_kernel", taps, (cfg.cca_time0, heads * d),
                           jnp.float32)
        bias0 = self.param("conv0_bias", nn.initializers.zeros, (heads * d,),
                           jnp.float32)
        conv1 = self.param(
            "conv1_kernel",
            nn.initializers.normal(1.0 / math.sqrt(cfg.cca_time1 * d)),
            (cfg.cca_time1, heads, d, d), cfg.param_dtype)
        bias1 = self.param("conv1_bias", nn.initializers.zeros, (heads * d,),
                           jnp.float32)
        k_temp = self.param("k_temp", nn.initializers.ones, (hkv,),
                            jnp.float32)

        with jax.named_scope("cca_mix"):
            c = jnp.concatenate([q_lat, k_lat], -1).astype(jnp.float32)
            c1 = bias0 + sum(conv0[j] * shift_tokens(c, j)
                             for j in range(cfg.cca_time0))
            # float32 operands at the default precision: one bfloat16
            # pass with float32 accumulation on the chip, and a product
            # the CPU backend has (it has no bf16 x bf16 -> f32 with the
            # batch axis in the middle)
            c1 = c1.reshape(b, t, heads, d)
            c2 = bias1.reshape(heads, d) + sum(
                jnp.einsum("bthd,hde->bthe", shift_tokens(c1, j),
                           conv1[j].astype(jnp.float32))
                for j in range(cfg.cca_time1))
            q4 = c[..., :h * d].reshape(b, t, h, d)
            k4 = c[..., h * d:].reshape(b, t, hkv, d)
            mq = (q4 + jnp.repeat(k4, group, axis=2)) / 2
            mk = jnp.mean(mq.reshape(b, t, hkv, group, d), axis=3)
            q = _l2_normalise(c2[:, :, :h] + mq, d)
            k = _l2_normalise(c2[:, :, h:] + mk, d) * k_temp[:, None]
        with jax.named_scope("attn_layout"):
            q = q.astype(cfg.dtype).transpose(0, 2, 1, 3)
            k = k.astype(cfg.dtype).transpose(0, 2, 1, 3)
            v = jnp.concatenate(
                [v_now.reshape(b, t, hkv // 2, d),
                 v_prev.reshape(b, t, hkv // 2, d)], axis=2,
            ).transpose(0, 2, 1, 3)
        # whole heads: the tables carry the rotary width (half of a head)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        out = merge_heads(causal_attention(q, k, v, cfg, attention_fn))
        return lora_dense(cfg, cfg.hidden_size, "o_proj", ("heads", "embed"))(
            out)


class ZayaRouter(nn.Module):
    """Step 8, all of it in float32 at full precision: the products are
    megaflops, and a rounded logit is a token sent to another expert."""

    cfg: ZayaConfig

    @nn.compact
    def __call__(self, h, state):
        cfg = self.cfg
        hid, r, e = cfg.hidden_size, cfg.router_hidden_size, cfg.num_experts
        fan_in = lambda n: nn.initializers.normal(1.0 / math.sqrt(n))
        down = self.param(
            "down", nn.with_logical_partitioning(fan_in(hid), ("embed", None)),
            (hid, r), jnp.float32)
        gamma = self.param("gamma", nn.initializers.ones, (r,), jnp.float32)
        scale = self.param("norm_scale", nn.initializers.ones, (r,),
                           jnp.float32)
        w1 = self.param("w1", fan_in(r), (r, r), jnp.float32)
        w2 = self.param("w2", fan_in(r), (r, r), jnp.float32)
        w3 = self.param("w3", fan_in(r), (r, e), jnp.float32)
        with jax.named_scope("router"):
            mm = lambda a, w: jnp.matmul(a, w, precision=HIGHEST)
            state = mm(h.astype(jnp.float32), down) + gamma * state
            n = state * jax.lax.rsqrt(
                jnp.mean(state * state, -1, keepdims=True)
                + cfg.rms_norm_eps) * scale
            z = mm(jax.nn.gelu(mm(jax.nn.gelu(mm(n, w1)), w2)), w3)
            return jax.nn.softmax(z, axis=-1), state


class ZayaMoE(nn.Module):
    cfg: ZayaConfig

    @nn.compact
    def __call__(self, h, state):
        cfg = self.cfg
        b, t, hid = h.shape
        probs, state = ZayaRouter(cfg, name="router_mlp")(h, state)
        with jax.named_scope("moe_dispatch"):
            probs = probs.reshape(b * t, cfg.num_experts)
            expert = jnp.argmax(probs, axis=-1).astype(jnp.int32)
            p_e = jnp.take_along_axis(probs, expert[:, None], axis=-1)
            layout = gmm.group_layout(expert, cfg.num_experts,
                                      cfg.moe_block_rows)
            xs = gmm.dispatch(h.reshape(b * t, hid), layout)
        # the experts' SwiGLU over rows sorted by expert (step 9)
        ys = GatedExperts(cfg, cfg.num_experts, name="experts")(xs, layout)
        with jax.named_scope("moe_combine"):
            y = gmm.combine(ys, layout).astype(jnp.float32) * p_e
        return (y.astype(cfg.dtype).reshape(b, t, hid), state,
                (layout.counts, layout.live_tiles[0]))


class ZayaBlock(nn.Module):
    """A layer under ``causal_lm.py``'s block protocol: the router's state
    is carried down the stack, the tokens each expert was sent are
    counted, and no cache is taken."""

    cfg: ZayaConfig

    @nn.compact
    def __call__(self, x, state, cos, sin, cache=None, attention_fn=None):
        cfg = self.cfg
        if cache is not None:
            raise NotImplementedError(
                "zaya: serving is not implemented (a slot would hold a "
                "latent cache, the previous token's h and the router's "
                "state); training only")
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        x = x + ZayaAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_norm")(x),
            cos, sin, attention_fn)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        y, state, counts = ZayaMoE(cfg, name="moe")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="post_attn_norm")(x),
            state)
        return x + y, state, None, counts


class ZayaForCausalLM(CausalLM):
    """:class:`CausalLM` over :class:`ZayaBlock`.

    What flows from layer to layer is the pair ``(x, s)``: the residual
    stream and the router's state. Every call sows ``moe_tokens``, the
    ``[layers, experts]`` count of tokens each expert was sent (they sum
    to ``B * T`` in every layer: nothing is dropped), ``moe_live``, per
    layer the number of experts that were sent any, and ``moe_tiles``, per
    layer the row tiles of the sorted buffer that hold a token.
    """

    block = ZayaBlock

    @nn.nowrap
    def init_carry(self, tokens):
        return jnp.zeros((*tokens.shape, self.cfg.router_hidden_size),
                         jnp.float32)

    @nn.nowrap
    def layer_stats(self, stats):
        counts, tiles = (jnp.stack(each) for each in zip(*stats))
        return {"moe_tokens": counts,
                "moe_live": jnp.sum(counts > 0, axis=1, dtype=jnp.int32),
                "moe_tiles": tiles}
