"""Nemotron-H-family causal LM (``model_type: nemotron_h``) — the third
model of the LLM path, and the first whose layers are of several kinds.

A stack in which letter ``i`` of ``hybrid_override_pattern`` says what
layer ``i`` is; every layer is ``x <- x + mixer_i(RMSNorm(x))``, one mixer
a layer, then a final norm and an untied head. The configuration takes the
keys of the model's public ``config.json`` by their own names. With
``u = RMSNorm(x)``:

``M`` — Mamba-2 (arXiv:2405.21060), ``H`` heads of size ``P``, ``G`` groups
of state size ``N``, ``d = H P``:

1. ``[z | xBC | dt] = u W_in`` (``d | d + 2 G N | H``), no bias;
2. ``xBC <- silu(conv(xBC) + b)``: a depthwise causal convolution of
   ``conv_kernel`` taps over the tokens;
3. split ``x [T, H, P]``, ``B [T, G, N]``, ``C [T, G, N]``;
   ``dt <- softplus(dt + dt_bias)``, ``a = -exp(A_log)`` a head;
4. ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = C_t h_t + D
   x_t`` (state ``[P, N]`` a head, zero before the first token), computed
   in chunks of ``chunk_size`` by ``ops/ssd.py``;
5. ``y <- GroupRMSNorm(y * silu(z)) * w``: the gate first, then an RMS
   norm over each of the ``G`` groups of ``d / G`` channels (a);
6. ``y W_out``.

``*`` — attention: ``q, k, v = u W_q, u W_k, u W_v``, no bias and NO rotary
embedding (the family's attention applies none; the positions are the
Mamba layers' to carry), causal softmax at ``1/sqrt(D)`` through the
trainer's attention product (the flash kernels), then ``W_o``.

``E`` — experts in a latent (``LatentMoE``), routed over ALL
``n_routed_experts_total`` experts, of which this layer holds
``n_routed_experts`` from ``held_experts_first`` on:

7. ``s = sigmoid(u W_g)`` in float32; chosen = the ``num_experts_per_tok``
   largest of ``s + b_sel`` (the selection bias picks, it does not weigh);
   ``w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``;
8. ``l = u W_fc1`` (hidden -> latent); ``r = sum_{e chosen AND held} w_e
   relu(l U_e)^2 V_e``: the held assignments sorted by expert, one grouped
   product a matrix (``ops/grouped_matmul.py``; relu^2 is handed to the
   second product, which applies it on its live tiles), none dropped
   whatever the routing; what the experts held elsewhere would add is left out — on one
   chip there is no exchange and nothing stands in for one;
9. ``r W_fc2 + relu(u S_up)^2 S_down`` (latent -> hidden; the shared
   expert sees every token).

(a) marks what the public config does not fix. NOT built: multi-token
prediction (``num_nextn_predict_layers``; a module after the last layer),
serving (a slot would hold a convolution tail and a state beside keys and
values: every block raises on a cache), ``time_step_limit`` (not in the
row: ``dt`` is not clamped).

What a federated round trains: LoRA adapters on the attention layers'
``q/k/v/o_proj`` and on every Mamba layer's ``in_proj`` / ``out_proj``;
router, selection bias, experts, latent projections, shared expert,
convolution, ``A_log``, ``D``, ``dt_bias`` and norm scales are frozen and
there is no auxiliary loss. ``nemotron_h_reference.py`` is the plain
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
from fedml_tpu.models.llm.layers import (RMSNorm, causal_attention,
                                         choice_weights, expert_stack,
                                         lora_dense, merge_heads,
                                         sigmoid_topk)
from fedml_tpu.ops import grouped_matmul as gmm
from fedml_tpu.ops.ssd import ssd

KINDS = "M*E"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    # attention layers
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    # Mamba-2 layers
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # expert layers: ``n_routed_experts`` are HELD here, from
    # ``held_experts_first`` on, of ``n_routed_experts_total`` the router
    # scores (0 = all of them are held)
    n_routed_experts: int = 512
    n_routed_experts_total: int = 0
    held_experts_first: int = 0
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    mlp_bias: bool = False
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    rope_theta: float = 10000.0   # in the row; no layer applies it
    max_position_embeddings: int = 262144
    # LoRA on the attention and Mamba projections (0 = disabled)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # training knobs, as LlamaConfig's
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    use_flash: bool = True
    # rows of a tile of the grouped product (ops/grouped_matmul.py)
    moe_block_rows: int = 128

    # what the round's program hands back beside the loss, summed over the
    # round: per expert layer the assignments placed with each held expert,
    # the held experts that got any, the sorted buffer's row tiles that
    # hold an assignment, the assignments whose expert is held here and the
    # rows that hold one (``dropped`` = their difference)
    STATS = ("moe_tokens", "moe_live", "moe_tiles", "moe_held",
             "moe_placed")
    # nothing trains the router, so no load-balance term joins the loss
    aux_loss_weight = 0.0

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        unsupported = [
            why for bad, why in (
                (len(pattern) < self.num_hidden_layers,
                 "fewer pattern letters than layers"),
                (set(pattern) - set(KINDS), "a layer kind other than M, *, E"),
                (self.tie_word_embeddings, "a tied head"),
                (self.attention_bias or self.mamba_proj_bias or self.mlp_bias,
                 "a bias on a projection"),
                (not self.use_conv_bias, "a convolution without its bias"),
                (self.num_attention_heads % self.num_key_value_heads,
                 "query heads not a multiple of key-value heads"),
                (self.mamba_num_heads % self.n_groups,
                 "Mamba heads not a multiple of n_groups"),
                (self.n_group != 1 or self.topk_group != 1,
                 "group-limited routing (n_group, topk_group != 1)"),
                (not self.norm_topk_prob, "norm_topk_prob off"),
                (self.n_shared_experts != 1, "n_shared_experts != 1"),
                (self.num_experts_per_tok > self.experts_total,
                 "more experts a token than experts"),
                (self.held_experts_first + self.n_routed_experts
                 > self.experts_total, "a held range past the last expert"),
            ) if bad]
        if unsupported:
            raise ValueError(
                f"NemotronHConfig: not implemented: {unsupported}")

    @property
    def round_stats(self) -> tuple:
        layers = self.hybrid_override_pattern[:self.num_hidden_layers]
        return self.STATS if "E" in layers else ()

    @property
    def experts_total(self) -> int:
        return self.n_routed_experts_total or self.n_routed_experts

    @property
    def rotary_dim(self) -> int:
        return self.head_dim   # the shell's tables; no layer reads them

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def layer_kind(self, i: int) -> str:
        return self.hybrid_override_pattern[i]

    def moe_capacity_rows(self, tokens: int) -> int:
        """Rows of an expert layer's sorted buffer for a step of
        ``tokens``: every choice of every token held here, each run padded
        to whole tiles."""
        return gmm.padded_rows(
            tokens * min(self.num_experts_per_tok, self.n_routed_experts),
            self.n_routed_experts, self.moe_block_rows)

    @property
    def moe_static(self) -> dict:
        """What the ``round/<n>/moe`` event says that no count carries."""
        return {"experts": self.experts_total,
                "top_k": self.num_experts_per_tok}

    def module(self) -> nn.Module:
        return NemotronHForCausalLM(self)

    # -- presets -----------------------------------------------------------
    @staticmethod
    def nemotron3_super_120b(**kw) -> "NemotronHConfig":
        return NemotronHConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "NemotronHConfig":
        """Unit-test scale with the published row's letters and ratios: the
        first period ``MEMEMEM*EME``, 16 query heads a key-value head, 16
        Mamba heads a group, inner = 2 x hidden, a quarter of the experts
        held, top-k a twenty-third of them rounded up, shared expert twice
        an expert's width."""
        for k, v in dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=11,
            hybrid_override_pattern="MEMEMEM*EME",
            num_attention_heads=16, num_key_value_heads=1, head_dim=4,
            mamba_num_heads=16, mamba_head_dim=4, n_groups=1,
            ssm_state_size=8, chunk_size=8,
            n_routed_experts=4, n_routed_experts_total=16,
            num_experts_per_tok=3, moe_intermediate_size=24,
            moe_latent_size=16, moe_shared_expert_intermediate_size=48,
            max_position_embeddings=128, remat=False, moe_block_rows=8,
        ).items():
            kw.setdefault(k, v)
        return NemotronHConfig(**kw)

    # what ``model_size`` may say, and the preset it means
    PRESETS = {"tiny": "tiny", "nemotron3_super_120b": "nemotron3_super_120b",
               "120b": "nemotron3_super_120b"}
    # the fields a user's yaml may override by name
    YAML_FIELDS = ("lora_rank", "lora_alpha", "num_hidden_layers",
                   "hybrid_override_pattern", "n_routed_experts",
                   "n_routed_experts_total", "held_experts_first",
                   "max_position_embeddings", "moe_block_rows", "chunk_size")

    @classmethod
    def from_args(cls, args: Any,
                  vocab_size: Optional[int] = None) -> "NemotronHConfig":
        """``model: nemotron_h`` in a user's yaml; ``model_size`` names a
        preset and the listed keys override it."""
        return preset_from_args(cls, args, vocab_size)


def _relu2(x):
    return jnp.square(nn.relu(x))


# relu^2 as the grouped product takes it (ops/grouped_matmul.py): applied,
# and differentiated, inside the kernels on the tiles that hold rows
RELU2 = gmm.Activation(
    "relu2", value=_relu2, derivative=lambda x: 2.0 * nn.relu(x))


class NemotronHMamba(nn.Module):
    """Steps 1-6."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, t, _ = u.shape
        h, p, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                      cfg.ssm_state_size)
        d, taps, f32 = cfg.mamba_inner, cfg.conv_kernel, jnp.float32
        zxbcdt = lora_dense(cfg, d + cfg.conv_dim + h, "in_proj",
                            ("embed", "mlp"))(u)
        conv = self.param("conv_kernel",
                          nn.initializers.normal(1.0 / math.sqrt(taps)),
                          (taps, cfg.conv_dim), f32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (cfg.conv_dim,), f32)
        # the constructor's defaults: dt = softplus(-4.6) = 0.01, a = -1
        dt_bias = self.param("dt_bias", nn.initializers.constant(-4.6), (h,),
                             f32)
        a_log = self.param("A_log", nn.initializers.zeros, (h,), f32)
        skip = self.param("D", nn.initializers.ones, (h,), f32)
        scale = self.param("gate_norm_scale", nn.initializers.ones, (d,), f32)

        with jax.named_scope("ssm_conv"):
            z = zxbcdt[..., :d]
            xbc = zxbcdt[..., d:d + cfg.conv_dim].astype(f32)
            padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
            mixed = conv_bias + sum(
                conv[j] * padded[:, j:j + t] for j in range(taps))
            xbc = nn.silu(mixed).astype(cfg.dtype)
            dt = nn.softplus(zxbcdt[..., d + cfg.conv_dim:].astype(f32)
                             + dt_bias)
            x = xbc[..., :d].reshape(b, t, h, p)
            bm = xbc[..., d:d + g * n].reshape(b, t, g, n)
            cm = xbc[..., d + g * n:].reshape(b, t, g, n)
        # the scan's backward computes its inside again (ops/ssd.py): a
        # layer keeps none of the [H, Q, Q] arrays
        y = ssd(x, dt, -jnp.exp(a_log), bm, cm, cfg.chunk_size)
        with jax.named_scope("ssm_gate_norm"):
            y = y.astype(f32) + skip[:, None] * x.astype(f32)
            gated = (y.reshape(b, t, d) * nn.silu(z.astype(f32))).reshape(
                b, t, g, d // g)
            normed = gated * jax.lax.rsqrt(
                jnp.mean(gated * gated, -1, keepdims=True) + cfg.rms_norm_eps)
            y = (normed.reshape(b, t, d) * scale).astype(cfg.dtype)
        return lora_dense(cfg, cfg.hidden_size, "out_proj",
                          ("mlp", "embed"))(y)


class NemotronHAttention(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u, attention_fn=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        q = lora_dense(cfg, h * d, "q_proj", ("embed", "heads"))(u)
        k = lora_dense(cfg, hkv * d, "k_proj", ("embed", "heads"))(u)
        v = lora_dense(cfg, hkv * d, "v_proj", ("embed", "heads"))(u)
        with jax.named_scope("attn_layout"):
            q = q.reshape(b, t, h, d).transpose(0, 2, 1, 3)
            k = k.reshape(b, t, hkv, d).transpose(0, 2, 1, 3)
            v = v.reshape(b, t, hkv, d).transpose(0, 2, 1, 3)
        out = merge_heads(causal_attention(q, k, v, cfg, attention_fn))
        return lora_dense(cfg, cfg.hidden_size, "o_proj", ("heads", "embed"))(
            out)


class NemotronHExperts(nn.Module):
    """``relu(l U_e)^2 V_e`` over rows already sorted by held expert."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, xs, layout):
        cfg = self.cfg
        lat, mid, e = (cfg.moe_latent_size, cfg.moe_intermediate_size,
                       cfg.n_routed_experts)

        experts = lambda name, shape, *axes: expert_stack(
            self, cfg, e, name, shape, *axes)
        product = lambda a, w, **kw: gmm.grouped_matmul(
            a, w, layout, block_m=cfg.moe_block_rows, **kw)
        up = product(xs, experts("up_proj", (lat, mid), "embed", "mlp"))
        # relu^2 is the second product's: no pass over the padded buffer
        return product(up, experts("down_proj", (mid, lat), "mlp", "embed"),
                       activation=RELU2)


class NemotronHShared(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        up = lora_dense(cfg, cfg.moe_shared_expert_intermediate_size,
                        "up_proj", ("embed", "mlp"), adapters=False)(u)
        return lora_dense(cfg, cfg.hidden_size, "down_proj",
                          ("mlp", "embed"), adapters=False)(_relu2(up))


class NemotronHMoE(nn.Module):
    """Steps 7-9; also what the layer counted."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, t, hid = u.shape
        total, held, first, k = (cfg.experts_total, cfg.n_routed_experts,
                                 cfg.held_experts_first,
                                 cfg.num_experts_per_tok)
        chosen, weights = sigmoid_topk(self, u, total, k,
                                       cfg.routed_scaling_factor)
        with jax.named_scope("moe_dispatch"):
            layout = gmm.group_layout(chosen, held, cfg.moe_block_rows, first)
            w_held = choice_weights(chosen, weights, first, held)   # [m, k]
        latent = lora_dense(cfg, cfg.moe_latent_size, "latent_in",
                            ("embed", "mlp"), adapters=False)(u)
        with jax.named_scope("moe_dispatch"):
            xs = gmm.dispatch(latent.reshape(b * t, cfg.moe_latent_size),
                              layout)
        ys = NemotronHExperts(cfg, name="experts")(xs, layout)
        with jax.named_scope("moe_combine"):
            mine = gmm.combine(ys, layout).astype(jnp.float32)     # [m, k, lat]
            routed = jnp.sum(mine * w_held[..., None], axis=1).astype(
                cfg.dtype).reshape(b, t, cfg.moe_latent_size)
        out = lora_dense(cfg, hid, "latent_out", ("mlp", "embed"),
                         adapters=False)(routed)
        out = out + NemotronHShared(cfg, name="shared")(u)
        in_range = (chosen >= first) & (chosen < first + held)
        stats = {"moe_tokens": layout.counts,
                 "moe_live": jnp.sum(layout.counts > 0, dtype=jnp.int32),
                 "moe_tiles": layout.live_tiles[0],
                 "moe_held": jnp.sum(in_range, dtype=jnp.int32),
                 "moe_placed": jnp.sum(layout.valid, dtype=jnp.int32)}
        return out, stats


class NemotronHBlock(nn.Module):
    """A layer under ``causal_lm.py``'s block protocol; ``kind`` is its
    letter of the pattern. Nothing is carried beside ``x``; an expert layer
    counts, the others do not; no cache is taken."""

    cfg: NemotronHConfig
    kind = ""

    @nn.compact
    def __call__(self, x, carry, cos, sin, cache=None, attention_fn=None):
        cfg = self.cfg
        if cache is not None:
            raise NotImplementedError(
                "nemotron_h: serving is not implemented (a slot would hold "
                "a convolution tail and an SSM state beside keys and "
                "values); training only")
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        u = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_norm")(x)
        stats = None
        if self.kind == "M":
            y = NemotronHMamba(cfg, name="mamba")(u)
        elif self.kind == "*":
            y = NemotronHAttention(cfg, name="attn")(u, attention_fn)
        else:
            y, stats = NemotronHMoE(cfg, name="moe")(u)
        return x + y, carry, None, stats


class NemotronHMambaBlock(NemotronHBlock):
    kind = "M"


class NemotronHAttentionBlock(NemotronHBlock):
    kind = "*"


class NemotronHMoEBlock(NemotronHBlock):
    kind = "E"


BLOCKS = {block.kind: block for block in (
    NemotronHMambaBlock, NemotronHAttentionBlock, NemotronHMoEBlock)}


class NemotronHForCausalLM(CausalLM):
    """:class:`CausalLM` over :class:`NemotronHBlock`, the kind of layer
    ``i`` read from the configuration's pattern. Every call sows, per
    EXPERT layer (first first), ``moe_tokens`` ``[layers, held experts]``,
    ``moe_live``, ``moe_tiles``, ``moe_held`` and ``moe_placed``
    ``[layers]``."""

    block = NemotronHBlock

    @nn.nowrap
    def layer_block(self, i):
        return BLOCKS[self.cfg.layer_kind(i)]

    @nn.nowrap
    def layer_stats(self, stats):
        counted = [s for s in stats if s is not None]
        return {name: jnp.stack([s[name] for s in counted])
                for name in self.cfg.round_stats}
