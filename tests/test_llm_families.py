"""The seam between ``models/llm/causal_lm.py::CausalLM`` and a model
family: a toy third family written here alone runs the fused round; the
families' default call and ``head_inputs=True`` agree; ``from_args`` gives
every preset and every overridable field the value written out below. On
the CPU at tiny widths; a timing here is never a speed."""
import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.llm import config_from_args
from fedml_tpu.models.llm.causal_lm import CausalLM
from fedml_tpu.models.llm.layers import (RMSNorm, apply_rope,
                                         causal_attention, lora_dense,
                                         merge_heads)
from fedml_tpu.models.llm.glm_moe_lite import GlmMoeLiteConfig
from fedml_tpu.models.llm.llama import LlamaConfig
from fedml_tpu.models.llm.zaya import ZayaConfig
from fedml_tpu.train.llm.sharding import unbox
from fedml_tpu.train.llm.trainer import LLMTrainer, extract_lora, merge_lora


# -- (i) a third family: a configuration and a block, nothing else --------
@dataclasses.dataclass(frozen=True)
class ToyConfig:
    vocab_size: int = 64
    hidden_size: int = 32
    num_hidden_layers: int = 3
    num_heads: int = 2
    rotary_dim: int = 16
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    lora_rank: int = 4
    lora_alpha: float = 8.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    use_flash: bool = False

    round_stats = ("toy_positive",)
    aux_loss_weight = 0.0

    def module(self):
        return ToyForCausalLM(self)


class ToyBlock(nn.Module):
    """One-projection attention (q = k = v) whose output joins a running
    mean of the stream that is carried down the stack; counts the
    positive entries of what it adds."""

    cfg: ToyConfig

    @nn.compact
    def __call__(self, x, mean, cos, sin, cache=None, attention_fn=None):
        cfg = self.cfg
        b, t, hid = x.shape
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        q = lora_dense(cfg, hid, "qkv_proj", ("embed", "heads"))(h)
        q = q.reshape(b, t, cfg.num_heads, -1).transpose(0, 2, 1, 3)
        q = apply_rope(q, cos, sin)
        out = merge_heads(causal_attention(q, q, q, cfg, attention_fn))
        y = lora_dense(cfg, hid, "o_proj", ("heads", "embed"))(out) + mean
        return (x + y, 0.5 * (mean + x), None,
                jnp.sum(y > 0, dtype=jnp.int32))


class ToyForCausalLM(CausalLM):
    block = ToyBlock

    @nn.nowrap
    def init_carry(self, tokens):
        return jnp.zeros((*tokens.shape, self.cfg.hidden_size), self.cfg.dtype)

    @nn.nowrap
    def layer_stats(self, stats):
        return {"toy_positive": jnp.stack(stats)}


class _Args:
    max_seq_length = 16
    per_device_batch_size = 4
    gradient_accumulation_steps = 1
    learning_rate = 1e-2
    mesh_dp, mesh_fsdp, mesh_tp, mesh_sp = 1, 4, 2, 1
    random_seed = 0


def _copy(t):
    return jax.tree.map(jnp.copy, t)


@pytest.mark.parametrize("remat_policy", ["full", "none"])
def test_a_third_family_runs_the_fused_round(remat_policy):
    """``LLMTrainer.compile_federated_round`` over a family no file under
    ``fedml_tpu/`` knows, against the host loop it replaces (float32 both,
    so what is left is the order of FedAvg's sum); the carried state and
    the per-layer count pass through the shell's ``nn.remat``, and the
    count leaves the round as its fifth output, summed over clients and
    steps."""
    from fedml_tpu.ml.aggregator.agg_operator import FedMLAggOperator

    cfg = ToyConfig(remat_policy=remat_policy)
    tr = LLMTrainer(cfg, _Args())
    tr.init(seed=0)
    assert type(tr.model).__name__ == "ToyForCausalLM"
    n_clients, steps, batch, seq = 3, 2, 16, 16
    assert batch == tr.batch_size
    rng = np.random.default_rng(0)
    xs = rng.integers(0, cfg.vocab_size,
                      size=(n_clients, steps, batch, seq)).astype(np.int32)
    ys = ((xs + 1) % cfg.vocab_size).astype(np.int32)
    ms = np.ones((n_clients, steps, batch), np.float32)
    w = np.asarray([1.0, 2.0, 3.0], np.float32)
    p0, o0 = _copy(tr.params), _copy(tr.opt_state)
    g0 = _copy(extract_lora(tr.params))
    assert len(g0) == 2 * 2 * cfg.num_hidden_layers

    p, o, uploads = _copy(p0), _copy(o0), []
    for c in range(n_clients):
        p = merge_lora(p, _copy(g0))
        for s in range(steps):
            p, o, _ = tr._train_step(
                p, o, jnp.asarray(xs[c, s][None]), jnp.asarray(ys[c, s][None]),
                jnp.asarray(ms[c, s][None]))
        uploads.append(_copy(extract_lora(p)))
    host_global = FedMLAggOperator.agg_with_weights(uploads, list(w))

    fed = tr.compile_federated_round(n_clients, steps)
    _, _, fused_global, loss, counts = fed(p0, o0, _copy(g0), xs, ys, ms, w)
    assert np.isfinite(float(loss))
    assert set(fused_global) == set(host_global)
    for k, v in host_global.items():
        np.testing.assert_allclose(fused_global[k], v, rtol=2e-4, atol=2e-5,
                                   err_msg=k)
        assert float(jnp.abs(v - g0[k]).max()) > 0, k  # and it moved
    positive = np.asarray(counts["toy_positive"])
    assert positive.shape == (cfg.num_hidden_layers,)
    entries = n_clients * steps * batch * seq * cfg.hidden_size
    assert (0 < positive).all() and (positive < entries).all()


# -- (ii) the default call and head_inputs=True, every family -------------
def _llama(tied):
    cfg = LlamaConfig.tiny(use_flash=False, tie_word_embeddings=tied,
                           dtype=jnp.float32)
    toks = jax.random.randint(jax.random.key(0), (2, 16), 0, cfg.vocab_size)
    return cfg, unbox(cfg.module().init(jax.random.key(0), toks)), toks


def _zaya():
    from tests.test_zaya import seeded

    cfg = ZayaConfig.tiny(lora_rank=4, dtype=jnp.float32,
                          param_dtype=jnp.float32)
    return (cfg, *seeded(cfg))


def _glm():
    from tests.test_glm_moe_lite import seeded

    cfg = GlmMoeLiteConfig.tiny(lora_rank=4, dtype=jnp.float32,
                                param_dtype=jnp.float32)
    return (cfg, *seeded(cfg))


def _qwen3_next():
    from fedml_tpu.models.llm.qwen3_next import Qwen3NextConfig
    from tests.test_qwen3_next import seeded

    cfg = Qwen3NextConfig.tiny(lora_rank=4, dtype=jnp.float32,
                               param_dtype=jnp.float32)
    return (cfg, *seeded(cfg))


def _longcat_flash():
    from fedml_tpu.models.llm.longcat_flash import LongcatFlashConfig
    from tests.test_longcat_flash import seeded

    cfg = LongcatFlashConfig.tiny(lora_rank=4, dtype=jnp.float32,
                                  param_dtype=jnp.float32)
    return (cfg, *seeded(cfg))


@pytest.mark.parametrize("family, rtol", [
    (lambda: _llama(True), 0), (lambda: _llama(False), 0), (_zaya, 0),
    # one entry of 8,192 of the embedding's gradient is 17.8 here, and the
    # two float32 sums of it lie 2.1e-5 apart: 1.2e-6 of it (about ten
    # float32 epsilons), just over the absolute bound that entries of
    # order 1 keep. Only this family gets a relative term.
    (_glm, 2e-6),
    # here entries of the embedding's gradient of order 3 lie up to 5.5e-4
    # apart (1.8e-4 of them): the two float32 heads' roundings go back
    # through the Gated DeltaNet layers, whose RMS norm of each head's
    # small output magnifies them (tests/test_qwen3_next.py)
    (_qwen3_next, 3e-4),
    (_longcat_flash, 0),
], ids=["llama-tied", "llama-untied", "zaya", "glm4_moe_lite", "qwen3_next",
        "longcat_flash"])
def test_head_inputs_give_the_loss_the_default_calls_logits_give(family,
                                                                 rtol):
    """The default call returns the head's product in the compute type,
    then float32 (one line of the shell, whatever the family);
    ``head_inputs=True`` stops before that product, and the loss made from
    it is ``optax``'s over those logits, targets of -1 left out — with
    every leaf differentiated, the head's own among them (full
    fine-tuning)."""
    import optax

    from fedml_tpu.models.llm.head_loss import head_loss

    cfg, params, toks = family()
    tied = cfg.tie_word_embeddings
    model = cfg.module()
    y = jnp.roll(toks, -1, axis=1).at[:, -1].set(-1)
    w = (y >= 0).astype(jnp.float32)

    out = model.apply(params, toks, head_inputs=True)
    head = params["params"]["embed_tokens" if tied else "lm_head"]
    assert out.tied == tied and out.head is head
    logits = model.apply(params, toks)
    before = out.hidden @ (head.T if tied else head)
    np.testing.assert_array_equal(logits, before.astype(jnp.float32))
    total, correct = head_loss(out, y, w)
    assert float(correct) == float(
        jnp.sum((jnp.argmax(logits, -1) == y) * w))

    def ours(p):
        return head_loss(model.apply(p, toks, head_inputs=True), y, w)[0]

    def theirs(p):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, toks), jnp.maximum(y, 0))
        return jnp.sum(ce * w)

    (loss, grads), (want, want_grads) = (
        jax.value_and_grad(f)(params) for f in (ours, theirs))
    np.testing.assert_allclose(total, want, rtol=1e-6)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0])
                       for g in (grads, want_grads))
    assert flat.keys() == want_flat.keys()
    for path, g in flat.items():
        np.testing.assert_allclose(g, want_flat[path], atol=2e-5, rtol=rtol,
                                   err_msg=str(path))
    assert float(jnp.abs(grads["params"]["embed_tokens"]).max()) > 1e-3


# -- (iii) from_args: every preset, every overridable field ---------------
F32, BF16 = jnp.float32, jnp.bfloat16
_LLAMA_COMMON = dict(
    rms_norm_eps=1e-5, tie_word_embeddings=False, lora_rank=0,
    lora_alpha=16.0, num_experts=0, num_experts_per_tok=2,
    moe_capacity_factor=1.25, moe_group_size=1024, moe_aux_weight=0.01,
    dtype=BF16, param_dtype=F32, remat_policy="full", use_flash=True)
_LLAMA_TINY = dict(
    _LLAMA_COMMON, vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=128, rope_theta=10000.0, remat=False)
_LLAMA_7B = dict(
    _LLAMA_COMMON, vocab_size=32000, hidden_size=4096,
    intermediate_size=11008, num_hidden_layers=32, num_attention_heads=32,
    num_key_value_heads=32, max_position_embeddings=4096,
    rope_theta=10000.0, remat=True)
_LLAMA_13B = dict(
    _LLAMA_7B, hidden_size=5120, intermediate_size=13824,
    num_hidden_layers=40, num_attention_heads=40, num_key_value_heads=40)
_LLAMA_8B = dict(
    _LLAMA_7B, vocab_size=128256, intermediate_size=14336,
    num_key_value_heads=8, rope_theta=500000.0)
_ZAYA_8B = dict(
    vocab_size=262272, hidden_size=2048, num_hidden_layers=40,
    num_attention_heads=8, num_key_value_heads=2, head_dim=128, cca_time0=2,
    cca_time1=2, partial_rotary_factor=0.5, rope_theta=5000000.0,
    num_experts=16, num_experts_per_tok=1, moe_intermediate_size=2048,
    router_hidden_size=256, rms_norm_eps=1e-5, tie_word_embeddings=True,
    attention_bias=False, max_position_embeddings=131072, lora_rank=0,
    lora_alpha=16.0, dtype=BF16, param_dtype=F32, remat=True,
    remat_policy="full", use_flash=True, moe_block_rows=64)
_ZAYA_TINY = dict(
    _ZAYA_8B, vocab_size=256, hidden_size=64, num_hidden_layers=2,
    head_dim=4, moe_intermediate_size=64, router_hidden_size=8,
    max_position_embeddings=128, remat=False, moe_block_rows=8)

_GLM_FLASH = dict(
    vocab_size=154880, hidden_size=2048, num_hidden_layers=47,
    first_k_dense_replace=1, intermediate_size=10240, num_attention_heads=20,
    num_key_value_heads=20, q_lora_rank=768, kv_lora_rank=512,
    qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
    attention_bias=False, rope_theta=1000000.0, rope_scaling=None,
    max_position_embeddings=202752, n_routed_experts=64,
    num_experts_per_tok=4, moe_intermediate_size=1536, n_shared_experts=1,
    routed_scaling_factor=1.8, norm_topk_prob=True, topk_method="noaux_tc",
    n_group=1, topk_group=1, hidden_act="silu", rms_norm_eps=1e-5,
    tie_word_embeddings=False, lora_rank=0, lora_alpha=16.0, dtype=BF16,
    param_dtype=F32, remat=True, remat_policy="full", use_flash=True,
    moe_block_rows=64)
_GLM_TINY = dict(
    _GLM_FLASH, vocab_size=256, hidden_size=32, num_hidden_layers=3,
    intermediate_size=160, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
    v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=24, max_position_embeddings=128, remat=False,
    moe_block_rows=8)

PRESETS = [
    ("glm4_moe_lite", None, _GLM_TINY), ("glm4_moe_lite", "tiny", _GLM_TINY),
    ("glm4_moe_lite", "glm_4_7_flash", _GLM_FLASH),
    ("glm4_moe_lite", "GLM-4.7-Flash", _GLM_FLASH),
    ("glm4_moe_lite", "30b_a3b", _GLM_FLASH),
    ("llama", None, _LLAMA_TINY), ("llama", "tiny", _LLAMA_TINY),
    ("llama", "llama2_7b", _LLAMA_7B), ("llama", "7b", _LLAMA_7B),
    ("llama", "Llama2-7B", _LLAMA_7B),
    ("llama", "llama2_13b", _LLAMA_13B), ("llama", "13b", _LLAMA_13B),
    ("llama", "llama3_8b", _LLAMA_8B), ("llama", "8b", _LLAMA_8B),
    ("zaya", None, _ZAYA_TINY), ("zaya", "tiny", _ZAYA_TINY),
    ("zaya", "zaya1_8b", _ZAYA_8B), ("zaya", "8b", _ZAYA_8B),
    ("zaya", "ZAYA1-8B", _ZAYA_8B),
]
CLASSES = {"llama": LlamaConfig, "zaya": ZayaConfig,
           "glm4_moe_lite": GlmMoeLiteConfig}
TINY = {"llama": _LLAMA_TINY, "zaya": _ZAYA_TINY, "glm4_moe_lite": _GLM_TINY}
# a preset at published widths a family, by a name its PRESETS know
BIG = {"llama": ("8b", _LLAMA_8B), "zaya": ("8b", _ZAYA_8B),
       "glm4_moe_lite": ("30b_a3b", _GLM_FLASH)}


def _args(model, **kw):
    return type("Args", (), dict(model=model, **kw))()


@pytest.mark.parametrize(
    "model, size, want", PRESETS,
    ids=[f"{m}-{s}" for m, s, _ in PRESETS])
def test_from_args_gives_each_preset_its_values(model, size, want):
    kw = {} if size is None else {"model_size": size}
    cfg = config_from_args(_args(model, **kw))
    assert type(cfg) is CLASSES[model] and cfg == CLASSES[model](**want)
    assert CLASSES[model].from_args(_args(model, **kw)) == cfg
    # ``model_name`` is read where ``model_size`` says nothing
    if size is not None:
        assert config_from_args(_args(model, model_name=size)) == cfg
    # only the tiny preset takes the data's vocabulary, 32 rows at least
    big = config_from_args(_args(model, **kw), vocab_size=99)
    small = config_from_args(_args(model, **kw), vocab_size=5)
    if want is TINY[model]:
        assert (big.vocab_size, small.vocab_size) == (99, 32)
        assert dataclasses.replace(big, vocab_size=256) == cfg
    else:
        assert big == cfg and small == cfg


# field, what a yaml may say, what the configuration then holds: converted
# to the type of the field's default
OVERRIDES = [
    ("llama", "lora_rank", "8", 8), ("llama", "lora_alpha", 32, 32.0),
    ("llama", "max_position_embeddings", "512", 512),
    ("llama", "num_hidden_layers", 3.0, 3), ("llama", "hidden_size", "96", 96),
    ("llama", "num_experts", "4", 4), ("llama", "num_experts_per_tok", 1, 1),
    ("llama", "moe_capacity_factor", "2", 2.0),
    ("zaya", "lora_rank", "8", 8), ("zaya", "lora_alpha", 32, 32.0),
    ("zaya", "num_hidden_layers", 3.0, 3),
    ("zaya", "max_position_embeddings", "512", 512),
    ("zaya", "moe_block_rows", "16", 16),
    ("glm4_moe_lite", "lora_rank", "8", 8),
    ("glm4_moe_lite", "num_hidden_layers", 6.0, 6),
    ("glm4_moe_lite", "first_k_dense_replace", "2", 2),
    ("glm4_moe_lite", "moe_block_rows", "128", 128),
    ("glm4_moe_lite", "use_flash_attention", 0, ("use_flash", False)),
    ("glm4_moe_lite", "remat_policy", "dots", "dots"),
    ("glm4_moe_lite", "base_params_bf16", True, ("param_dtype", BF16)),
    # the three switches every family reads under the same names
    ("llama", "use_flash_attention", 0, ("use_flash", False)),
    ("zaya", "use_flash_attention", 0, ("use_flash", False)),
    ("llama", "remat_policy", "dots", "dots"),
    ("zaya", "remat_policy", "dots", "dots"),
    ("llama", "base_params_bf16", True, ("param_dtype", BF16)),
    ("zaya", "base_params_bf16", True, ("param_dtype", BF16)),
]


@pytest.mark.parametrize(
    "model, key, said, want", OVERRIDES,
    ids=[f"{m}-{k}" for m, k, _, _ in OVERRIDES])
def test_from_args_overrides_a_field_and_no_other(model, key, said, want):
    field, value = want if isinstance(want, tuple) else (key, want)
    for size, base in (("tiny", TINY[model]), BIG[model]):
        cfg = config_from_args(_args(model, model_size=size, **{key: said}))
        assert type(getattr(cfg, field)) is type(value)
        assert cfg == CLASSES[model](**{**base, field: value})
    # a key the family does not list is not read
    other = "moe_block_rows" if model == "llama" else "hidden_size"
    assert config_from_args(_args(model, **{other: 16})) == \
        CLASSES[model](**TINY[model])


# -- (iv) the seam by which a family says what layer i is -------------------
@pytest.mark.parametrize("remat_policy", ["none", "full", "dots"])
@pytest.mark.parametrize("family", ["llama", "zaya"])
def test_a_family_of_one_kind_lowers_as_if_it_said_so_a_layer(family,
                                                              remat_policy):
    """``CausalLM.layer_block`` answers ``block`` for every layer unless a
    family says otherwise; a family that says ``block`` for each ``i``
    itself lowers to the same program text, with remat and without (the
    shell builds each kind's remat class once, whatever the depth)."""
    import re

    from fedml_tpu.models.llm.llama import LlamaForCausalLM
    from fedml_tpu.models.llm.zaya import ZayaForCausalLM

    cfg = (LlamaConfig if family == "llama" else ZayaConfig).tiny(
        lora_rank=4, use_flash=False, remat=True, remat_policy=remat_policy)
    base = LlamaForCausalLM if family == "llama" else ZayaForCausalLM
    asked = []

    class Saying(base):
        @nn.nowrap
        def layer_block(self, i):
            asked.append(i)
            return type(self).block

    Saying.__name__ = base.__name__   # the class's name is in every op_name
    tokens = jnp.zeros((1, 8), jnp.int32)
    texts = []
    for module in (base(cfg), Saying(cfg)):
        assert module.layer_block(0) is base.block
        params = jax.eval_shape(module.init, jax.random.key(0), tokens)
        lowered = jax.jit(module.apply).lower(params, tokens).as_text()
        texts.append(re.sub(r"loc\([^)]*\)|#loc.*", "", lowered))
    assert asked.count(1) >= 1 and set(asked) == set(
        range(cfg.num_hidden_layers))
    assert texts[0] == texts[1]
