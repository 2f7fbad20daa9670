"""LLM path: flash-attention kernel parity, Llama model, sharded trainer,
LoRA freezing, federated FedLLM rounds. All on the 8-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.llm.llama import LlamaConfig, LlamaForCausalLM
from fedml_tpu.ops.flash_attention import flash_attention, reference_attention


class _Args:
    max_seq_length = 32
    per_device_batch_size = 8
    gradient_accumulation_steps = 1
    learning_rate = 1e-2
    mesh_dp, mesh_fsdp, mesh_tp, mesh_sp = 2, 2, 2, 1


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 4, 128, 32))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 128, 32))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, 128, 32))
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=causal)
    assert float(jnp.abs(out - ref).max()) < 2e-2


@pytest.mark.parametrize("causal", [
    pytest.param(True, marks=pytest.mark.slow),  # causal variant covered
    False,                                       # fast by the non-ragged test
])
def test_flash_attention_ragged_lengths(causal):
    """T not divisible by block sizes: phantom rows/cols must not leak."""
    key = jax.random.key(7)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 100, 32))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 100, 32))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, 100, 32))
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=causal)
    assert float(jnp.abs(out - ref).max()) < 2e-2
    g1 = jax.grad(lambda *a: flash_attention(
        *a, causal=causal, interpret=True, block_q=32, block_k=32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: reference_attention(*a, causal=causal).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 2e-2


def test_flash_attention_grads_match():
    key = jax.random.key(1)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 16))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 16))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, 64, 16))
    g1 = jax.grad(
        lambda *a: flash_attention(*a, causal=True, interpret=True,
                                   block_q=32, block_k=32).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        lambda *a: reference_attention(*a, causal=True).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 2e-2


def test_llama_forward_and_decode_parity():
    cfg = LlamaConfig.tiny(use_flash=False)
    model = LlamaForCausalLM(cfg)
    toks = jax.random.randint(jax.random.key(0), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.key(0), toks)
    full = model.apply(params, toks)
    assert full.shape == (2, 16, cfg.vocab_size)
    caches = model.init_kv_caches(2, 16)
    l1, caches = model.apply(params, toks[:, :8], jnp.arange(8), caches)
    l2, _ = model.apply(params, toks[:, 8:], jnp.arange(8, 16), caches)
    stitched = jnp.concatenate([l1, l2], axis=1)
    assert float(jnp.abs(stitched - full).max()) < 1e-4


@pytest.mark.slow
def test_llm_trainer_converges_full_ft():
    from fedml_tpu.train.llm.trainer import LLMTrainer

    cfg = LlamaConfig.tiny(lora_rank=0, use_flash=False)
    tr = LLMTrainer(cfg, _Args())
    tr.init(seed=0)
    rng = np.random.default_rng(0)
    V = 16
    losses = []
    for _ in range(20):
        x = rng.integers(0, V, size=(8, 32))
        losses.append(tr.step(x, (x + 1) % V, np.ones((8,))))
    assert losses[-1] < losses[0] * 0.5, losses


@pytest.mark.slow
def test_llm_trainer_lora_freezes_base():
    from fedml_tpu.train.llm.trainer import LLMTrainer, extract_lora

    cfg = LlamaConfig.tiny(lora_rank=4, use_flash=False)
    tr = LLMTrainer(cfg, _Args())
    tr.init(seed=0)
    emb0 = np.asarray(tr.params["params"]["embed_tokens"])
    rng = np.random.default_rng(0)
    for _ in range(3):
        x = rng.integers(0, 16, size=(8, 32))
        tr.step(x, (x + 1) % 16, np.ones((8,)))
    assert np.allclose(emb0, np.asarray(tr.params["params"]["embed_tokens"]))
    lora = extract_lora(tr.params)
    assert len(lora) == 4 * cfg.num_hidden_layers * 2  # qkvo × (a, b)
    assert any(float(jnp.abs(v).max()) > 0 for k, v in lora.items()
               if "lora_b" in k)


@pytest.mark.slow
def test_llm_checkpoint_roundtrip(tmp_path):
    from fedml_tpu.train.llm.trainer import LLMTrainer, extract_lora

    cfg = LlamaConfig.tiny(lora_rank=4, use_flash=False)
    tr = LLMTrainer(cfg, _Args())
    tr.init(seed=0)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 16, size=(8, 32))
    tr.step(x, (x + 1) % 16, np.ones((8,)))
    path = tr.save_checkpoint(str(tmp_path), 0)
    saved = {k: np.asarray(v) for k, v in extract_lora(tr.params).items()}
    tr.step(x, (x + 1) % 16, np.ones((8,)))
    tr.load_checkpoint(path)
    now = extract_lora(tr.params)
    for k, v in now.items():
        assert np.allclose(saved[k], np.asarray(v))

    # fine-tune -> serve loop: a FRESH serving-style params tree (the
    # `serve --checkpoint` path) picks up the trained adapters
    import jax

    from fedml_tpu.models.llm.llama import LlamaForCausalLM
    from fedml_tpu.train.llm.sharding import unbox
    from fedml_tpu.train.llm.trainer import restore_checkpoint_into

    import jax.numpy as jnp

    fresh = unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(7), jnp.zeros((1, 8), jnp.int32)))
    served = restore_checkpoint_into(fresh, path, lora_only=True)
    for k, v in extract_lora(served).items():
        assert np.allclose(saved[k], np.asarray(v))


@pytest.mark.slow
def test_fedllm_rounds_improve():
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments_from_dict
    from fedml_tpu.data import load_federated
    from fedml_tpu.train.llm.run_fedllm import FedLLMAPI

    args = fedml_tpu.init(load_arguments_from_dict({
        "common_args": {"training_type": "simulation", "random_seed": 0},
        "data_args": {"dataset": "synthetic_lm", "max_seq_length": 32,
                      "vocab_size": 32, "train_size": 128, "test_size": 32},
        "model_args": {"model": "llama", "model_size": "tiny", "lora_rank": 4,
                       "use_flash_attention": False},
        "train_args": {"backend": "sp", "federated_optimizer": "FedAvg",
                       "client_num_in_total": 2, "client_num_per_round": 2,
                       "comm_round": 2, "epochs": 1, "batch_size": 8,
                       "per_device_batch_size": 8, "learning_rate": 5e-3,
                       "mesh_dp": 1, "mesh_fsdp": 4, "mesh_tp": 2, "mesh_sp": 1,
                       "frequency_of_the_test": 1},
    }))
    ds = load_federated(args)
    api = FedLLMAPI(args, None, ds)
    r0 = api.train_one_round(0)
    r1 = api.train_one_round(1)
    assert r1["test_loss"] < r0["test_loss"]
