"""On-device fused federated LLM round (VERDICT r4 task 1).

``LLMTrainer.compile_federated_round`` fuses client-switch, local steps
and LoRA FedAvg into one donated-buffer XLA program. These tests pin (a)
numerical parity with the host round loop it replaces (the reference's
round shape, ``cross_silo/server/fedml_server_manager.py:174-252``),
(b) the ``FedLLMAPI on_device_round`` wiring, and (c) the guard that
refuses to silently bypass host-side trust-stack hooks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.llm.llama import LlamaConfig
from fedml_tpu.train.llm.trainer import LLMTrainer, extract_lora, merge_lora


class _Args:
    max_seq_length = 16
    per_device_batch_size = 4
    gradient_accumulation_steps = 1
    learning_rate = 1e-2
    mesh_dp, mesh_fsdp, mesh_tp, mesh_sp = 1, 4, 2, 1
    random_seed = 0


def _copy(t):
    return jax.tree.map(jnp.copy, t)


# bfloat16 is what every cell computes in. The two sides are two XLA
# programs, and at its default the CPU compiler is allowed excess precision:
# it drops a rounding to bfloat16 wherever it can, and not the same ones in
# both, so they part by an ulp somewhere in the layers' backward pass and
# Adam carries that into the adapters (PERF.md, PR 31's finding, has the
# leaf, the step and the sizes, on this tree and on its parent). With
# every rounding the source writes kept, the two agree to the order of
# FedAvg's sum. float32 has nothing to drop.
@pytest.mark.parametrize("dtype, options", [
    (jnp.bfloat16, {"xla_allow_excess_precision": False}),
    (jnp.float32, {}),
], ids=["bfloat16", "float32"])
def test_fused_round_matches_host_loop(dtype, options):
    cfg = LlamaConfig.tiny(lora_rank=4, use_flash=False, dtype=dtype)
    tr = LLMTrainer(cfg, _Args())
    tr.init(seed=0)
    n_clients, steps, batch, seq = 3, 2, 4, 16
    rng = np.random.default_rng(0)
    xs = rng.integers(
        0, cfg.vocab_size, size=(n_clients, steps, batch, seq)
    ).astype(np.int32)
    ys = ((xs + 1) % cfg.vocab_size).astype(np.int32)
    ms = np.ones((n_clients, steps, batch), np.float32)
    w = np.asarray([1.0, 2.0, 3.0], np.float32)

    p0, o0 = _copy(tr.params), _copy(tr.opt_state)
    g0 = _copy(extract_lora(tr.params))

    # host round loop — exactly what the fused program replaces
    from fedml_tpu.ml.aggregator.agg_operator import FedMLAggOperator

    train_step = jax.jit(tr._train_step.jitted, compiler_options=options)
    p, o = _copy(p0), _copy(o0)
    uploads = []
    for c in range(n_clients):
        p = merge_lora(p, _copy(g0))
        for s in range(steps):
            p, o, _ = train_step(
                p, o,
                jnp.asarray(xs[c, s][None]), jnp.asarray(ys[c, s][None]),
                jnp.asarray(ms[c, s][None]),
            )
        uploads.append(_copy(extract_lora(p)))
    host_global = FedMLAggOperator.agg_with_weights(uploads, list(w))

    fed = jax.jit(tr.compile_federated_round(n_clients, steps).jitted,
                  compiler_options=options)
    p1, o1, fused_global, loss = fed(p0, o0, g0, xs, ys, ms, w)
    assert np.isfinite(float(loss))
    assert set(fused_global) == set(host_global)
    for k in host_global:
        np.testing.assert_allclose(
            np.asarray(fused_global[k]), np.asarray(host_global[k]),
            rtol=2e-4, atol=2e-5)
    # params leave the round holding the LAST client's adapters — parity
    # with the host loop's live state before its final merge
    live = extract_lora(p1)
    for k in host_global:
        np.testing.assert_allclose(
            np.asarray(live[k]), np.asarray(uploads[-1][k]),
            rtol=2e-4, atol=2e-5)


def test_fused_round_chains_via_donation():
    """Outputs feed straight back in as the next round's donated inputs."""
    cfg = LlamaConfig.tiny(lora_rank=4, use_flash=False)
    tr = LLMTrainer(cfg, _Args())
    tr.init(seed=1)
    fed = tr.compile_federated_round(2, 1)
    rng = np.random.default_rng(1)
    xs = rng.integers(0, cfg.vocab_size, size=(2, 1, 4, 16)).astype(np.int32)
    ys = ((xs + 1) % cfg.vocab_size).astype(np.int32)
    ms = np.ones((2, 1, 4), np.float32)
    w = np.ones((2,), np.float32)
    p, o, g = tr.params, tr.opt_state, _copy(extract_lora(tr.params))
    losses = []
    for _ in range(3):
        p, o, g, loss = fed(p, o, g, xs, ys, ms, w)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # same data every round → loss must drop
    # the trainer told the loss that its mesh cuts the vocabulary in 2
    # (``mesh_tp``), so the head is walked inside each shard
    from fedml_tpu.telemetry import get_tracer

    plans = [r["attrs"] for r in get_tracer().records()
             if r["name"] == "loss/plan"]
    assert plans[-1]["shards"] == 2 and plans[-1]["vocab"] == cfg.vocab_size


def test_fused_round_requires_lora():
    cfg = LlamaConfig.tiny(lora_rank=0, use_flash=False)
    tr = LLMTrainer(cfg, _Args())
    tr.init(seed=0)
    with pytest.raises(ValueError, match="LoRA"):
        tr.compile_federated_round(2, 1)


def _fedllm_args(extra_train=None, **extra_sections):
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments_from_dict

    train = {"federated_optimizer": "FedAvg", "client_num_in_total": 4,
             "client_num_per_round": 2, "comm_round": 2, "epochs": 1,
             "batch_size": 4, "per_device_batch_size": 4,
             "learning_rate": 5e-3, "mesh_dp": 1, "mesh_fsdp": 4,
             "mesh_tp": 2, "mesh_sp": 1, "frequency_of_the_test": 1,
             "on_device_round": True}
    train.update(extra_train or {})
    return fedml_tpu.init(load_arguments_from_dict({
        "common_args": {"training_type": "simulation", "random_seed": 0},
        "data_args": {"dataset": "synthetic_lm", "max_seq_length": 16,
                      "vocab_size": 32, "train_size": 64, "test_size": 16},
        "model_args": {"model": "llama", "model_size": "tiny",
                       "lora_rank": 4, "use_flash": False},
        "train_args": train,
        **extra_sections,
    }))


def test_fedllm_api_on_device_round():
    from fedml_tpu.data import load_federated
    from fedml_tpu.train.llm.run_fedllm import FedLLMAPI

    args = _fedllm_args()
    ds = load_federated(args)
    api = FedLLMAPI(args, None, ds)
    assert api.on_device
    r0 = api.train_one_round(0)
    r1 = api.train_one_round(1)
    assert np.isfinite(r0["train_loss"]) and np.isfinite(r1["train_loss"])
    assert "test_loss" in r1


def test_on_device_round_refuses_host_hooks():
    from fedml_tpu.data import load_federated
    from fedml_tpu.train.llm.run_fedllm import FedLLMAPI

    args = _fedllm_args(
        defense_args={"enable_defense": True,
                      "defense_type": "norm_diff_clipping",
                      "norm_bound": 5.0},
    )
    ds = load_federated(args)
    with pytest.raises(ValueError, match="on_device_round"):
        FedLLMAPI(args, None, ds)
