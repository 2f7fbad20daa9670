"""``flops.py`` and ``weights.py`` against the real module's shapes."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import flops, program, spec, weights

ROOT = spec.ROOT


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,total", [("yi-6b", 6.06e9),
                                        ("smollm2-1.7b", 1.71e9)])
def test_parameter_count_matches_the_module(name, total):
    from fedml_tpu.models.llm.llama import LlamaForCausalLM
    from fedml_tpu.train.llm.sharding import unbox

    config = _config(name)
    cfg = program.llama_config(config, {"remat_policy": "none"})
    shapes = unbox(jax.eval_shape(
        LlamaForCausalLM(cfg).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32)))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    module = {"/".join(str(p.key) for p in path).removeprefix("params/"):
              (tuple(v.shape), jnp.dtype(v.dtype)) for path, v in flat}
    stated = {k: (tuple(s), jnp.dtype(d))
              for k, (s, d, _) in weights.leaf_specs(config).items()}
    assert module == stated
    base = weights.param_count(config)
    assert base == pytest.approx(total, rel=0.01)
    # what a token is multiplied by: everything but the norms and (untied)
    # the embedding rows it only looks up
    p = flops.base_matmul_params(config)
    matmul = config["num_hidden_layers"] * p["layer"] + p["head"]
    norms = (2 * config["num_hidden_layers"] + 1) * config["hidden_size"]
    embed = config["vocab_size"] * config["hidden_size"]
    tied = config["tie_word_embeddings"]
    assert matmul == base - norms - (0 if tied else embed)
    assert weights.param_count(config, lora=True) == (
        config["num_hidden_layers"] * flops.lora_params_per_layer(config))


def test_weights_repeat_and_differ():
    config = {"hidden_size": 16, "intermediate_size": 32, "num_hidden_layers": 2,
              "num_attention_heads": 2, "num_key_value_heads": 1,
              "vocab_size": 32, "tie_word_embeddings": True,
              "run": {"lora_rank": 2, "lora_targets": ["q_proj", "v_proj"]}}
    big = 2 ** 31 + 12345
    a, b = weights.make_all(config, big), weights.make_all(config, big)
    c = weights.make_all(config, big + 1)
    assert set(a) == set(weights.leaf_specs(config))
    for k in a:
        assert (a[k] == b[k]).all()
    assert not (a["layer_0/mlp/up_proj/kernel"]
                == c["layer_0/mlp/up_proj/kernel"]).all()
    assert not (a["layer_0/mlp/up_proj/kernel"]
                == a["layer_1/mlp/up_proj/kernel"]).all()
    assert float(jnp.abs(a["layer_0/attn/q_proj/lora_b"]).max()) > 0
    assert "layer_0/attn/k_proj/lora_a" not in a


def test_the_data_repeats_and_rounds_differ():
    import numpy as np

    from benchmarks.harness import data

    traffic = {"clients_total": 4, "clients_per_round": 4, "local_steps": 2,
               "per_device_batch": 1, "seq_len": 16, "samples_per_client": 4,
               "data": {"maker": "markov_band", "step_probs": [0.8, 0.2],
                        "noise": 0.05}}
    a = data.make_clients(2 ** 31 + 5, 100, traffic)
    b = data.make_clients(2 ** 31 + 5, 100, traffic)
    assert all((a[c][0] == b[c][0]).all() for c in a)
    assert (a[0][0][:, 1:] == a[0][1][:, :-1]).all()  # y is x shifted
    x1, _, w = data.round_batches(7, 1, a, traffic)
    x2, _, _ = data.round_batches(7, 2, a, traffic)
    assert x1.shape == (4, 2, 1, 16) and w.tolist() == [4.0] * 4
    assert not np.array_equal(x1, x2)
    assert data.round_clients(7, 1, 8, 3) == data.round_clients(7, 1, 8, 3)
    assert len(data.round_clients(7, 1, 8, 3)) == 3
