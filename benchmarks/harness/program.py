"""The system under test: the program's own entry points, nothing else.

``fedml_tpu.init`` → a ``FederatedDataset`` of the harness's data →
``FedLLMAPI(on_device_round: true, cfg=...)`` → ``train_one_round(r)``.
Copied from ``chip_smoke.py`` (``round_config``, ``federated_rounds``),
which later PRs may edit; this file is the yardstick's own.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from . import data as data_mod
from . import weights as weights_mod

NEVER = 1 << 30  # a round index no window reaches: no eval, no last round


def round_config(seed: int, config: dict, traffic: dict) -> dict:
    """The yaml sections a user would write for this cell."""
    clients = int(traffic["clients_total"])
    return {
        "common_args": {"training_type": "simulation",
                        "random_seed": int(seed) % (1 << 32)},
        "data_args": {"dataset": "benchmark", "vocab_size": config["vocab_size"],
                      "max_seq_length": int(traffic["seq_len"])},
        "model_args": {"model": "llama"},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": clients,
                       "client_num_per_round": int(traffic["clients_per_round"]),
                       "comm_round": NEVER,
                       "frequency_of_the_test": NEVER,
                       "local_steps_per_round": int(traffic["local_steps"]),
                       "per_device_batch_size": int(traffic["per_device_batch"]),
                       "learning_rate": float(traffic["learning_rate"]),
                       "max_grad_norm": float(traffic["max_grad_norm"]),
                       "weight_decay": float(traffic["weight_decay"]),
                       "on_device_round": True},
    }


def llama_config(config: dict, traffic: dict):
    """``LlamaConfig`` with every width as the configuration's file has it."""
    import jax.numpy as jnp
    from fedml_tpu.models.llm.llama import LlamaConfig

    run = config["run"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        tie_word_embeddings=config["tie_word_embeddings"],
        lora_rank=run["lora_rank"], lora_alpha=run["lora_alpha"],
        dtype=dtypes[run["compute_dtype"]],
        param_dtype=dtypes[run["base_dtype"]],
        remat_policy=traffic["remat_policy"],
        use_flash=bool(run["use_flash_attention"]))


def federated_dataset(clients: dict, vocab: int, seq_len: int):
    from fedml_tpu.data.dataset import FederatedDataset

    xs = np.concatenate([clients[c][0] for c in sorted(clients)])
    ys = np.concatenate([clients[c][1] for c in sorted(clients)])
    return FederatedDataset(
        train_data_num=len(xs), test_data_num=0,
        train_data_global=(xs, ys), test_data_global=(xs[:0], ys[:0]),
        train_data_local_num_dict={c: len(v[0]) for c, v in clients.items()},
        train_data_local_dict=dict(clients),
        test_data_local_dict={}, class_num=vocab, feature_dim=seq_len)


def build(seed: int, config: dict, traffic: dict):
    """The program's objects for this cell, as its user would build them."""
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments_from_dict
    from fedml_tpu.telemetry import install_compile_cache_counters
    from fedml_tpu.telemetry import reset_catalog
    from fedml_tpu.train.llm.run_fedllm import FedLLMAPI

    reset_catalog()  # this run's records count this run's calls only
    args = fedml_tpu.init(load_arguments_from_dict(
        round_config(seed, config, traffic)))
    install_compile_cache_counters()
    clients = data_mod.make_clients(seed, config["vocab_size"], traffic)
    dataset = federated_dataset(clients, config["vocab_size"],
                                int(traffic["seq_len"]))
    return FedLLMAPI(args, None, dataset,
                     cfg=llama_config(config, traffic), mesh=None)


def _paths(tree) -> tuple:
    """``(keys, leaves, treedef)`` of a tree, keys as ``a/b/c``."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = ["/".join(str(getattr(p, "key", getattr(p, "name", p)))
                     for p in path) for path, _ in flat]
    return keys, [leaf for _, leaf in flat], treedef


def install_weights(api, seed: int, config: dict) -> None:
    """Replace the constructor's weights by the harness's, made from the
    seed; fresh optimizer state; the global adapters re-read from them."""
    import jax
    from fedml_tpu.train.llm.trainer import extract_trainable

    engine = api.client.engine
    if engine.params is not None:
        keys, leaves, treedef = _paths(engine.params)
        want = {k: (tuple(s), np.dtype(d))
                for k, (s, d, _) in weights_mod.leaf_specs(config).items()}
        have = {k.removeprefix("params/"): (tuple(v.shape), np.dtype(v.dtype))
                for k, v in zip(keys, leaves)}
        if have != want:
            diff = sorted(set(have.items()) ^ set(want.items()), key=str)
            raise SystemExit("benchmark: the program's parameter tree is not "
                             f"the layout weights.py states: {diff[:6]}")
        api.bench_layout = (keys, treedef)
        del leaves
        free(api)
    keys, treedef = api.bench_layout
    shardings = jax.tree.leaves(engine.shardings)
    made = weights_mod.make_all(config, seed)
    new = [jax.device_put(made[k.removeprefix("params/")], s)
           for k, s in zip(keys, shardings)]
    del made
    engine.params = jax.tree_util.tree_unflatten(treedef, new)
    engine.opt_state = jax.jit(engine.tx.init)(
        extract_trainable(engine.params))
    api.global_exchange = engine.exchange_state()


def reseed(api, seed: int, config: dict, traffic: dict) -> None:
    """The same compiled objects on another seed's weights and data (the
    reading of many seeds in one process, for setting limits)."""
    api.args.random_seed = int(seed) % (1 << 32)
    clients = data_mod.make_clients(seed, config["vocab_size"], traffic)
    api.dataset = federated_dataset(clients, config["vocab_size"],
                                    int(traffic["seq_len"]))
    install_weights(api, seed, config)


def snapshot(api) -> dict:
    """What the comparison reads of the program's state, copied to the
    host: Adam's moments and step count, and the global adapters."""
    import jax

    engine = api.client.engine
    flat, _ = jax.tree_util.tree_flatten_with_path(engine.opt_state)
    out = {"mu": {}, "nu": {}, "count": None}
    for path, leaf in flat:
        names = [str(getattr(p, "name", "")) for p in path]
        key = str(getattr(path[-1], "key", ""))
        for moment in ("mu", "nu"):
            if moment in names:
                out[moment][key.removeprefix("params/")] = np.asarray(leaf)
        if "count" in names and out["count"] is None:
            out["count"] = int(leaf)
    out["lora"] = {k.removeprefix("params/"): np.asarray(v)
                   for k, v in api.global_exchange.items()}
    return out


def tokens_per_round(traffic: dict) -> int:
    return (int(traffic["clients_per_round"]) * int(traffic["local_steps"])
            * int(traffic["per_device_batch"]) * int(traffic["seq_len"]))


def window(api, seconds: float, first_round: int, span=None) -> dict:
    """Call ``train_one_round`` for ``seconds``: whole rounds, from the
    first one's start to the end of the round that passes the mark."""
    import contextlib

    span = span or (lambda name: contextlib.nullcontext())
    losses = []
    r = first_round
    t0 = time.perf_counter()
    while True:
        with span("bench.round"):
            report = api.train_one_round(r)
        wall_s = time.perf_counter() - t0
        losses.append(float(report["train_loss"]))
        r += 1
        if wall_s >= seconds:
            break
    return {"rounds": len(losses), "wall_s": wall_s, "losses": losses}


def free(api) -> None:
    """Drop the program's device state so that the reference fits."""
    import jax

    engine = api.client.engine
    for tree in (engine.params, engine.opt_state, api.global_exchange):
        for leaf in jax.tree.leaves(tree):
            if hasattr(leaf, "delete"):
                leaf.delete()
    engine.params = engine.opt_state = api.global_exchange = None
    gc.collect()
