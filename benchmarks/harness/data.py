"""Token data and the round's batches, from ``--seed`` alone (numpy only).

``markov_band`` is a copy of the arithmetic of the program's
``synthetic_lm`` loader (``fedml_tpu/data/data_loader.py``; listed in
PERF.md for a later PR to delete one of the two): an order-1 Markov stream
whose token mostly moves to t+1 or t+2 (mod V), with a share of uniformly
random jumps. ``round_batches`` is the plain statement of which rows a
round trains on — written from the documented semantics of
``FedLLMAPI.train_one_round`` (seeded client draw, ``steps x batch`` rows
drawn with replacement per client) and imported by the reference only; the
program assembles its own.
"""
from __future__ import annotations

import numpy as np


def markov_band(seed: int, vocab: int, seq_len: int, n: int,
                step_probs=(0.8, 0.2), noise: float = 0.05):
    """``n`` samples ``(x, y) = (tokens[:-1], tokens[1:])`` of length T."""
    rng = np.random.default_rng(int(seed) + 77)
    toks = np.zeros((n, seq_len + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=n)
    step = rng.choice(np.arange(1, len(step_probs) + 1), p=list(step_probs),
                      size=(n, seq_len))
    jump = rng.random((n, seq_len)) < noise
    rand_tok = rng.integers(0, vocab, size=(n, seq_len))
    for t in range(seq_len):
        nxt = (toks[:, t] + step[:, t]) % vocab
        toks[:, t + 1] = np.where(jump[:, t], rand_tok[:, t], nxt)
    return toks[:, :-1], toks[:, 1:]


def make_clients(seed: int, vocab: int, traffic: dict) -> dict:
    """``{client: (x, y)}`` with equal shards, cut in order from one draw."""
    spec = traffic["data"]
    if spec["maker"] != "markov_band":
        raise ValueError(f"unknown data maker {spec['maker']!r}")
    clients = int(traffic["clients_total"])
    per = int(traffic["samples_per_client"])
    x, y = markov_band(seed, vocab, int(traffic["seq_len"]), clients * per,
                       spec["step_probs"], spec["noise"])
    return {c: (x[c * per:(c + 1) * per], y[c * per:(c + 1) * per])
            for c in range(clients)}


def round_clients(seed: int, round_idx: int, total: int, per_round: int):
    """The seeded client draw: everyone when ``per_round >= total``."""
    if per_round >= total:
        return list(range(total))
    rng = np.random.default_rng(round_idx + int(seed))
    return sorted(rng.choice(np.arange(total), per_round,
                             replace=False).tolist())


def round_batches(seed: int, round_idx: int, clients: dict, traffic: dict):
    """``(xs, ys, weights)``: ``[clients, steps, batch, T]`` rows of round
    ``round_idx`` and each client's aggregation weight (its shard size)."""
    ids = round_clients(seed, round_idx, int(traffic["clients_total"]),
                        int(traffic["clients_per_round"]))
    steps, batch = int(traffic["local_steps"]), int(traffic["per_device_batch"])
    rng = np.random.default_rng(int(seed) * 9973 + round_idx)
    xs, ys, weights = [], [], []
    for cid in ids:
        x, y = clients[cid]
        idx = rng.integers(0, x.shape[0], size=(steps, batch))
        xs.append(x[idx])
        ys.append(y[idx])
        weights.append(float(x.shape[0]))
    return np.stack(xs), np.stack(ys), np.asarray(weights, np.float32)
