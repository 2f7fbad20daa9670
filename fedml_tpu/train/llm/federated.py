"""Federated LLM fine-tuning — the FedLLM spotlight, TPU-native.

Parity target: ``python/spotlight_prj/fedllm/run_fedllm.py`` — ``LLMTrainer``
(:246) / ``LLMAggregator`` (:460) binding ``fedml.train.llm`` into the
``ClientTrainer``/``ServerAggregator`` frame, with per-round checkpoints
(:171) and DeepSpeed process-group sync (:435).

TPU re-design: each client runs the compiled sharded train step from
``trainer.py`` over its own token shard; when LoRA is on, ONLY the adapter
dict crosses the federation transport (the reference ships peft state
dicts the same way), so a 7B base model federates with ~0.1% of the
traffic of full FedAvg. The exchanged payload is the flat
``{path: array}`` dict from :func:`extract_lora`, which the generic
``FedMLAggOperator`` treats as just another pytree.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Optional, Tuple

import numpy as np

from fedml_tpu.core.alg_frame.client_trainer import ClientTrainer
from fedml_tpu.core.alg_frame.server_aggregator import ServerAggregator
from fedml_tpu.train.llm.trainer import LLMTrainer

logger = logging.getLogger(__name__)

Pytree = Any


class LLMClientTrainer(ClientTrainer):
    """ClientTrainer over the sharded LLM train step.

    ``train(params, train_data, device, args)`` consumes the *exchangeable*
    params (LoRA dict or full tree), runs ``args.epochs`` of local steps,
    and returns the updated exchangeable params.
    """

    def __init__(self, cfg: Any, args: Any, mesh=None):
        super().__init__(model=None, args=args)
        self.engine = LLMTrainer(cfg, args, mesh=mesh)
        self.engine.init(seed=int(getattr(args, "random_seed", 0)))
        self.lora_only = self.engine.lora_only
        self._round_seed = 0

    # engine-contract hooks: shapes are already static (fixed [B, T] token
    # batches), so pad_to_batches is a no-op; the round index seeds shuffling
    def set_pad_to_batches(self, n) -> None:
        pass

    def set_round(self, round_idx: int) -> None:
        self._round_seed = int(round_idx)

    def get_exchange_params(self) -> Pytree:
        # fresh buffers (the train step donates params); host numpy when
        # the silo mesh spans processes — see LLMTrainer.exchange_state
        return self.engine.exchange_state()

    def set_exchange_params(self, exchanged: Pytree) -> None:
        self.engine.load_exchange_state(exchanged)

    def train(self, params: Pytree, train_data, device, args) -> Tuple[Pytree, Dict]:
        """ClientTrainer contract: (new_exchange_params, metrics)."""
        self.set_exchange_params(params)
        x, y = train_data
        x = np.asarray(x)
        y = np.asarray(y)
        batch = self.engine.batch_size
        epochs = int(getattr(args, "epochs", 1))
        seed = (int(getattr(args, "random_seed", 0)) * 9973 + self.id * 1009
                + self._round_seed)
        rng = np.random.default_rng(seed)
        n = x.shape[0]
        losses = []
        for _ in range(epochs):
            order = rng.permutation(n)
            for i in range(0, n, batch):
                idx = order[i : i + batch]
                m = np.ones((batch,), np.float32)
                if len(idx) < batch:
                    # pad the trailing partial batch; mask=0 rows are no-ops
                    # (same pad+mask discipline as data/dataset.batch_epochs)
                    m[len(idx):] = 0.0
                    idx = np.concatenate(
                        [idx, np.full(batch - len(idx), idx[0] if len(idx) else 0)]
                    ).astype(idx.dtype)
                losses.append(self.engine.step(x[idx], y[idx], m))
        self.local_sample_number = n
        metrics = {"train_loss": float(np.mean(losses)) if losses else 0.0,
                   "train_samples": float(n)}
        return self.get_exchange_params(), metrics

    def test(self, params: Pytree, test_data, device, args) -> Dict:
        self.set_exchange_params(params)
        x, y = test_data
        n = min(len(x), self.engine.batch_size * 8)
        return self.engine.evaluate(np.asarray(x[:n]), np.asarray(y[:n]))


class LLMAggregator(ServerAggregator):
    """ServerAggregator for LLM federation — aggregates the exchange dict.

    The payloads are flat ``{path: array}`` dicts (or full pytrees); both
    are pytrees, so the defense/DP hook chain and ``FedMLAggOperator``
    apply unchanged. Reference: ``run_fedllm.py:460`` LLMAggregator.
    """

    def __init__(self, cfg: Any, args: Any, mesh=None,
                 engine: Optional[LLMTrainer] = None):
        super().__init__(model=None, args=args)
        self.engine = engine or LLMTrainer(cfg, args, mesh=mesh)
        if self.engine.params is None:
            self.engine.init(seed=int(getattr(args, "random_seed", 0)))
        self.lora_only = self.engine.lora_only

    def get_init_params(self) -> Pytree:
        return self.engine.exchange_state()

    def set_global_params(self, exchanged: Pytree) -> None:
        self.engine.load_exchange_state(exchanged)

    def test(self, params: Pytree, test_data, device, args) -> Dict:
        self.set_global_params(params)
        x, y = test_data
        n = min(len(x), self.engine.batch_size * 8)
        metrics = self.engine.evaluate(np.asarray(x[:n]), np.asarray(y[:n]))
        return {"test_loss": metrics["eval_loss"], "test_acc": metrics["eval_acc"]}

    def save_round(self, ckpt_dir: str, round_idx: int) -> str:
        return self.engine.save_checkpoint(ckpt_dir, round_idx)
