"""FedLLM — federated LoRA fine-tuning round loop (the flagship config).

Parity target: ``python/spotlight_prj/fedllm/run_fedllm.py`` (the reference's
FedLLM app: cross-silo FedAvg over peft adapters). This is the simulation
analogue: N clients share one compiled engine (sequential local training, the
``sp`` backend shape — ``simulation/sp/fedavg/fedavg_api.py:66``), exchanging
LoRA dicts; aggregation is a weighted tree-average. The cross-silo engine
runs the same trainer/aggregator pair over a real transport.

BASELINE.md config #4: Llama-2-7B LoRA, 8 clients, FSDP+TP mesh.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Dict, List

import numpy as np

from fedml_tpu.data.dataset import FederatedDataset
from fedml_tpu.models.llm import config_from_args
from fedml_tpu.simulation.sampling import sample_clients
from fedml_tpu.telemetry import get_tracer
from fedml_tpu.train.llm.federated import LLMAggregator, LLMClientTrainer

logger = logging.getLogger(__name__)


class FedLLMAPI:
    """Round loop: sample clients → local LoRA steps → weighted average.

    Every round is one trace of the process tracer: ``round/<n>/run`` (the
    fused path's root) over ``sample``, ``stage``, ``dispatch``, ``wait``
    and, when they run, ``eval`` and ``checkpoint``; the host path leaves
    ``sample``, ``client/<id>/train`` and ``aggregate``. A model that
    routes tokens to experts also leaves the point event ``round/<n>/moe``
    (:meth:`_moe_event`).
    """

    def __init__(self, args: Any, device: Any, dataset: FederatedDataset,
                 cfg: Any = None, mesh=None):
        """``cfg``: a model family's configuration object, whose
        ``module()`` is a ``models/llm/causal_lm.py::CausalLM`` bound to
        the family's block; without one it is built from ``args`` by the
        ``model`` they name (``models.llm.config_from_args``). Runs under
        the span ``llm/build``, whose children are the ``program/*`` stages
        of ``llm/init_params``."""
        with get_tracer().span("llm/build"):
            self.args = args
            self.dataset = dataset
            self.cfg = cfg or config_from_args(
                args, vocab_size=dataset.class_num)
            # one engine serves every simulated client (params are swapped
            # in); this is exactly the reference's sp-backend memory model
            self.client = LLMClientTrainer(self.cfg, args, mesh=mesh)
            self.aggregator = LLMAggregator(
                self.cfg, args, mesh=mesh, engine=self.client.engine
            )
            self.global_exchange = self.aggregator.get_init_params()
            self.test_history: List[dict] = []
            # on_device_round: true fuses the ENTIRE round (client-switch,
            # local steps, LoRA FedAvg) into one donated-buffer XLA program
            # — see LLMTrainer.compile_federated_round. The trust-stack
            # hooks intercept per-client payloads on the host, which that
            # program bypasses, so the two are mutually exclusive by
            # construction.
            self.on_device = bool(getattr(args, "on_device_round", False))
            self._fed_round = None
            self._fed_round_key = None
            if self.on_device:
                self._check_no_host_hooks()

    def _check_no_host_hooks(self) -> None:
        from fedml_tpu.core.dp.fedml_differential_privacy import (
            FedMLDifferentialPrivacy,
        )
        from fedml_tpu.core.fhe.fhe_agg import FedMLFHE
        from fedml_tpu.core.security.attacker import FedMLAttacker
        from fedml_tpu.core.security.defender import FedMLDefender

        active = [
            name
            for name, on in (
                ("attack", FedMLAttacker.get_instance().is_attack_enabled()),
                ("defense", FedMLDefender.get_instance().is_defense_enabled()),
                ("dp", FedMLDifferentialPrivacy.get_instance().is_dp_enabled()),
                ("fhe", FedMLFHE.get_instance().is_fhe_enabled()),
            )
            if on
        ]
        if active:
            raise ValueError(
                f"on_device_round: true is incompatible with host-side "
                f"trust-stack hooks (active: {', '.join(active)}) — the "
                f"fused round never surfaces per-client payloads to the "
                f"host; disable the hooks or drop on_device_round")

    def _train_one_round_on_device(self, round_idx: int) -> Dict:
        """The fused-round fast path: one XLA program per round."""
        engine = self.client.engine
        tracer = get_tracer()
        batch = engine.batch_size
        steps = int(getattr(self.args, "local_steps_per_round", 0) or 0)
        if steps <= 0:
            # default: one optimizer step per local epoch, each on a fresh
            # random batch (the fixed-shape SPMD analogue of an epoch sweep)
            steps = int(getattr(self.args, "epochs", 1))
        with tracer.span(f"round/{round_idx}/run", steps=steps) as run:
            with tracer.span(f"round/{round_idx}/sample") as sp:
                client_ids = sample_clients(self.args, round_idx)
                sp.attrs["clients"] = len(client_ids)
            key = (len(client_ids), steps)
            if self._fed_round_key != key:
                self._fed_round = engine.compile_federated_round(*key)
                self._fed_round_key = key

            with tracer.span(f"round/{round_idx}/stage") as sp:
                xs = np.zeros(
                    (len(client_ids), steps, batch, engine.seq_len), np.int32)
                ys = np.zeros_like(xs)
                ms = np.ones((len(client_ids), steps, batch), np.float32)
                weights = np.zeros((len(client_ids),), np.float32)
                rng = np.random.default_rng(
                    int(getattr(self.args, "random_seed", 0)) * 9973
                    + round_idx)
                for i, cid in enumerate(client_ids):
                    x, y = self.dataset.train_data_local_dict[cid]
                    x, y = np.asarray(x), np.asarray(y)
                    idx = rng.integers(0, x.shape[0], size=(steps, batch))
                    xs[i], ys[i] = x[idx], y[idx]
                    weights[i] = float(
                        self.dataset.train_data_local_num_dict[cid])
                sp.attrs.update(
                    rows=ms.size, tokens=xs.size,
                    bytes=xs.nbytes + ys.nbytes + ms.nbytes + weights.nbytes)
            run.attrs.update(clients=len(client_ids), tokens=xs.size)

            t0 = time.time()
            # until the program returns its futures: the host→device copy
            # of the feed (it goes in as numpy), the catalog's wrapper, the
            # enqueue — and the compile, when this signature is new
            with tracer.span(f"round/{round_idx}/dispatch",
                             program="llm/fused_round"):
                (engine.params, engine.opt_state, self.global_exchange, loss,
                 *stats) = self._fed_round(
                     engine.params, engine.opt_state, self.global_exchange,
                     xs, ys, ms, weights)
            with tracer.span(f"round/{round_idx}/wait"):
                loss = float(loss)  # jit returns futures: block BEFORE stopping t
                stats = {k: np.asarray(v) for s in stats for k, v in s.items()}
            dt = time.time() - t0
            if "moe_tokens" in stats:
                self._moe_event(round_idx, stats, xs.size, ms.size // batch,
                                self.cfg)
            report = {"round": round_idx, "round_sec": dt, "train_loss": loss}
            self._maybe_test_and_checkpoint(round_idx, report)
        return report

    @staticmethod
    def _moe_event(round_idx: int, stats: Dict[str, np.ndarray],
                   tokens: int, steps: int, cfg: Any) -> Dict:
        """``round/<n>/moe``: how the round's assignments spread over the
        experts held here. ``moe_tokens`` ``[expert layers, held experts]``
        and ``moe_live`` ``[expert layers]`` are summed over the round's
        ``steps``; what no count carries is the configuration's to say
        (``cfg.moe_static``: ``experts`` the router scores and ``top_k``;
        ``cfg.moe_capacity_rows(tokens of a step)``: the sorted buffer's
        static rows). A family that holds every expert need not count
        ``moe_held`` / ``moe_placed``: every assignment is held, and the
        counts say where it was placed.
        ``max_over_mean`` is the busiest held expert's load over the held
        experts' mean load in the worst layer; ``held_share`` the share of
        all assignments whose expert is held here; ``dropped`` the held
        assignments that reached no row in some layer (a dropless layer
        reads 0); ``live_share`` the share of (step, layer, held expert)
        triples in which the expert got a token — a step's grouped products
        read only those experts' matrices; ``tiles_per_run`` the live row
        tiles (``moe_tiles`` ``[expert layers]``, summed like ``moe_live``)
        over those triples: how many of a grouped product's tile products
        an expert's weight copy has to hide under; ``buffer_live_share``
        the rows that hold an assignment over the sorted buffer's static
        rows (``capacity_rows`` a step and layer); ``zero_share``, where
        the family counts ``moe_zero``, the share of all assignments that
        went to an identity expert (no row, no product)."""
        static = cfg.moe_static
        counts = stats["moe_tokens"]
        layers, held = counts.shape
        top_k = int(static["top_k"])
        assignments = int(tokens) * top_k
        per_layer = counts.sum(axis=1)
        here = stats.get("moe_held", np.full(layers, assignments))
        placed = stats.get("moe_placed", per_layer)
        step_tokens = int(tokens) // max(int(steps), 1)
        capacity = int(cfg.moe_capacity_rows(step_tokens))
        extra = {}
        if "moe_zero" in stats:
            extra["zero_share"] = float(
                stats["moe_zero"].sum() / (assignments * layers))
        return get_tracer().event(
            f"round/{round_idx}/moe",
            layers=int(layers), experts=int(static["experts"]),
            held=int(held), top_k=top_k, tokens=int(tokens),
            steps=int(steps), assignments=assignments,
            held_share=float(here.sum() / (assignments * layers)),
            capacity_rows=capacity,
            buffer_live_share=float(
                placed.sum() / (capacity * max(int(steps), 1) * layers)),
            max_over_mean=float(
                (counts.max(axis=1) * held / np.maximum(per_layer, 1)).max()),
            live_share=float(
                stats["moe_live"].sum() / (steps * layers * held)),
            tiles_per_run=float(
                stats["moe_tiles"].sum() / max(stats["moe_live"].sum(), 1)),
            dropped=int((here - placed).max()), **extra)

    def train_one_round(self, round_idx: int) -> Dict:
        if self.on_device:
            return self._train_one_round_on_device(round_idx)
        tracer = get_tracer()
        with tracer.span(f"round/{round_idx}/sample") as sp:
            client_ids = sample_clients(self.args, round_idx)
            sp.attrs["clients"] = len(client_ids)
        payloads = []
        t0 = time.time()
        for cid in client_ids:
            self.client.set_id(cid)
            self.client.set_round(round_idx)
            data = self.dataset.train_data_local_dict[cid]
            n = self.dataset.train_data_local_num_dict[cid]
            # run_local_training = attack/DP/FHE hook chain around train()
            with tracer.span(f"round/{round_idx}/client/{cid}/train",
                             n_samples=n):
                updated, _metrics = self.client.run_local_training(
                    self.global_exchange, data, None, self.args
                )
            payloads.append((float(n), updated))
        # full ServerAggregator hook chain: defense/DP before-hooks,
        # defense-wrapped FedMLAggOperator, central-DP/contribution after
        with tracer.span(f"round/{round_idx}/aggregate",
                         clients=len(payloads)):
            model_list, _ = self.aggregator.on_before_aggregation(payloads)
            self.global_exchange = self.aggregator.aggregate(model_list)
            self.global_exchange = self.aggregator.on_after_aggregation(
                self.global_exchange
            )
        dt = time.time() - t0

        report = {"round": round_idx, "round_sec": dt}
        self._maybe_test_and_checkpoint(round_idx, report)
        return report

    def _maybe_test_and_checkpoint(self, round_idx: int, report: Dict) -> None:
        freq = int(getattr(self.args, "frequency_of_the_test", 1))
        if round_idx % max(freq, 1) == 0 or round_idx == int(
            getattr(self.args, "comm_round", 1)
        ) - 1:
            with get_tracer().span(f"round/{round_idx}/eval"):
                metrics = self.aggregator.test(
                    self.global_exchange, self.dataset.test_data_global,
                    None, self.args
                )
            report.update(metrics)
            self.test_history.append(report)
            logger.info("fedllm round %d: %s", round_idx, metrics)
        ckpt_dir = getattr(self.args, "checkpoint_dir", None)
        every = int(getattr(self.args, "save_every_rounds", 0) or 0)
        if ckpt_dir and every and round_idx % every == 0:
            with get_tracer().span(f"round/{round_idx}/checkpoint"):
                self.aggregator.save_round(str(ckpt_dir), round_idx)

    def train(self) -> Dict:
        t0 = time.time()
        rounds = int(getattr(self.args, "comm_round", 1))
        for r in range(rounds):
            self.train_one_round(r)
        wall = time.time() - t0
        final = self.test_history[-1] if self.test_history else {}
        return {
            "wall_clock_sec": wall,
            "rounds": rounds,
            "rounds_per_sec": rounds / max(wall, 1e-9),
            **final,
        }
