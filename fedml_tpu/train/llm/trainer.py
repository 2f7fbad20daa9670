"""Sharded LLM trainer — HF-Trainer/DeepSpeed replaced by one jitted step.

Parity target: ``train/llm/hf_trainer.py:28`` (HFTrainer w/ checkpointing)
+ ``train/llm/distributed.py`` (ZeRO-3 helpers). TPU-native design:

- ONE compiled train step: grad-accumulation microbatches under
  ``lax.scan``, loss/grad in bf16 compute with fp32 masters, optimizer
  update — all inside the same XLA program, sharded over the
  (dp, fsdp, tp, sp) mesh from ``sharding.py``;
- LoRA fine-tuning differentiates ONLY the trainable flat dict (adapters
  + MoE router): the frozen base is a closure constant of the loss — no
  dead wgrads, and an int8 base (QLoRA, ``base_quantize: "int8"``)
  stays differentiable (reference: peft adapters,
  ``configurations.py:291``; the reference has no QLoRA);
- round-level checkpointing via orbax (SURVEY §5 flags this as an
  improvement over the reference, which has no FL-engine checkpointing).
"""
from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from fedml_tpu.models.llm.head_loss import causal_lm_loss
from fedml_tpu.train.llm.sharding import (
    batch_sharding,
    data_parallel_size,
    init_sharded_params,
    mesh_from_args,
    replicated,
)

logger = logging.getLogger(__name__)

Pytree = Any


def is_lora_path(path: Tuple) -> bool:
    return any("lora" in str(getattr(p, "key", p)) for p in path)


def is_trainable_path(path: Tuple) -> bool:
    """LoRA adapters + ``LlamaMoE``'s router, the leaf named ``router``
    (tiny, no LoRA twin, and the load-balance loss must be able to act on
    it). ``zaya``'s router MLP lives under ``moe/router_mlp`` and is NOT
    caught here: that model's rounds train the adapters alone."""
    return is_lora_path(path) or any(
        str(getattr(p, "key", p)) == "router" for p in path
    )


def extract_trainable(params: Pytree) -> dict:
    """Flat {key-path: leaf} dict of every TRAINED leaf (LoRA + router).

    The exchange payload stays :func:`extract_lora` (adapters only —
    router state is local, matching the reference's peft exchange); this
    wider set is what the optimizer differentiates and updates."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {_path_str(p): v for p, v in flat if is_trainable_path(p)}


def merge_trainable(params: Pytree, trained: dict) -> Pytree:
    return jax.tree_util.tree_map_with_path(
        lambda path, base: trained.get(_path_str(path), base), params
    )


def _path_str(path: Tuple) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def extract_lora(params: Pytree) -> dict:
    """The exchangeable state: a flat {key-path: leaf} dict of LoRA leaves.

    A flat dict (not a pruned pytree) so it serializes directly onto the
    federation transport — parity with the reference shipping peft adapter
    state dicts (``spotlight_prj/fedllm/run_fedllm.py:171-244``).
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {_path_str(p): v for p, v in flat if is_lora_path(p)}


def merge_lora(params: Pytree, lora: dict) -> Pytree:
    return jax.tree_util.tree_map_with_path(
        lambda path, base: lora.get(_path_str(path), base), params
    )


class LLMTrainer:
    """Compiled causal-LM fine-tuning over a named mesh.

    ``cfg`` is a model family's configuration object: the trainer asks it
    for its flax module (``cfg.module()``, a ``models/llm/causal_lm.py::
    CausalLM`` bound to the family's block), the weight of sown auxiliary
    losses (``cfg.aux_loss_weight``) and the names of the counts its module
    sows for a round (``cfg.round_stats``), and knows no model by name."""

    def __init__(self, cfg: Any, args: Any, mesh=None):
        self.cfg = cfg
        self.args = args
        self.model = cfg.module()
        self.mesh = mesh if mesh is not None else mesh_from_args(args)
        self.seq_len = int(getattr(args, "max_seq_length", 512))
        # per_device_batch_size is PER DEVICE: every [B, T] batch is split
        # over the mesh's (dp, fsdp) axes, so the global batch this engine
        # builds, samples and evaluates with is it x dp*fsdp — B=1 means
        # one sequence per chip on one chip and on four
        self.batch_size = int(getattr(
            args, "per_device_batch_size",
            getattr(args, "batch_size", 8))) * data_parallel_size(self.mesh)
        self.accum = int(getattr(args, "gradient_accumulation_steps", 1))
        self.lora_only = cfg.lora_rank > 0

        lr = float(getattr(args, "learning_rate", 1e-4))
        wd = float(getattr(args, "weight_decay", 0.0))
        warmup = int(getattr(args, "warmup_steps", 0))
        max_steps = int(getattr(args, "max_steps", 1000))
        if warmup > 0:
            sched = optax.warmup_cosine_decay_schedule(
                0.0, lr, warmup, max(max_steps, warmup + 1)
            )
        else:
            sched = lr
        base_tx = optax.chain(
            optax.clip_by_global_norm(float(getattr(args, "max_grad_norm", 1.0))),
            optax.adamw(sched, weight_decay=wd),
        )
        # one optimizer for both modes. Under LoRA the train step
        # differentiates ONLY the trainable flat dict (extract_trainable)
        # and the optimizer runs on that dict — frozen base weights never
        # see a gradient, which both drops the reliance on XLA DCE'ing
        # 13.5 GB of dead wgrads and is what makes an int8-quantized base
        # (QLoRA) differentiable at all (jax.grad refuses int8 inputs).
        self.tx = base_tx
        # QLoRA: store the frozen base quantized — per-channel int8
        # (ops/quant.quantize_int8, 6.9 GB instead of 13.5 at 7B) or
        # blockwise 4-bit int4/nf4 (ops/quant.quantize_int4, ~3.6 GB) —
        # which frees HBM for real batch sizes; matmuls dequantize inside
        # the fused round program (the dequantized tile is an XLA
        # temporary — a full-precision base is never materialized).
        # Requires LoRA (the base must be frozen: integer leaves carry no
        # gradient).
        self.base_quantize = str(
            getattr(args, "base_quantize", "") or "").lower()
        if self.base_quantize and self.base_quantize not in (
                "int8", "int4", "nf4"):
            raise ValueError(
                f"base_quantize={self.base_quantize!r}: must be one of "
                "'int8', 'int4', 'nf4'")
        if self.base_quantize and not self.lora_only:
            raise ValueError(
                "base_quantize requires lora_rank > 0 (QLoRA trains "
                "adapters over a frozen quantized base)")

        import flax.linen as nn

        from fedml_tpu.train.llm.sharding import LOGICAL_RULES

        # sequence parallelism: when the mesh has an sp axis, attention runs
        # as an explicit ring over the ICI instead of GSPMD's all-gather.
        # Otherwise the flash kernel runs per shard of the axes batch and
        # heads already ride (GSPMD cannot partition a Mosaic call itself).
        attention_fn = None
        sp_size = dict(zip(self.mesh.axis_names, self.mesh.devices.shape)).get("sp", 1)
        if sp_size > 1 and bool(getattr(args, "use_ring_attention", True)):
            from fedml_tpu.parallel.ring_attention import make_ring_attention_fn

            attention_fn = make_ring_attention_fn(self.mesh, "sp", causal=True)
        elif cfg.use_flash:
            from fedml_tpu.ops.flash_attention import (
                make_sharded_flash_attention,
            )

            rules = dict(LOGICAL_RULES)
            attention_fn = make_sharded_flash_attention(
                self.mesh, rules["batch"], rules["heads"])

        aux_w = float(cfg.aux_loss_weight)
        stat_names = tuple(cfg.round_stats)

        # the compiled programs outlive this object in the process-wide
        # catalog: their closures hold the (param-free) module, never
        # ``self`` — or every trainer's whole params tree would stay
        # resident until the next one re-registers the program names
        model = self.model
        # the mesh cuts the head's vocabulary axis; the loss cuts its
        # blocks inside each shard (models/llm/head_loss.py)
        vocab_shards = self.mesh.shape[dict(LOGICAL_RULES)["vocab"]]

        def head_inputs(p, x, **kwargs):
            out = model.apply(p, x, attention_fn=attention_fn,
                              head_inputs=True, **kwargs)
            head, *state = out if kwargs else (out,)
            return (dataclasses.replace(head, shards=vocab_shards), *state)

        def apply_fn(p, x):
            # activation constraints inside the model resolve against these
            # logical→mesh rules (otherwise they are silent no-ops)
            with nn.logical_axis_rules(LOGICAL_RULES):
                if not aux_w and not stat_names:
                    return head_inputs(p, x)[0]
                head, state = head_inputs(p, x, mutable=["intermediates"])
                sown = dict(state["intermediates"])
                # counts the module sows once, at its top (a tuple of one)
                stats = {k: sown.pop(k)[0] for k in stat_names}
                # what is left is each layer's sown load-balance term:
                # without the aux pressure in the objective a trained
                # router collapses
                auxes = jax.tree.leaves(sown)
                aux = aux_w * sum(auxes) / max(len(auxes), 1)
                return (head, aux, stats) if stats else (head, aux)

        self._loss_fn = causal_lm_loss(apply_fn)

        def eval_apply_fn(p, x):
            # evaluation reports PURE cross-entropy: no aux regularizer, so
            # perplexity and dense-baseline comparisons stay meaningful
            with nn.logical_axis_rules(LOGICAL_RULES):
                return head_inputs(p, x)[0]

        self._eval_loss_fn = causal_lm_loss(eval_apply_fn)
        self._train_step = None  # compiled lazily once shardings exist
        self.params = None
        self.opt_state = None
        self._step = 0

    # -- init -------------------------------------------------------------
    def init(self, seed: int = 0, zeros: bool = False):
        """``zeros=True``: sharded zero params (dryrun fast path — see
        ``init_sharded_params``)."""
        sample = jnp.zeros((self.batch_size, self.seq_len), jnp.int32)
        self.params, self.shardings = init_sharded_params(
            self.model, sample, self.mesh, seed=seed, zeros=zeros
        )
        if self.base_quantize:
            self._quantize_base()
        if self.lora_only:
            self.opt_state = jax.jit(self.tx.init)(
                extract_trainable(self.params))
        else:
            self.opt_state = jax.jit(self.tx.init)(self.params)
        self._compile()
        return self.params

    def _quantize_base(self) -> None:
        from fedml_tpu.ops.quant import (QuantizedTensor, QuantizedTensor4,
                                         quantize_params_int4,
                                         quantize_params_int8)

        # donate: at 7B the full-precision source and the quantized twin
        # can't both be resident; each kernel's buffer dies as its twin
        # lands
        min_size = int(getattr(self.args, "base_quantize_min_size", 65536))
        if self.base_quantize in ("int4", "nf4"):
            self.params = quantize_params_int4(
                self.params, fmt=self.base_quantize, donate=True,
                min_size=min_size,
                block=int(getattr(self.args, "base_quantize_block", 64)))
        else:
            self.params = quantize_params_int8(
                self.params, mode="dequant", donate=True, min_size=min_size)
        # rebuild the shardings tree to the new structure: quantized data
        # / scale inherit the source kernel's layout through the jnp
        # quantization ops (ZeRO-sharded quantized base), so record what
        # the arrays actually carry; non-quantized leaves keep their
        # original NamedShardings.
        old = {_path_str(p): s for p, s in
               jax.tree_util.tree_flatten_with_path(self.shardings)[0]}

        def _shard_of(path, leaf):
            if isinstance(leaf, QuantizedTensor4):
                return QuantizedTensor4(
                    leaf.data.sharding, leaf.scale.sharding,
                    leaf.orig_shape, fmt=leaf.fmt, block=leaf.block)
            if isinstance(leaf, QuantizedTensor):
                return QuantizedTensor(leaf.data.sharding,
                                       leaf.scale.sharding, leaf.mode)
            return old[_path_str(path)]

        self.shardings = jax.tree_util.tree_map_with_path(
            _shard_of, self.params,
            is_leaf=lambda x: isinstance(
                x, (QuantizedTensor, QuantizedTensor4)),
        )

    def _compile(self):
        loss_fn = self._loss_fn
        tx = self.tx
        lora_only = self.lora_only

        def train_step(params, opt_state, xs, ys, mask):
            """xs/ys: [n_micro, B, T]; mask: [n_micro, B].

            LoRA mode differentiates only the trainable flat dict
            (adapters + router): the frozen base — possibly int8 — rides
            through as a closure constant of the loss."""
            n_micro = xs.shape[0]  # static at trace time
            wrt = extract_trainable(params) if lora_only else params

            def micro(carry, batch):
                grads_acc, loss_acc = carry
                x, y, m = batch

                def loss_of(t):
                    p = merge_trainable(params, t) if lora_only else t
                    return loss_fn(p, x, y, m)

                (loss, _), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(wrt)
                grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
                return (grads_acc, loss_acc + loss), None

            zero = jax.tree.map(jnp.zeros_like, wrt)
            (grads, loss_sum), _ = jax.lax.scan(micro, (zero, 0.0), (xs, ys, mask))
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            updates, opt_state = tx.update(grads, opt_state, wrt)
            new = optax.apply_updates(wrt, updates)
            params = merge_trainable(params, new) if lora_only else new
            return params, opt_state, loss_sum / n_micro

        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        # inputs are [accum, B, ...]: the *batch* dim rides (dp, fsdp)
        micro_spec = NamedSharding(self.mesh, P(None, ("dp", "fsdp")))
        self._micro_spec = micro_spec
        # cataloged: the LLM hot step — bench.py reads its XLA-cost FLOPs
        # (mfu_source "xla") straight off the catalog record
        from fedml_tpu.telemetry.profiling import wrap_jit

        self._train_step = wrap_jit("llm/train_step", jax.jit(
            train_step,
            in_shardings=(self.shardings, None, micro_spec, micro_spec, micro_spec),
            out_shardings=(self.shardings, None, replicated(self.mesh)),
            donate_argnums=(0, 1),
        ))

        eval_loss_fn = self._eval_loss_fn

        def eval_step(params, x, y, m):
            loss, (correct, denom) = eval_loss_fn(params, x, y, m)
            return loss, correct, denom

        eval_spec = batch_sharding(self.mesh)
        self._eval_spec = eval_spec
        self._eval_step = wrap_jit("llm/eval_step", jax.jit(
            eval_step,
            in_shardings=(self.shardings, eval_spec, eval_spec, eval_spec),
        ), multi_shape=True)
        # built once: a fresh lambda per exchange_state() call would miss
        # the jit cache and recompile the all-gather every round
        self._gather = jax.jit(lambda t: t,
                               out_shardings=replicated(self.mesh))

    # -- stepping ---------------------------------------------------------
    def _put(self, x, spec, dtype=None):
        """Host batch → globally sharded device array.

        ``device_put`` (not ``jnp.asarray``) so the path also works when
        the mesh spans multiple *processes* (multi-host silo over DCN):
        every process passes the identical host array and receives only
        its addressable shards — numpy straight into a jit with
        non-trivial shardings is rejected by JAX in that regime.
        """
        return jax.device_put(np.asarray(x, dtype), spec)

    def step(self, xs, ys, mask) -> float:
        """One optimizer step over [accum, B, T] token microbatches."""
        xs, ys, mask = np.asarray(xs), np.asarray(ys), np.asarray(mask)
        if xs.ndim == 2:  # single microbatch convenience
            xs, ys = xs[None], ys[None]
            mask = mask[None]
        self.params, self.opt_state, loss = self._train_step(
            self.params, self.opt_state,
            self._put(xs, self._micro_spec),
            self._put(ys, self._micro_spec),
            self._put(mask, self._micro_spec, np.float32),
        )
        self._step += 1
        return float(loss)

    def evaluate(self, x, y) -> dict:
        m = self._put(np.ones((np.shape(x)[0],)), self._eval_spec, np.float32)
        loss, correct, denom = self._eval_step(
            self.params, self._put(x, self._eval_spec),
            self._put(y, self._eval_spec), m
        )
        return {
            "eval_loss": float(loss),
            "eval_acc": float(correct) / max(float(denom), 1.0),
        }

    # -- federation exchange (multi-host safe) ----------------------------
    def exchange_state(self):
        """The federated-exchange payload (LoRA dict, or full params) as
        fresh buffers safe to ship.

        Single-process: on-device copies (the sp fast path — no host
        round-trip). Multi-process silo (mesh over DCN): leaves are
        sharded across processes and NOT fully addressable, so a compiled
        all-gather replicates them first and host numpy is returned —
        every process then holds the identical payload, and only the
        silo's rank-0 hands it to the federation transport.
        """
        payload = extract_lora(self.params) if self.lora_only else self.params
        if jax.process_count() == 1:
            return jax.tree.map(jnp.copy, payload)
        full = self._gather(payload)
        return jax.tree.map(lambda a: np.asarray(a.addressable_data(0)), full)

    def load_exchange_state(self, exchanged) -> None:
        """Merge an exchange payload back into the live (sharded) params.

        Every leaf is re-laid-out onto its NamedSharding via
        ``device_put`` — required in the multi-process regime (host
        leaves can't enter a jit with non-trivial shardings) and a fresh
        buffer either way (the train step DONATES params, so merged
        state must never alias the caller's arrays).
        """
        if self.lora_only:
            merged = merge_lora(self.params, dict(exchanged))
        else:
            merged = exchanged

        def _relay(v, live, s):
            if v is live:
                # untouched live leaf (the frozen base in LoRA mode):
                # keep it — copying would transiently double HBM for the
                # whole frozen model every round
                return v
            if isinstance(v, jax.Array) and v.sharding.is_equivalent_to(
                    s, v.ndim):
                return jnp.copy(v)  # keeps sharding; no host round-trip
            return jax.device_put(np.asarray(v), s)

        self.params = jax.tree.map(_relay, merged, self.params,
                                   self.shardings)

    # -- on-device federated round ----------------------------------------
    def lane_opt_state(self, client_parallel: int):
        """Per-lane optimizer state for the client-parallel round.

        The sequential round threads ONE optimizer state through all
        clients; with ``client_parallel`` lanes running concurrently on
        the mesh's ``dp`` axis that threading must break — each lane
        owns its own (tiny, adapters-only) state, stacked on a leading
        lane axis and sharded ``P("dp")`` so lane ``i``'s state lives
        with lane ``i``'s compute. Returns ``(opt_states, shardings)``.
        """
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        cp = int(client_parallel)
        stacked = jax.tree.map(
            lambda v: jnp.stack([v] * cp), self.opt_state)
        shardings = jax.tree.map(
            lambda v: NamedSharding(self.mesh, P("dp")), stacked)
        return jax.device_put(stacked, shardings), shardings

    def compile_federated_round_cp(self, n_clients: int, local_steps: int,
                                   client_parallel: int):
        """The fused round with client slots data-parallel on ``dp``.

        The multichip form of :meth:`compile_federated_round`: the
        ``n_clients`` are folded into ``[groups, cp]`` and each group's
        ``cp`` lanes train CONCURRENTLY across the mesh's ``dp`` axis —
        every lane client-switches to the round's global adapters, runs
        its ``local_steps`` under ``lax.scan``, and the count-weighted
        adapter FedAvg contracts over the lane axis (XLA inserts the
        one dp all-reduce of the tiny LoRA dict; the frozen base stays
        fsdp-sharded and dp-replicated, never gathered). Still ONE
        donated-buffer XLA program; the host touches nothing between
        clients.

        Semantics vs the sequential round: identical client-switch and
        FedAvg math, but optimizer state is PER LANE (see
        :meth:`lane_opt_state`) — concurrent clients cannot thread one
        adam state, exactly as real cross-silo clients never shared
        one. Returns ``fed_round(params, opt_states, global_lora, xs,
        ys, ms, weights)`` with ``xs``/``ys``: ``[groups, cp,
        local_steps, B, T]``, ``ms``: ``[groups, cp, local_steps, B]``,
        ``weights``: ``[groups, cp]``; ``params``, ``opt_states`` and
        ``global_lora`` are donated.
        """
        if not self.lora_only:
            raise ValueError(
                "compile_federated_round_cp requires a LoRA model")
        cp = int(client_parallel)
        mesh_axes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        dp_size = int(mesh_axes.get("dp", 1))
        if cp != dp_size:
            raise ValueError(
                f"client_parallel={cp} must equal the mesh dp axis "
                f"({dp_size}) — lanes ride dp")
        if int(n_clients) % cp:
            raise ValueError(
                f"n_clients={n_clients} must divide into client_parallel="
                f"{cp} lanes")
        loss_fn = self._loss_fn
        tx = self.tx

        def fed_round(params, opt_states, global_lora, xs, ys, ms, weights):
            def group(carry, inp):
                opt_states, acc = carry
                x_g, y_g, m_g, w_g = inp

                def lane(o, x_c, y_c, m_c):
                    with jax.named_scope("client_switch"):
                        p = merge_lora(params, global_lora)

                    def local(c, batch):
                        p_c, o_c = c
                        x, y, m = batch
                        wrt = extract_trainable(p_c)

                        def loss_of(t):
                            return loss_fn(merge_trainable(p_c, t), x, y, m)

                        (loss, _), grads = jax.value_and_grad(
                            loss_of, has_aux=True)(wrt)
                        with jax.named_scope("optimizer"):
                            updates, o_c = tx.update(grads, o_c, wrt)
                            p_c = merge_trainable(
                                p_c, optax.apply_updates(wrt, updates))
                        return (p_c, o_c), loss

                    (p, o), losses = jax.lax.scan(
                        local, (p, o), (x_c, y_c, m_c))
                    return o, extract_lora(p), jnp.mean(losses)

                opt_states, loras, losses = jax.vmap(lane)(
                    opt_states, x_g, y_g, m_g)
                # contraction over the lane axis IS the FedAvg partial
                # sum — the only cross-lane (dp) communication in the
                # round, and it moves adapters, not the base
                with jax.named_scope("fedavg"):
                    acc = jax.tree.map(
                        lambda a, l: a + jnp.einsum(
                            "c,c...->...", w_g, l.astype(jnp.float32)),
                        acc, loras)
                return (opt_states, acc), jnp.mean(losses)

            acc0 = jax.tree.map(
                lambda v: jnp.zeros(v.shape, jnp.float32), global_lora)
            (opt_states, acc), losses = jax.lax.scan(
                group, (opt_states, acc0), (xs, ys, ms, weights))
            with jax.named_scope("fedavg"):
                wsum = jnp.sum(weights)
                new_global = jax.tree.map(
                    lambda a, g: (a / wsum).astype(g.dtype), acc, global_lora)
            return params, opt_states, new_global, jnp.mean(losses)

        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        lora_shardings = extract_lora(self.shardings)
        opt_shardings = jax.tree.map(
            lambda v: NamedSharding(self.mesh, P("dp")), self.opt_state)
        # lanes on dp, batch on fsdp (ZeRO data sharding), steps/tokens whole
        data_spec = NamedSharding(self.mesh, P(None, "dp", None, "fsdp"))
        w_spec = NamedSharding(self.mesh, P(None, "dp"))
        rep = replicated(self.mesh)
        from fedml_tpu.telemetry.profiling import wrap_jit

        return wrap_jit("llm/fused_round_cp", jax.jit(
            fed_round,
            in_shardings=(self.shardings, opt_shardings, lora_shardings,
                          data_spec, data_spec, data_spec, w_spec),
            out_shardings=(self.shardings, opt_shardings, lora_shardings,
                           rep),
            donate_argnums=(0, 1, 2),
        ), multi_shape=True)

    def compile_federated_round(self, n_clients: int, local_steps: int):
        """Compile an ENTIRE federated LoRA round into one XLA program.

        Replaces the host loop the reference runs round-by-round
        (``cross_silo/server/fedml_server_manager.py:174-252``: receive →
        merge → local steps → extract → FedAvg) with a single jitted
        function — client-switch (LoRA reset to the global adapters),
        ``local_steps`` optimizer steps per client under ``lax.scan``, and
        the count-weighted FedAvg of the resulting adapters all happen on
        device with donated buffers. No pytree flatten/unflatten or host
        numpy runs between device steps, so the round throughput is set by
        the chip, not the host Python interpreter (round-4 bench lost ~22%
        of rounds/s to the host-side merge on a 1-core box).

        Returns ``fed_round(params, opt_state, global_lora, xs, ys, ms,
        weights) -> (params, opt_state, new_global_lora, mean_loss)`` — and,
        for a model whose configuration names ``round_stats``, a fifth
        output: the dict of those counts summed over the round's clients
        and steps (``zaya``: ``moe_tokens`` ``[layers, experts]``) — with
        ``xs``/``ys``: ``[n_clients, local_steps, B, T]`` token batches,
        ``ms``: ``[n_clients, local_steps, B]`` masks, ``weights``:
        ``[n_clients]`` aggregation weights (normalized internally, same
        math as ``FedMLAggOperator.agg_with_weights``). ``params``,
        ``opt_state`` and ``global_lora`` are DONATED: chain rounds by
        feeding each round's outputs straight back in.
        """
        if not self.lora_only:
            raise ValueError(
                "compile_federated_round requires a LoRA model (the frozen "
                "base rides inside the program; full-param exchange would "
                "double HBM)")
        loss_fn = self._loss_fn
        tx = self.tx

        def fed_round(params, opt_state, global_lora, xs, ys, ms, weights):
            def client(carry, inp):
                params, opt_state, acc = carry
                x_c, y_c, m_c, w = inp
                # client-switch: reset adapters to the round's global state
                # (tree surgery: it lowers to no operation of its own today,
                # so the scope names only what a later change adds here)
                with jax.named_scope("client_switch"):
                    params = merge_lora(params, global_lora)

                def local(c, batch):
                    p, o = c
                    x, y, m = batch
                    wrt = extract_trainable(p)

                    def loss_of(t):
                        return loss_fn(merge_trainable(p, t), x, y, m)

                    (loss, (_, _, *stats)), grads = jax.value_and_grad(
                        loss_of, has_aux=True)(wrt)
                    with jax.named_scope("optimizer"):
                        updates, o = tx.update(grads, o, wrt)
                        p = merge_trainable(
                            p, optax.apply_updates(wrt, updates))
                    return (p, o), (loss, stats)

                (params, opt_state), (losses, stats) = jax.lax.scan(
                    local, (params, opt_state), (x_c, y_c, m_c))
                with jax.named_scope("fedavg"):
                    lora = extract_lora(params)
                    acc = jax.tree.map(
                        lambda a, l: a + w * l.astype(jnp.float32), acc, lora)
                return (params, opt_state, acc), (jnp.mean(losses),
                                                  over_steps(stats))

            over_steps = lambda tree: jax.tree.map(lambda s: s.sum(0), tree)
            acc0 = jax.tree.map(
                lambda v: jnp.zeros(v.shape, jnp.float32), global_lora)
            (params, opt_state, acc), (losses, stats) = jax.lax.scan(
                client, (params, opt_state, acc0), (xs, ys, ms, weights))
            with jax.named_scope("fedavg"):
                wsum = jnp.sum(weights)
                new_global = jax.tree.map(
                    lambda a, g: (a / wsum).astype(g.dtype), acc, global_lora)
            # params keep the LAST client's adapters — the next round's
            # client-switch overwrites them with new_global anyway, and
            # emitting the same value as two outputs (params leaf + global
            # leaf) would break donation aliasing; callers needing live
            # params to hold the aggregate use load_exchange_state
            out = (params, opt_state, new_global, jnp.mean(losses))
            return out + tuple(over_steps(stats))

        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        lora_shardings = extract_lora(self.shardings)
        # pin opt state to its live shardings on BOTH sides: donated
        # buffers must alias, and leaving the output to GSPMD lets it
        # pick a different axis than the input holds (the 4-bit packed
        # base perturbs propagation enough to surface this), which is a
        # runtime size mismatch on the alias
        rep = replicated(self.mesh)

        def _opt_shard(v):
            s = getattr(v, "sharding", None)
            if isinstance(s, NamedSharding) and s.mesh == self.mesh:
                return s
            return rep  # scalars (adam count) live on one device

        opt_shardings = jax.tree.map(_opt_shard, self.opt_state)
        data_spec = NamedSharding(self.mesh, P(None, None, ("dp", "fsdp")))
        from fedml_tpu.telemetry.profiling import wrap_jit

        return wrap_jit("llm/fused_round", jax.jit(
            fed_round,
            in_shardings=(self.shardings, opt_shardings, lora_shardings,
                          data_spec, data_spec, data_spec, rep),
            out_shardings=(self.shardings, opt_shardings, lora_shardings,
                           rep) + (rep,) * bool(self.cfg.round_stats),
            donate_argnums=(0, 1, 2),
        ), multi_shape=True)

    # -- checkpointing (orbax) -------------------------------------------
    def save_checkpoint(self, ckpt_dir: str, round_idx: int):
        import orbax.checkpoint as ocp

        path = os.path.abspath(os.path.join(ckpt_dir, f"round_{round_idx}"))
        ckptr = ocp.StandardCheckpointer()
        payload = extract_lora(self.params) if self.lora_only else self.params
        ckptr.save(path, payload, force=True)
        ckptr.wait_until_finished()
        logger.info("saved %s checkpoint → %s", "LoRA" if self.lora_only else "full", path)
        return path

    def load_checkpoint(self, path: str):
        self.params = restore_checkpoint_into(
            self.params, path, lora_only=self.lora_only)
        return self.params


def restore_checkpoint_into(params: Pytree, path: str,
                            lora_only: bool) -> Pytree:
    """Restore a round checkpoint (``save_checkpoint`` format) into a
    params tree — LoRA-only payloads merge into the given base; full
    payloads replace it. Also the serving path (`serve --checkpoint`)."""
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    if lora_only:
        template = jax.tree.map(np.asarray, extract_lora(params))
        restored = ckptr.restore(os.path.abspath(path), template)
        return merge_lora(params, restored)
    template = jax.tree.map(np.asarray, params)
    return ckptr.restore(os.path.abspath(path), template)
