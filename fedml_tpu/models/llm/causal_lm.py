"""The causal LM every family of the LLM path is: embedding, rotary tables,
a stack of the family's blocks (recomputed in the backward pass where the
configuration says so), final norm, head. A family is a configuration, a
block and the few lines that bind them to :class:`CausalLM` (``llama.py``,
``zaya.py``); one whose layers are of several kinds says which block layer
``i`` is (:meth:`CausalLM.layer_block`; ``nemotron_h.py``).

The block protocol::

    Block(cfg, name="layer_<i>")(x, carry, cos, sin, cache, attention_fn)
        -> (x, carry, cache, stats)

``x`` is the residual stream ``[B, T, hidden]``; ``carry`` what else the
family hands from layer to layer (:meth:`CausalLM.init_carry` makes the
first); ``cos`` / ``sin`` the rotary tables; ``cache`` layer ``i``'s entry
of ``kv_caches`` (a block that cannot serve raises on one);
``attention_fn`` the trainer's attention product
(``layers.causal_attention`` takes it); ``stats`` what the layer counted
(:meth:`CausalLM.layer_stats` says what is sown of them). Each is ``None``
where there is nothing: a ``None`` has no leaves, so it costs the traced
program nothing.
"""
from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.llm.head_loss import HeadInputs
from fedml_tpu.models.llm.layers import (RMSNorm, _maybe_packed_param,
                                         rope_tables)


class CausalLM(nn.Module):
    """Token ids [B, T] → logits [B, T, V] in float32.

    ``__call__(tokens)`` is the forward that serving, conversion and the
    parity tests read; ``head_inputs=True`` stops before the head's product
    and returns :class:`HeadInputs` (the final hidden state and the head's
    matrix), which is what the training loss takes; ``kv_caches`` threads
    an explicit cache a layer for serving (``fedml_tpu/serving``) and adds
    the new caches to what is returned.

    A family subclasses it under its own name (which is in every compiled
    operation's ``op_name``) and sets ``block``.
    """

    cfg: Any
    block = None  # the family's block class (the protocol above)

    @nn.nowrap
    def layer_block(self, i: int):
        """The block class of layer ``i``: ``block``, unless the family's
        layers are of several kinds and it says which one ``i`` is."""
        return self.block

    @nn.nowrap
    def init_carry(self, tokens):
        """What the first block is handed beside ``x``."""
        return None

    @nn.nowrap
    def layer_stats(self, stats: list) -> dict:
        """``{name: array}`` sown under ``intermediates`` from the layers'
        ``stats``, first layer first; the names are the configuration's
        ``round_stats``."""
        return {}

    @nn.compact
    def __call__(self, tokens, positions=None, kv_caches=None, attention_fn=None,
                 head_inputs=False):
        cfg = self.cfg
        emb = self.param(
            "embed_tokens",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.hidden_size),
            cfg.param_dtype,
        )
        with jax.named_scope("embed"):
            x = emb.astype(cfg.dtype)[tokens]
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        with jax.named_scope("rope"):
            cos, sin = rope_tables(positions, cfg.rotary_dim, cfg.rope_theta)
        carry = self.init_carry(tokens)

        blocks = {}  # a block class -> the class the layers are built from

        def built_from(block):
            if cfg.remat and cfg.remat_policy != "none" and kv_caches is None:
                policy = None  # "full": save only block inputs
                if cfg.remat_policy == "dots":
                    policy = (jax.checkpoint_policies
                              .dots_with_no_batch_dims_saveable)
                # argument 6 is ``attention_fn`` (0 is the module)
                return nn.remat(block, static_argnums=(6,), policy=policy)
            return block

        new_caches, stats = [], []
        for i in range(cfg.num_hidden_layers):
            block = self.layer_block(i)
            if block not in blocks:
                blocks[block] = built_from(block)
            cache_i = kv_caches[i] if kv_caches is not None else None
            x, carry, new_cache, layer = blocks[block](
                cfg, name=f"layer_{i}")(
                x, carry, cos, sin, cache_i, attention_fn
            )
            new_caches.append(new_cache)
            stats.append(layer)
        for name, value in self.layer_stats(stats).items():
            self.sow("intermediates", name, value)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        head = emb if cfg.tie_word_embeddings else _maybe_packed_param(
            self,
            "lm_head",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("embed", "vocab")
            ),
            (cfg.hidden_size, cfg.vocab_size),
            cfg.param_dtype,
        )
        if head_inputs:
            return HeadInputs(x, head, cfg.tie_word_embeddings)
        with jax.named_scope("lm_head"):
            # the product in the compute type, then float32
            if cfg.tie_word_embeddings:
                logits = x @ emb.astype(cfg.dtype).T
            else:
                from fedml_tpu.ops.quant import matmul_maybe_quantized

                logits = matmul_maybe_quantized(x, head, cfg.dtype)
            logits = logits.astype(jnp.float32)
        if kv_caches is not None:
            return logits, new_caches
        return logits
