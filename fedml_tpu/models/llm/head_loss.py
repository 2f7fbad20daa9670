"""The head's product, the softmax cross-entropy and their gradient as ONE
function with its own VJP, walked in blocks of the vocabulary.

``[rows, V]`` logits in float32 are the largest array of a training step
(1.07 GB per 1,024 rows of a 262,272-row head), and the plain form keeps
several: the logits, their softmax gradient, and — because a ``gather``
of the target's logit transposes to a ``scatter`` — two relayouts of that
gradient. :func:`head_loss` never holds them whole. It walks the head in
equal blocks of the vocabulary; a block's logits live only inside its
step of the walk. Each block leaves its row maximum, its sum of
exponentials, the target's logit where the target lies in the block
(an iota compared with the label: no gather) and its share of the
gradient with respect to the hidden state, ``exp(logits − m) · E_blk``;
blocks merge the way the flash kernels merge key blocks, rescaling as the
maximum moves. So there are two products of the head's size a step
whatever the block count, and the head is read once by each. Where a mesh
cuts the vocabulary axis in shards, blocks are cut inside each shard (the
axis is viewed ``[shards, blocks, block]`` and the walk goes over the
middle one), so a block is every device's own rows and the walk gathers
nothing: what crosses the mesh is each block's row maxima and sums and,
once after the walk, the shards' partial sums of the second product. What is
subtracted for the target, ``E[y]``, is gathered at the end — except where
the head is one block: its sum is final before the second product, so the
one-hot goes into that product's operand, which is the plain form's
arithmetic with nothing gathered.

The gradient with respect to the hidden state is made in the forward rule
and only multiplied by the (scalar) cotangent in the backward rule. The
head's own gradient needs the finished log-sum-exp, so it is a second
walk (two more products); it runs only where the head is among the leaves
differentiated (``symbolic_zeros``: the rule sees which inputs are).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from fedml_tpu.telemetry import get_tracer

# bound on one block's float32 logits, the same for every model: under it
# the whole head is one block
_BLOCK_BYTES = 1 << 29


@dataclasses.dataclass(frozen=True)
class HeadInputs:
    """What a model hands the loss in place of logits.

    ``hidden`` is ``[..., H]`` after the final norm, in the compute type.
    ``head`` is the matrix as the model holds it: the tied ``[V, H]``
    embedding, the untied ``[H, V]`` ``lm_head``, or that leaf quantized.
    ``shards`` is the number of pieces the mesh cuts the vocabulary axis
    in, which the model does not know: who holds the mesh sets it.
    """

    hidden: jax.Array
    head: Any
    tied: bool
    shards: int = 1


def plan(rows: int, vocab: int, shards: int = 1) -> Tuple[int, int]:
    """``(blocks, block)``: the fewest equal blocks of a shard of the
    vocabulary (the whole of it on one device), each a multiple of 128
    wide, whose ``[rows, block]`` float32 logits fit ``_BLOCK_BYTES``.
    One block is the whole shard, whatever its width; a shard that no such
    count divides stays whole, which is the plain form's arithmetic."""
    width = vocab // shards
    if rows * width * 4 <= _BLOCK_BYTES:
        return 1, width
    for n in range(2, width // 128 + 1):
        if width % (128 * n) == 0 and rows * (width // n) * 4 <= _BLOCK_BYTES:
            return n, width // n
    return 1, width


_VOCAB = (-2, -1)  # a block's logits are [..., shards, block]


def _block_stats(logits, y, ids):
    """A block's row maximum, ``exp(logits − maximum)`` and its row sum,
    the target's logit where the target lies in the block (else 0), and
    where that is: the logits' vocabulary indices ``ids`` (an iota)
    compared with the label, in the pass that exponentiates."""
    m = jax.lax.stop_gradient(jnp.max(logits, axis=_VOCAB))
    p = jnp.exp(logits - m[..., None, None])
    hit = ids == y[..., None, None]
    return m, p, jnp.sum(p, axis=_VOCAB), jnp.sum(
        jnp.where(hit, logits, 0.0), axis=_VOCAB), hit


def _ids(logits, first, stride):
    """The vocabulary index of each logit of a block that starts at
    ``first`` of every shard, shards ``stride`` apart."""
    iota = partial(jax.lax.broadcasted_iota, jnp.int32, logits.shape)
    return stride * iota(logits.ndim - 2) + first + iota(logits.ndim - 1)


def _finish(m, s, target, w):
    """Summed loss and the weight of the rows whose target's logit is the
    row's maximum: ``argmax == y`` short of an exact tie, where every
    label that ties counts and ``argmax`` would count the first alone."""
    ce = m + jnp.log(s) - target
    return jnp.sum(ce * w), jnp.sum((target >= m).astype(jnp.float32) * w)


class _Walk:
    """The head cut in ``blocks`` along each shard of its vocabulary, for
    one call."""

    def __init__(self, h, head, tied, shards):
        self.h, self.tied = h, tied
        self.hidden = h.shape[-1]
        self.vocab = head.shape[0 if tied else 1]
        self.rows = math.prod(h.shape[:-1])
        # shards of unequal width are not cut along: blocks then cross them
        self.shards = shards if self.vocab % shards == 0 else 1
        self.width = self.vocab // self.shards
        self.blocks, self.block = plan(self.rows, self.vocab, self.shards)
        # the vocabulary axis as [shards, width]: a block is the same
        # columns of every shard
        self.head = head.reshape(
            (self.shards, self.width, self.hidden) if tied
            else (self.hidden, self.shards, self.width))
        v = "gvh" if tied else "hgv"
        # the second product keeps the shards apart: their partial sums
        # meet once, after the walk (``dh``), not once a block
        self.fwd, self.back = f"...h,{v}->...gv", f"...gv,{v}->g...h"
        self.wgrad = f"...gv,...h->{v}"

    def rows_of(self, j):
        """Block ``j`` of every shard of the head in the compute type."""
        e = self.head if self.blocks == 1 else jax.lax.dynamic_slice_in_dim(
            self.head, j * self.block, self.block, 1 if self.tied else 2)
        return e.astype(self.h.dtype)

    def logits(self, e):
        with jax.named_scope("lm_head"):
            return jnp.einsum(self.fwd, self.h, e,
                              preferred_element_type=jnp.float32)

    def ids(self, logits, j):
        return _ids(logits, j * self.block, self.width)

    def event(self, head_differentiated):
        get_tracer().event(
            "loss/plan", rows=self.rows, vocab=self.vocab,
            hidden=self.hidden, blocks=self.blocks, block=self.block,
            axis="vocab", shards=self.shards,
            logits_block_bytes=self.rows * self.shards * self.block * 4,
            head_differentiated=bool(head_differentiated))


def _walk(h, head, y, w, tied, shards, want_dh, head_differentiated=False):
    """``(loss, correct, dh, lse)``: one walk over the head's blocks.
    ``dh`` (``None`` unless asked for) is the summed loss's gradient with
    respect to ``h`` in float32, accumulated in the same pass; ``lse`` is
    each row's log-sum-exp."""
    walk = _Walk(h, head, tied, shards)
    walk.event(head_differentiated)
    whole = walk.blocks == 1

    def block(j):
        e = walk.rows_of(j)
        logits = walk.logits(e)
        with jax.named_scope("loss"):
            m, p, s, target, hit = _block_stats(
                logits, y, walk.ids(logits, j))
        if not want_dh:
            return m, s, target
        if whole:
            # the sum is final before the second product: the softmax
            # gradient itself is its operand, and nothing is gathered
            with jax.named_scope("loss"):
                p = p / s[..., None, None] - hit
        with jax.named_scope("lm_head"):
            # the one rounding of the softmax gradient: to the compute
            # type, as the operand of the second product
            acc = jnp.einsum(walk.back, p.astype(h.dtype), e,
                             preferred_element_type=jnp.float32)
        return m, s, target, acc

    def merge(j, carry):
        m0, s0, t0, *acc0 = carry
        m1, s1, t1, *acc1 = block(j)
        with jax.named_scope("loss"):
            m = jnp.maximum(m0, m1)
            a0, a1 = jnp.exp(m0 - m), jnp.exp(m1 - m)
            acc = [x0 * a0[..., None] + x1 * a1[..., None]
                   for x0, x1 in zip(acc0, acc1)]
            return (m, s0 * a0 + s1 * a1, t0 + t1, *acc)

    # the loop's own counter and slices book with the head, not nowhere
    with jax.named_scope("lm_head"):
        if whole:
            carry = block(0)
        else:
            # from the recurrence's neutral element, so block 0 is a turn of
            # the loop like the others: one body to trace, lower and compile
            rows = jnp.zeros(h.shape[:-1], jnp.float32)
            acc0 = [jnp.zeros((walk.shards, *h.shape), jnp.float32)]
            carry = jax.lax.fori_loop(
                0, walk.blocks, merge,
                (rows - jnp.inf, rows, rows, *(acc0 if want_dh else [])))
        m, s, target, *acc = carry
    with jax.named_scope("loss"):
        loss, correct = _finish(m, s, target, w)
        lse = m + jnp.log(s)
    if not want_dh:
        return loss, correct, None, lse
    with jax.named_scope("lm_head"):
        acc = jnp.sum(acc[0], axis=0)  # over the shards
    if whole:
        with jax.named_scope("loss"):
            return loss, correct, w[..., None] * acc, lse
    with jax.named_scope("lm_head"):
        # blocks were summed before their sum was final: the target's row
        # of the head comes off at the end (an untied head's columns: the
        # compiler keeps a transposed copy of the head for this gather)
        at = jnp.maximum(y, 0)  # a row of target -1 weighs 0
        picked = (head[at] if tied
                  else jnp.moveaxis(head[:, at], 0, -1)).astype(h.dtype)
    with jax.named_scope("loss"):
        dh = w[..., None] * (acc / s[..., None] - picked)
        return loss, correct, dh, lse


def _head_grad(h, head, y, w, tied, shards, lse):
    """The summed loss's gradient with respect to the head: a second walk,
    which makes each block's logits again under the finished log-sum-exp."""
    walk = _Walk(h, head, tied, shards)

    def block(j):
        logits = walk.logits(walk.rows_of(j))
        with jax.named_scope("loss"):
            hit = walk.ids(logits, j) == y[..., None, None]
            g = (jnp.exp(logits - lse[..., None, None])
                 - hit) * w[..., None, None]
        with jax.named_scope("lm_head"):
            return jnp.einsum(walk.wgrad, g.astype(h.dtype), h,
                              preferred_element_type=jnp.float32)

    with jax.named_scope("lm_head"):
        parts = jax.lax.map(block, jnp.arange(walk.blocks))
    # [blocks, shards, block, H] or [blocks, H, shards, block]: the blocks
    # go back between the shards and their columns
    return jnp.moveaxis(parts, 0, 1 if tied else 2).reshape(head.shape)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _head_loss(h, head, y, w, tied, shards):
    return _walk(h, head, y, w, tied, shards, want_dh=False)[:2]


def _head_loss_fwd(h, head, y, w, tied, shards):
    y, w = y.value, w.value  # the targets and row weights carry no gradient
    loss, correct, dh, lse = _walk(
        h.value, head.value, y, w, tied, shards, want_dh=True,
        head_differentiated=head.perturbed)
    dhead = (_head_grad(h.value, head.value, y, w, tied, shards, lse)
             if head.perturbed else None)
    # float32 until the cotangent has multiplied them: rounded once, to
    # the types these empty arrays carry
    types = [jnp.zeros((0,), x.value.dtype) for x in (h, head)]
    return (loss, correct), ((dh, dhead), types)


def _head_loss_bwd(tied, shards, saved, cts):
    grads, types = saved
    ct = cts[0]  # the count of correct rows is no function of the inputs
    if isinstance(ct, SymbolicZero):
        ct = jnp.zeros((), jnp.float32)
    with jax.named_scope("loss"):
        scaled = [None if g is None else (ct * g).astype(t.dtype)
                  for g, t in zip(grads, types)]
    return (*scaled, None, None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd, symbolic_zeros=True)


def head_loss(inputs: HeadInputs, y, w):
    """``(summed loss, weight of correct rows)`` of next-token targets
    ``y`` (``-1``: no target) under row weights ``w``, both shaped like
    ``inputs.hidden`` less its last axis. A row is correct where its
    target's logit is the row's maximum, so a target that ties it exactly
    counts (``argmax == y`` would count the first of the tied alone).

    A quantized head keeps its own product (``matmul_maybe_quantized``:
    the leaf is frozen and cannot be cut in blocks of the vocabulary);
    the loss over its whole logits is the same block arithmetic.
    """
    # imported here as the models do: ``ops.quant`` brings Pallas with it
    from fedml_tpu.ops.quant import (QuantizedTensor, QuantizedTensor4,
                                     matmul_maybe_quantized)

    if isinstance(inputs.head, (QuantizedTensor, QuantizedTensor4)):
        with jax.named_scope("lm_head"):
            logits = matmul_maybe_quantized(
                inputs.hidden, inputs.head, inputs.hidden.dtype
            ).astype(jnp.float32)
        with jax.named_scope("loss"):
            logits = logits[..., None, :]  # one shard, one block
            m, _, s, target, _ = _block_stats(
                logits, y, _ids(logits, 0, 0))
            return _finish(m, s, target, w)
    return _head_loss(inputs.hidden, inputs.head, y, w, inputs.tied,
                      inputs.shards)


def causal_lm_loss(apply_fn):
    """Next-token CE over a [B, T] token batch; mask is [B] sample validity.

    Matches the trainer contract in ``ml/trainer/local_sgd.py`` so the LLM
    drops into every federated engine unchanged: ``loss, (correct, denom,
    *stats)``, where ``correct`` counts the valid rows whose target's logit
    is the row's maximum (a target that ties it exactly counts; see
    ``head_loss``).
    """

    def loss_fn(params, x, y, mask):
        out = apply_fn(params, x)  # y: next tokens [B, T]
        # apply_fns return the model's HeadInputs (``head_inputs=True``);
        # MoE ones (HeadInputs, aux_loss) and, where the model counts
        # something a round, a dict of those counts
        head, aux, *stats = out if isinstance(out, tuple) else (out, 0.0)
        with jax.named_scope("loss"):
            valid = (y >= 0).astype(jnp.float32) * mask[:, None]
            denom = jnp.maximum(jnp.sum(valid), 1.0)
        # the product, the cross-entropy and their gradient in one
        total, correct = head_loss(head, y, valid)
        with jax.named_scope("loss"):
            return total / denom + aux, (correct, denom, *stats)

    return loss_fn
