"""The plain reference of one federated LoRA round.

Imports nothing of the program and takes nothing the program made: the
weights come again from ``weights.py`` and the seed, the rows from
``data.py``. Plain jax.numpy in float32, no flax, no optax, no Pallas.
Its matrix products run at ``Precision.HIGH`` (below, why); ``precision=
"float32_highest"`` runs them at ``HIGHEST``, which PERF.md reads once
against ``HIGH`` to show that nothing compared here sees the difference.
It runs layer by layer (each layer's input is kept, its backward
recomputes the layer) so that it fits beside nothing else on one chip, and
attention runs over a few heads at a time.

What it states, in order: every selected client starts from the round's
global adapters; ``local_steps`` steps of AdamW (b1 0.9, b2 0.999, eps
1e-8) after a clip of the global gradient norm, on ONE optimizer state
threaded through all clients and rounds; the loss is the mean next-token
cross-entropy over all positions; the new global adapters are the
shard-size-weighted mean of the clients' adapters; the round's loss is the
mean over clients of the mean over steps.

``precision`` puts a lower precision in the program's place (the control
of the comparison): ``bfloat16`` or ``fp8`` round both operands of every
matrix product, forward and backward (``fp8`` = e4m3 with one scale per
tensor); accumulation stays float32. ``fault`` plants a fault of the round.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import data as data_mod
from . import weights as weights_mod

# float32 operands in three bfloat16 passes: error ~2^-17 per product,
# 250x below the bfloat16 (2^-9) it is the reference for, at half of the
# time of ``HIGHEST`` (six passes): every run of every check pays it
PASSES = {"float32_highest": jax.lax.Precision.HIGHEST}
DEFAULT_PASSES = jax.lax.Precision.HIGH
B1, B2, EPS = 0.9, 0.999, 1e-8
HEADS_AT_A_TIME = 8


def _rounded(x, precision: str):
    x = x.astype(jnp.float32)
    if precision in ("float32", "float32_highest"):
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.max(jnp.abs(x)) / 448.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mm(a, b, precision):
    """``a @ b`` over the last two axes, operands rounded to ``precision``."""
    return jnp.matmul(_rounded(a, precision), _rounded(b, precision),
                      precision=PASSES.get(precision, DEFAULT_PASSES))


def _mm_fwd(a, b, precision):
    return mm(a, b, precision), (a, b)


def _mm_bwd(precision, res, dy):
    a, b = res
    passes = PASSES.get(precision, DEFAULT_PASSES)
    dy_r = _rounded(dy, precision)
    da = jnp.matmul(dy_r, jnp.swapaxes(_rounded(b, precision), -1, -2),
                    precision=passes)
    db = jnp.matmul(jnp.swapaxes(_rounded(a, precision), -1, -2), dy_r,
                    precision=passes)
    # a weight shared over the batch: sum what the batch axes broadcast
    while db.ndim > b.ndim:
        db = db.sum(0)
    while da.ndim > a.ndim:
        da = da.sum(0)
    return da.astype(a.dtype), db.astype(b.dtype)


mm.defvjp(_mm_fwd, _mm_bwd)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotary embedding over ``[B, H, T, D]``, halves convention."""
    t, d = x.shape[-2], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, precision):
    """Causal softmax attention, ``[N, T, D]`` each, a few heads at a time."""
    n, t, d = q.shape
    group = min(HEADS_AT_A_TIME, n)
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def some_heads(qkv):
        qh, kh, vh = qkv
        s = mm(qh, jnp.swapaxes(kh, -1, -2), precision) * (d ** -0.5)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return mm(p, vh, precision)

    split = lambda x: x.reshape(n // group, group, t, d)
    return jax.lax.map(some_heads, (split(q), split(k), split(v))).reshape(
        n, t, d)


class Reference:
    """One cell's model, data and optimizer, from the seed."""

    def __init__(self, seed: int, config: dict, traffic: dict,
                 precision: str = "float32", fault: str = ""):
        self.seed, self.cfg, self.traffic = int(seed), config, traffic
        self.precision, self.fault = precision, fault
        self.clients = data_mod.make_clients(seed, config["vocab_size"], traffic)
        self.top = weights_mod.make_top(config, seed)
        self.base, self.lora = [], []
        for i in range(config["num_hidden_layers"]):
            layer = weights_mod.make_layer(config, seed, i)
            self.base.append({k: v for k, v in layer.items()
                              if not weights_mod.is_lora(k)})
            self.lora.append({k: v for k, v in layer.items()
                              if weights_mod.is_lora(k)})
        self.init_lora = jax.tree.map(np.asarray, self.lora)
        zeros = lambda: jax.tree.map(jnp.zeros_like, self.lora)
        self.mu, self.nu, self.count = zeros(), zeros(), 0
        self.grad_sq = None  # per leaf: sum over steps of |raw gradient|^2
        self.steps = 0
        self._layer_fwd = jax.jit(self._layer)
        self._layer_bwd = jax.jit(self._layer_back)
        self._head_grad = jax.jit(jax.value_and_grad(self._head))
        self._adam = jax.jit(self._adam_step)

    # -- the model ---------------------------------------------------------
    def _dense(self, x, base, lora, name):
        y = mm(x, base[f"{name}/kernel"], self.precision)
        a = lora.get(f"{name}/lora_a")
        if a is not None:
            run = self.cfg["run"]
            y = y + mm(mm(x, a, self.precision), lora[f"{name}/lora_b"],
                       self.precision) * (run["lora_alpha"] / run["lora_rank"])
        return y

    def _layer(self, x, base, lora):
        cfg = self.cfg
        b, t, hid = x.shape
        h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = hid // h
        y = rms_norm(x, base["input_norm/scale"], cfg["rms_norm_eps"])
        heads = lambda z, n: z.reshape(b, t, n, d).transpose(0, 2, 1, 3)
        q = rope(heads(self._dense(y, base, lora, "attn/q_proj"), h),
                 cfg["rope_theta"])
        k = rope(heads(self._dense(y, base, lora, "attn/k_proj"), hkv),
                 cfg["rope_theta"])
        v = heads(self._dense(y, base, lora, "attn/v_proj"), hkv)
        k, v = (jnp.repeat(z, h // hkv, axis=1) for z in (k, v))
        flat = lambda z: z.reshape(b * h, t, d)
        o = attention(flat(q), flat(k), flat(v), self.precision)
        o = o.reshape(b, h, t, d).transpose(0, 2, 1, 3).reshape(b, t, hid)
        x = x + self._dense(o, base, lora, "attn/o_proj")
        y = rms_norm(x, base["post_attn_norm/scale"], cfg["rms_norm_eps"])
        gate = self._dense(y, base, {}, "mlp/gate_proj")
        up = self._dense(y, base, {}, "mlp/up_proj")
        return x + mm(jax.nn.silu(gate) * up, base["mlp/down_proj/kernel"],
                      self.precision)

    def _layer_back(self, x, base, lora, dy):
        _, vjp = jax.vjp(lambda x_, l_: self._layer(x_, base, l_), x, lora)
        return vjp(dy)

    def _head(self, x, top, targets):
        x = rms_norm(x, top["final_norm/scale"], self.cfg["rms_norm_eps"])
        if self.cfg["tie_word_embeddings"]:
            head = jnp.swapaxes(top["embed_tokens"], 0, 1)
        else:
            head = top["lm_head"]
        logits = mm(x, head, self.precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    def loss_and_grads(self, lora, tokens, targets):
        x = self.top["embed_tokens"][tokens].astype(jnp.float32)
        inputs = []
        for base, adapters in zip(self.base, lora):
            inputs.append(x)
            x = self._layer_fwd(x, base, adapters)
        loss, dx = self._head_grad(x, self.top, targets)
        grads = [None] * len(lora)
        for i in reversed(range(len(lora))):
            dx, grads[i] = self._layer_bwd(inputs[i], self.base[i], lora[i], dx)
        return loss, grads

    # -- the optimizer -----------------------------------------------------
    def _adam_step(self, lora, grads, mu, nu, count):
        lr = float(self.traffic["learning_rate"])
        wd = float(self.traffic["weight_decay"])
        sq = jax.tree.map(lambda g: jnp.sum(g * g), grads)
        norm = jnp.sqrt(sum(jax.tree.leaves(sq)))
        clip = jnp.minimum(1.0, float(self.traffic["max_grad_norm"]) / norm)
        grads = jax.tree.map(lambda g: g * clip, grads)
        count = count + 1
        mu = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
        nu = jax.tree.map(lambda n, g: B2 * n + (1 - B2) * g * g, nu, grads)
        c1, c2 = 1 - B1 ** count, 1 - B2 ** count
        lora = jax.tree.map(
            lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + EPS)
                                      + wd * p), lora, mu, nu)
        return lora, mu, nu, sq

    # -- the round ---------------------------------------------------------
    def run_round(self, round_idx: int) -> dict:
        # the program is told ``seed % 2**32`` (its ``random_seed``)
        xs, ys, weights = data_mod.round_batches(
            self.seed % (1 << 32), round_idx, self.clients, self.traffic)
        n = len(weights)
        taking_part = range(n // 2) if self.fault == "half_clients" else range(n)
        acc = jax.tree.map(jnp.zeros_like, self.lora)
        losses, wsum = [], 0.0
        for c in taking_part:
            lora = self.lora  # the client switch: back to the global adapters
            steps = []
            for s in range(xs.shape[1]):
                loss, grads = self.loss_and_grads(
                    lora, jnp.asarray(xs[c, s]), jnp.asarray(ys[c, s]))
                self.count += 1
                lora, self.mu, self.nu, sq = self._adam(
                    lora, grads, self.mu, self.nu, float(self.count - 1))
                self.grad_sq = sq if self.grad_sq is None else jax.tree.map(
                    jnp.add, self.grad_sq, sq)
                self.steps += 1
                steps.append(float(loss))
            losses.append(float(np.mean(steps)))
            w = float(weights[c])
            acc = jax.tree.map(lambda a, l: a + w * l, acc, lora)
            wsum += w
        self.lora = jax.tree.map(lambda a: a / wsum, acc)
        return {"loss": float(np.mean(losses))}

    def state(self) -> dict:
        """Flat ``{path: array}`` views, named as ``weights.leaf_specs``."""
        def flat(layers):
            return {f"layer_{i}/{k}": np.asarray(v)
                    for i, layer in enumerate(layers) for k, v in layer.items()}

        grad_rms = {k: float(np.sqrt(v / max(self.steps, 1)))
                    for k, v in flat(self.grad_sq).items()}
        return {"mu": flat(self.mu), "nu": flat(self.nu), "count": self.count,
                "lora": flat(self.lora), "init_lora": flat(self.init_lora),
                "grad_rms": grad_rms}
