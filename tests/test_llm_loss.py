"""The head and the loss as one function (``models/llm/head_loss.py``)
against ``optax`` over whole float32 logits, on the CPU at tiny sizes; and
what the compiled train step of a tiny model no longer holds. A timing
here is never a speed."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fedml_tpu import telemetry
from fedml_tpu.models.llm import head_loss as hl
from fedml_tpu.models.llm.head_loss import HeadInputs, head_loss
from fedml_tpu.models.llm.llama import LlamaConfig
from fedml_tpu.train.llm.trainer import LLMTrainer, extract_lora

B, T, H = 2, 7, 32  # 14 rows: no multiple of anything
# (vocabulary, blocks it must be cut in): one block whatever its width; a
# vocabulary of the form 3 x odd x 128, cut in 3 as the 262,272-row head
# is; and 4 blocks of 512
VOCABS = [(1000, 1), (3 * 5 * 128, 3), (2048, 4)]


@pytest.fixture
def small_blocks(monkeypatch):
    """The bound on a block's float32 logits, set so that 14 rows x 640
    fit and 14 x 641 do not: the tests' vocabularies are cut as large ones
    are. The bound is a constant of the module, no argument of anything."""
    monkeypatch.setattr(hl, "_BLOCK_BYTES", B * T * 640 * 4)


def _case(tied, dtype, vocab, seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    h = jax.random.normal(k[0], (B, T, H), jnp.float32).astype(dtype)
    head = 0.3 * jax.random.normal(
        k[1], (vocab, H) if tied else (H, vocab), jnp.float32)
    y = jax.random.randint(k[2], (B, T), 0, vocab)
    logits = _logits(h, head, tied, dtype)
    # half of the rows aim at the row's own maximum, so some are correct;
    # one has no target, one batch row is masked in part
    y = jnp.where(jnp.arange(T) % 2 == 0, jnp.argmax(logits, -1), y)
    y = y.at[0, 3].set(-1).at[1, 6].set(-1)
    mask = jnp.array([1.0, 0.5])
    w = (y >= 0).astype(jnp.float32) * mask[:, None]
    return h, head, y, w


def _logits(h, head, tied, dtype):
    return jnp.einsum("bth,vh->btv" if tied else "bth,hv->btv",
                      h.astype(dtype), head.astype(dtype),
                      preferred_element_type=jnp.float32)


def _optax(h, head, y, w, tied, dtype):
    logits = _logits(h, head, tied, dtype)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(y, 0))
    correct = jnp.sum((jnp.argmax(logits, -1) == y) * w)
    return jnp.sum(ce * w), correct


CASES = [pytest.param(tied, dtype, vocab, blocks,
                      id=f"{'tied' if tied else 'untied'}-"
                         f"{jnp.dtype(dtype).name}-v{vocab}")
         for tied in (True, False)
         for dtype in (jnp.float32, jnp.bfloat16)
         for vocab, blocks in VOCABS]


@pytest.mark.parametrize("tied,dtype,vocab,blocks", CASES)
def test_loss_and_gradients_are_optaxs(small_blocks, tied, dtype, vocab,
                                       blocks):
    assert hl.plan(B * T, vocab) == (blocks, vocab // blocks)
    h, head, y, w = _case(tied, dtype, vocab)

    def ours(h, head):
        return head_loss(HeadInputs(h, head, tied), y, w)

    def theirs(h, head):
        return _optax(h, head, y, w, tied, dtype)

    (loss, correct), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True))(h, head)
    (want, want_correct), want_grads = jax.value_and_grad(
        theirs, argnums=(0, 1), has_aux=True)(h, head)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    assert float(want_correct) >= 3.0
    assert float(correct) == float(want_correct)
    # float32: the order of summation. bfloat16: the softmax gradient is
    # rounded once on both sides, as the operand of the second product
    # (2**-9 of entries of order 1, summed over the contraction)
    atol = 3e-6 if dtype == jnp.float32 else 2e-2
    for got, ref in zip(grads, want_grads):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=atol, rtol=0)
    # the gradient with respect to the hidden state alone (the head
    # frozen, as in every LoRA round) is the same array
    dh = jax.jit(jax.grad(lambda h: ours(h, head)[0]))(h)
    np.testing.assert_allclose(np.asarray(dh, np.float32),
                               np.asarray(grads[0], np.float32),
                               atol=atol, rtol=0)
    # and the function called with no gradient asked (evaluation)
    np.testing.assert_allclose(jax.jit(ours)(h, head)[0], loss, rtol=1e-6)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_rows_without_target_or_weight_move_nothing(small_blocks, tied):
    vocab = 3 * 5 * 128
    h, head, y, w = _case(tied, jnp.float32, vocab)
    f = jax.jit(jax.value_and_grad(
        lambda h, y: head_loss(HeadInputs(h, head, tied), y, w)[0]))
    loss, dh = f(h, y)
    assert not np.any(np.asarray(dh)[0, 3]) and not np.any(
        np.asarray(dh)[1, 6])
    # what the hidden state or the target of such a row is changes nothing
    loss2, dh2 = f(h.at[0, 3].set(9.0), y.at[1, 6].set(5))
    assert float(loss2) == float(loss)
    np.testing.assert_array_equal(np.delete(np.asarray(dh2), 3, axis=1),
                                  np.delete(np.asarray(dh), 3, axis=1))


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_a_target_that_ties_the_maximum_counts_as_correct(small_blocks, tied):
    """The rule of the count: a row is correct where its target's logit
    is the row's maximum. ``argmax == y`` says the same short of an exact
    tie, where it counts the first index alone; here every label that
    ties counts (two rows of the head that are one vector, a hidden row
    of zeros), which costs no pass over the logits."""
    vocab = 3 * 5 * 128
    h, head, y, w = _case(tied, jnp.float32, vocab)
    top = jnp.argmax(_logits(h, head, tied, jnp.float32), -1)
    one = int(top[0, 1])
    twin = (one + 700) % vocab  # in another block than its twin
    head = (head.at[twin].set(head[one]) if tied
            else head.at[:, twin].set(head[:, one]))
    h = h.at[1, 2].set(0.0)  # every logit of this row is 0
    # the later of the twins and the last of the zeros: argmax has neither
    y = top.at[0, 1].set(max(one, twin)).at[1, 2].set(vocab - 1)
    w = jnp.ones_like(w)
    logits = _logits(h, head, tied, jnp.float32)
    first = jnp.sum(jnp.argmax(logits, -1) == y)
    ties = jnp.sum(jnp.take_along_axis(logits, y[..., None], -1)[..., 0]
                   >= jnp.max(logits, -1))
    assert int(ties) == B * T and int(first) == B * T - 2
    assert float(head_loss(HeadInputs(h, head, tied), y, w)[1]) == B * T


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_blocks_are_cut_inside_each_shard(small_blocks, tied):
    """A vocabulary the mesh cuts in 2 is walked as [2, 3, 640]: block j
    is columns j of both shards. The numbers are the whole head's."""
    vocab = 2 * 3 * 5 * 128
    assert hl.plan(B * T, vocab, 2) == (3, 640)
    h, head, y, w = _case(tied, jnp.float32, vocab)
    y = y.at[0, 0].set(vocab - 1).at[0, 1].set(vocab // 2)

    def ours(h, head, shards):
        return head_loss(HeadInputs(h, head, tied, shards), y, w)

    (loss, correct), grads = jax.jit(jax.value_and_grad(
        lambda h, head: ours(h, head, 2), argnums=(0, 1), has_aux=True)
    )(h, head)
    (want, want_correct), want_grads = jax.value_and_grad(
        lambda h, head: _optax(h, head, y, w, tied, jnp.float32),
        argnums=(0, 1), has_aux=True)(h, head)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    assert float(correct) == float(want_correct)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, atol=3e-6, rtol=0)
    # shards of unequal width are not cut along
    np.testing.assert_allclose(ours(h, head, 7)[0], want, rtol=2e-6)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_a_sharded_head_is_walked_without_gathering_it(small_blocks, tied):
    """On a mesh that cuts the vocabulary in 2 (``tp``) the walk's blocks
    are each device's own columns: the compiled text gathers nothing,
    where blocks cut across the shards (``shards=1``) gather the head."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from fedml_tpu.train.llm.sharding import make_mesh

    vocab = 2 * 3 * 5 * 128
    h, head, y, w = _case(tied, jnp.float32, vocab)
    mesh = make_mesh(fsdp=1, tp=2, devices=jax.devices()[:2])
    cut = NamedSharding(mesh, P("tp", None) if tied else P(None, "tp"))
    rep = NamedSharding(mesh, P())

    def compiled(shards):
        return jax.jit(
            jax.value_and_grad(lambda h, head: head_loss(
                HeadInputs(h, head, tied, shards), y, w)[0], argnums=(0, 1)),
            in_shardings=(rep, cut), out_shardings=(rep, (rep, cut)),
        ).lower(h, head).compile()

    inside, across = compiled(2), compiled(1)
    assert "all-gather" not in inside.as_text()
    assert "all-gather" in across.as_text()
    loss, (dh, dhead) = inside(h, head)
    want, (want_dh, want_dhead) = across(h, head)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_allclose(dh, want_dh, atol=3e-6, rtol=0)
    np.testing.assert_allclose(dhead, want_dhead, atol=3e-6, rtol=0)


@pytest.mark.parametrize("differentiated", [False, True],
                         ids=["head_frozen", "head_trained"])
def test_the_plan_event_says_what_engaged(small_blocks, differentiated):
    vocab = 3 * 5 * 128
    h, head, y, w = _case(True, jnp.float32, vocab)
    tracer = telemetry.get_tracer()
    before = len([r for r in tracer.records() if r["name"] == "loss/plan"])
    jax.grad(lambda h, head: head_loss(HeadInputs(h, head, True), y, w)[0],
             argnums=(0, 1) if differentiated else 0)(h, head)
    events = [r for r in tracer.records() if r["name"] == "loss/plan"]
    assert len(events) == before + 1  # one a trace
    assert events[-1]["attrs"] == {
        "rows": B * T, "vocab": vocab, "hidden": H, "blocks": 3,
        "block": 640, "axis": "vocab", "shards": 1,
        "logits_block_bytes": B * T * 640 * 4,
        "head_differentiated": differentiated}


@pytest.mark.parametrize("rows,vocab,blocks", [
    (1024, 262272, 3),    # zaya1-8b.round-mid: 3 x 683 x 128
    (4096, 49152, 2),     # smollm2-1.7b.round-long
    (512, 64000, 1),      # yi-6b.round-short: 131 MB of logits, one block
    (2048, 262272, 683),  # 3 blocks are 716 MB each: the next divisor
    (16, 250, 1),         # no multiple of 128: whole
    (4096, 50257, 1),     # 823 MB that no multiple of 128 divides: whole
], ids=["zaya", "smollm2", "yi", "zaya-t2048", "odd", "odd-over"])
def test_block_count_follows_from_the_shapes(rows, vocab, blocks):
    n, block = hl.plan(rows, vocab)
    assert (n, n * block) == (blocks, vocab)
    assert n == 1 or block % 128 == 0
    # a vocabulary the mesh cuts in 2: each shard in blocks of its own
    # (smollm2's 24,576 columns a shard in 1; a shard of zaya's is 131,136
    # = 1024.5 x 128 columns, which no multiple of 128 divides: whole)
    n2, block2 = hl.plan(rows, vocab, 2)
    assert 2 * n2 * block2 == vocab - vocab % 2
    assert n2 == 1 or (block2 % 128 == 0 and n2 <= n)


# --- what the compiled train step of a tiny model no longer holds ----------
V_STEP, T_STEP, H_STEP, BLOCKS_STEP = 4096, 64, 32, 8
LOGITS_BYTES = T_STEP * V_STEP * 4


@pytest.fixture(scope="module")
def cut_in_eight():
    """The module's bound set so that the 4,096-row head of the tiny step
    is cut in 8 blocks of 512, as a large head is cut."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hl, "_BLOCK_BYTES", LOGITS_BYTES // BLOCKS_STEP)
        yield


@pytest.fixture(scope="module")
def trainer(cut_in_eight):
    """A tiny LoRA model's trainer (tied 4,096-row head, B1 T64, hidden
    32, float32): ``T x V`` float32 is 1 MiB, twice the embedding and
    over everything else its train step holds."""
    from fedml_tpu.train.llm.sharding import make_mesh

    class Args:
        max_seq_length = T_STEP
        per_device_batch_size = 1
        learning_rate = 1e-3

    cfg = LlamaConfig.tiny(vocab_size=V_STEP, hidden_size=H_STEP,
                           tie_word_embeddings=True, lora_rank=4,
                           use_flash=False, dtype=jnp.float32)
    tr = LLMTrainer(cfg, Args(),
                    mesh=make_mesh(fsdp=1, devices=jax.devices()[:1]))
    tr.init(seed=0)
    return tr


@pytest.fixture(scope="module")
def lowered_step(trainer):
    tokens = jnp.zeros((1, 1, T_STEP), jnp.int32)
    return trainer._train_step.lower(
        trainer.params, trainer.opt_state, tokens, tokens,
        jnp.ones((1, 1), jnp.float32))


@pytest.fixture(scope="module")
def train_step(lowered_step):
    return lowered_step.compile()


# --- set-up: what the walk hands the tracer, the lowering and the compiler --
def test_the_block_body_is_lowered_once(lowered_step):
    """The walk starts from the recurrence's neutral element, so block 0
    is a turn of the loop like the others: the lowered text (what set-up
    builds on every run, cached executable or not) holds each of the
    head's two products once. A block peeled out before the loop made it
    four, and two bodies for the compiler."""
    block = V_STEP // BLOCKS_STEP
    products = [line for line in lowered_step.as_text().splitlines()
                if "stablehlo.dot_general" in line
                and re.search(rf"[<x]{block}x", line)]
    assert len(products) == 2, products


def test_the_constructor_traces_the_model_once():
    """``init_sharded_params`` needs the model's abstract output (for the
    shardings) and its jitted init: one bound ``model.init`` serves both,
    so JAX finds the first trace again. Two ``model.init`` expressions
    are two objects, and the whole model was traced twice a set-up."""
    import flax.linen as nn

    from fedml_tpu.train.llm.sharding import init_sharded_params, make_mesh

    traced = []

    class Counted(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            traced.append(tokens.shape)
            w = self.param("w", nn.with_logical_partitioning(
                nn.initializers.ones, ("embed",)), (H,), jnp.float32)
            return w.sum() + tokens.sum()

    params, shardings = init_sharded_params(
        Counted(), jnp.zeros((1, T), jnp.int32),
        make_mesh(fsdp=1, devices=jax.devices()[:1]))
    assert traced == [(1, T)]
    assert params["params"]["w"].shape == (H,)
    assert jax.tree.structure(params) == jax.tree.structure(shardings)


def test_tracing_the_round_traces_the_head_once(trainer):
    """``loss/plan`` is left each time ``head_loss`` is traced: tracing and
    lowering the whole fused round (2 clients x 2 steps under two scans
    and ``value_and_grad``) leaves one, and runs no program."""
    tracer = telemetry.get_tracer()
    before = len([r for r in tracer.records() if r["name"] == "loss/plan"])
    fed = trainer.compile_federated_round(2, 2)
    tokens = jnp.zeros((2, 2, 1, T_STEP), jnp.int32)
    fed.lower(trainer.params, trainer.opt_state,
              extract_lora(trainer.params), tokens, tokens,
              jnp.ones((2, 2, 1), jnp.float32), jnp.ones((2,), jnp.float32))
    events = [r for r in tracer.records() if r["name"] == "loss/plan"]
    assert len(events) == before + 1
    assert events[-1]["attrs"]["blocks"] == BLOCKS_STEP


def test_no_scatter_and_no_whole_float32_logits(train_step):
    text = train_step.as_text()
    assert " scatter(" not in text
    # no array of T x V float32 elements, in any shape: not the logits,
    # not their gradient, not the plain form's flat relayout of it
    sizes = [int(np.prod([int(d) for d in s.split(",")]))
             for s in re.findall(r"f32\[([\d,]+)\]", text)]
    assert max(sizes) < T_STEP * V_STEP
    assert T_STEP * V_STEP // BLOCKS_STEP in sizes  # a block's logits
    # the head's two products, each once (in the body of the loop over
    # the blocks, which starts at block 0), and no third: the frozen
    # head's own gradient is not made. Found by the block's width among a
    # product's shapes
    # (no other axis of the step is 512 long), since the CPU compiler
    # rewrites the second product and drops its name.
    shape = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", text))
    block = str(V_STEP // BLOCKS_STEP)
    products = [
        out for out, dims, args in re.findall(
            r"%([\w.-]+) = \w+\[([\d,]*)\]\S* (?:dot|convolution)\(([^)]*)\)",
            text)
        if block in ",".join(
            [dims] + [shape.get(a.strip().lstrip("%"), "")
                      for a in args.split(",")]).split(",")]
    assert len(products) == 2, products


def test_temporaries_are_under_one_float32_logits_array(train_step):
    """The plain form (``take_along_axis`` over whole float32 logits under
    ``value_and_grad``) plans over twice ``T x V x 4`` for the head and
    loss alone at this size (the logits, their softmax gradient, its
    scatter); the whole step now plans under once that."""
    h = jnp.zeros((1, T_STEP, H_STEP), jnp.float32)
    head = jnp.zeros((V_STEP, H_STEP), jnp.float32)
    y = jnp.zeros((1, T_STEP), jnp.int32)
    w = jnp.ones((1, T_STEP), jnp.float32)

    def temporaries(loss):
        return jax.jit(jax.value_and_grad(loss)).lower(
            h).compile().memory_analysis().temp_size_in_bytes

    assert temporaries(
        lambda h: _optax(h, head, y, w, True, jnp.float32)[0]
    ) > 2 * LOGITS_BYTES
    assert temporaries(
        lambda h: head_loss(HeadInputs(h, head, True), y, w)[0]
    ) < LOGITS_BYTES
    assert train_step.memory_analysis().temp_size_in_bytes < LOGITS_BYTES
