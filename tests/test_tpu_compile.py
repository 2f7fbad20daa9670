"""Compile the main path's kernels and the fused round for a DESCRIBED
TPU v5e (``jax.experimental.topologies``) — no chip attached, nothing
runs. What the chip's compiler refuses (a kernel it cannot tile or
partition, a program that does not fit) fails here at no chip time.
A compile that passes is not a chip run; ``chip_smoke.py`` is.

Everything that touches the TPU library lives in fixtures of THIS file:
one process at a time may load libtpu, so only the xdist worker that is
handed this file does (see /opt/skills/guides/on-chip-measurement §2).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16e9  # published: 16 GB per chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    had_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip — the next run would warn
    # and compile again, so keep these out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    if had_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("shape", [(1, 32, 512, 128), (1, 32, 4096, 64)],
                         ids=["t512_d128", "t4096_d64"])  # the two cells'
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, backward, shape):
    from fedml_tpu.ops.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    fn = fwd
    if backward:
        fn = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(qkv, qkv, qkv).compile()
    assert _kernels(compiled) == (3 if backward else 1)  # fwd | fwd, dq, dkv
    # each kernel under its own name, in the instruction's name and its
    # op_name: what a device trace shows and the benchmark's readers match
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    names = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"][:len(calls)]
    for name in names:
        (line,) = [c for c in calls
                   if re.search(rf'op_name="[^"]*[/(]{name}[/)]', c)]
        assert name in line.split(" = ")[0]


@pytest.mark.parametrize("h,f", [(4096, 11008), (11008, 4096), (4096, 32000)])
def test_dequant_matmul_compiles_for_v5e(one_chip, h, f):
    from fedml_tpu.ops.quant import pallas_dequant_matmul

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda x, q, s: pallas_dequant_matmul(
            x, q, s, jnp.bfloat16, interpret=False)
    ).lower(sds((8, h), jnp.bfloat16), sds((h, f), jnp.int8),
            sds((f,), jnp.float32)).compile()
    assert _kernels(compiled) == 1


def test_grouped_matmul_compiles_for_v5e(one_chip):
    """``moe_gmm`` and its row gradient ``moe_gmm_t`` at the widths of
    ``zaya1-8b.round-mid``: 1,024 tokens over 16 experts of 2048 x 2048,
    each kernel under its own name."""
    from fedml_tpu.ops import grouped_matmul as gmm

    m, k, n, e = 1024, 2048, 2048, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, w, expert):
        layout = gmm.group_layout(expert, e)
        out = gmm.grouped_matmul(gmm.dispatch(x, layout), w, layout,
                                 interpret=False)
        return gmm.combine(out, layout).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        sds((m, k), jnp.bfloat16), sds((e, k, n), jnp.bfloat16),
        sds((m,), jnp.int32)).compile()
    assert _kernels(compiled) == 2
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sorted(c.split(" = ")[0].strip().lstrip("%").split(".")[0]
                  for c in calls) == ["moe_gmm", "moe_gmm_t"]
    # no transposed copy of the experts' 134 MB is made for the backward
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    assert " sort(" not in text and " scatter(" not in text


def _compile_fused_round(devices, fsdp, layers=2, clients=8, steps=2):
    """``llm/fused_round`` at the 7B widths, lowered from shapes alone.

    The trainer's constructor places nothing; params, shardings and the
    optimizer state are handed to it as ``ShapeDtypeStruct`` trees on the
    described devices, then the wrapped jit is lowered with abstract data.
    """
    from fedml_tpu.models.llm.llama import LlamaConfig
    from fedml_tpu.train.llm.sharding import (
        logical_shardings,
        make_mesh,
        replicated,
        unbox,
    )
    from fedml_tpu.train.llm.trainer import (
        LLMTrainer,
        extract_lora,
        extract_trainable,
    )

    class Args:
        max_seq_length = 512
        per_device_batch_size = 1
        learning_rate = 1e-4

    mesh = make_mesh(fsdp=fsdp, devices=devices[:fsdp])
    cfg = LlamaConfig.llama2_7b(
        num_hidden_layers=layers, lora_rank=16, param_dtype=jnp.bfloat16,
        remat_policy="none", use_flash=True)
    tr = LLMTrainer(cfg, Args(), mesh=mesh)
    batch, seq = tr.batch_size, tr.seq_len
    assert batch == fsdp  # per_device_batch_size is per device
    abstract = jax.eval_shape(
        tr.model.init, jax.random.key(0),
        jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    tr.shardings = unbox(logical_shardings(abstract, mesh))
    tr.params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        unbox(abstract), tr.shardings)
    rep = replicated(mesh)
    tr.opt_state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(tr.tx.init, extract_trainable(tr.params)))
    fed = tr.compile_federated_round(clients, steps)
    tokens = jax.ShapeDtypeStruct((clients, steps, batch, seq), jnp.int32)
    return fed.lower(
        tr.params, tr.opt_state, extract_lora(tr.params), tokens, tokens,
        jax.ShapeDtypeStruct((clients, steps, batch), jnp.float32),
        jax.ShapeDtypeStruct((clients,), jnp.float32)).compile()


@pytest.mark.parametrize("fsdp", [1, 4], ids=["one_chip", "fsdp4"])
def test_fused_round_7b_widths_compiles_for_v5e(topo, monkeypatch, fsdp):
    from fedml_tpu.ops import dispatch

    # model code asks the default backend (the CPU, here) which form of
    # the kernel to emit; the test answers for the described chip
    monkeypatch.setattr(dispatch, "default_platform", lambda: "tpu")
    compiled = _compile_fused_round(topo.devices, fsdp)
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert resident < V5E_HBM_BYTES
    # params, opt state and adapters are donated: outputs alias arguments
    assert mem.alias_size_in_bytes > 0.99 * mem.output_size_in_bytes
    # 2 layers x (flash fwd + dq + dkv), partitioned or not
    assert _kernels(compiled) == 6
    text = compiled.as_text()
    if fsdp == 1:
        assert "all-gather" not in text
    else:
        # ZeRO-3: each device holds a quarter of the 1.35 GB base and the
        # weights are gathered per layer
        assert mem.argument_size_in_bytes < 0.3 * 1.36e9
        assert "all-gather" in text
