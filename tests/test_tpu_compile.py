"""Compile the main path's kernels and the fused round for a DESCRIBED
TPU v5e (``jax.experimental.topologies``) — no chip attached, nothing
runs. What the chip's compiler refuses (a kernel it cannot tile or
partition, a program that does not fit) fails here at no chip time.
A compile that passes is not a chip run; ``chip_smoke.py`` is.

Everything that touches the TPU library lives in fixtures of THIS file:
one process at a time may load libtpu, so only the xdist worker that is
handed this file does (see /opt/skills/guides/on-chip-measurement §2).
"""
import math
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


def test_held_top_k_grouped_matmul_compiles_for_v5e(one_chip):
    """The expert layer of ``nemotron-3-super-120b-a12b.round-2k``: 2,048
    tokens, 22 choices a token of 512 experts, 128 held, in a 1024-wide
    latent with 2688-wide experts — the layout with a held range, both
    grouped products (column tiles of 896 and 1024) and their row
    gradients, still without a sort or a scatter; relu^2 between the
    products is the second one's own (its prologue, its row gradient's
    epilogue), so no fusion walks the 61,312-row buffer between the
    kernels, forward or backward."""
    from fedml_tpu.models.llm.nemotron_h import RELU2
    from fedml_tpu.ops import grouped_matmul as gmm

    m, k, lat, mid, held, bm = 2048, 22, 1024, 2688, 128, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, up, down, chosen, gate):
        layout = gmm.group_layout(chosen, held, bm, first=0)
        product = lambda a, w, **kw: gmm.grouped_matmul(
            a, w, layout, block_m=bm, interpret=False, **kw)
        hidden = product(gmm.dispatch(x, layout), up)
        out = product(hidden, down, activation=RELU2)
        mine = gmm.combine(out, layout).astype(jnp.float32)
        return jnp.sum(mine * gate[..., None])

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        sds((m, lat), jnp.bfloat16), sds((held, lat, mid), jnp.bfloat16),
        sds((held, mid, lat), jnp.bfloat16), sds((m, k), jnp.int32),
        sds((m, k), jnp.float32)).compile()
    assert _kernels(compiled) == 4
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sorted(c.split(" = ")[0].strip().lstrip("%").split(".")[0]
                  for c in calls) == ["moe_gmm"] * 2 + ["moe_gmm_t"] * 2
    rows = gmm.padded_rows(m * k, held, bm)
    assert rows == 61312 and f"bf16[{rows},{mid}]" in text
    assert " sort(" not in text and " scatter(" not in text
    # the [61312, 2688] buffer is a kernel's output and a kernel's operand
    # and nothing else's: relu^2 (forward) and 2 relu (backward) are not
    # fusions over rows that three times in four hold nothing
    wide = [line.split(" = ")[0].strip() for line in text.splitlines()
            if re.search(rf" = \(?bf16\[{rows},{mid}\]", line)
            and "parameter(" not in line]
    assert len(wide) == 2 and all("moe_gmm" in name for name in wide), wide
    # no [assignments, assignments] grid: the largest integer or boolean
    # array is the [row tiles, rows a tile, tokens] one, as pred
    assert f"[{m * k},{m * k}]" not in text


def test_gated_top_k_grouped_matmul_compiles_for_v5e(one_chip):
    """The four grouped-product shapes of ``glm-4.7-flash.round-4k``: 4,096
    tokens, 4 choices a token of 64 experts, all held, 64-row tiles, 2048
    into 1536 and back (column tiles of 768 and 1024) and both row
    gradients — runs of four and five tiles, so the kernels' two weight
    slots and the copy a run ahead are what compiles here, each kernel
    still under its own name."""
    from fedml_tpu.ops import grouped_matmul as gmm

    m, k, hid, mid, e, bm = 4096, 4, 2048, 1536, 64, 64

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, up, down, chosen):
        layout = gmm.group_layout(chosen, e, bm)
        product = lambda a, w: gmm.grouped_matmul(
            a, w, layout, block_m=bm, interpret=False)
        out = product(product(gmm.dispatch(x, layout), up), down)
        return gmm.combine(out, layout).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        sds((m, hid), jnp.bfloat16), sds((e, hid, mid), jnp.bfloat16),
        sds((e, mid, hid), jnp.bfloat16), sds((m, k), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sorted(c.split(" = ")[0].strip().lstrip("%").split(".")[0]
                  for c in calls) == ["moe_gmm"] * 2 + ["moe_gmm_t"] * 2
    rows = gmm.padded_rows(m * k, e, bm)
    assert rows == 20416
    assert sorted(re.search(r" = \(?(bf16\[\d+,\d+\])", c).group(1)
                  for c in calls) == sorted(
        [f"bf16[{rows},{mid}]", f"bf16[{rows},{hid}]"] * 2)
    # no transposed copy of an expert stack (403 MB) for the backward
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9


def test_chunked_scan_compiles_for_v5e(one_chip):
    """``ops/ssd.py`` at the Mamba-2 widths of the same cell (128 heads x
    64, 8 groups, state 128, chunks of 128 at T2048), forward and backward
    with its inside recomputed: it fits beside nothing else in well under
    a gigabyte and keeps no ``[H, Q, Q]`` array between the passes."""
    from fedml_tpu.ops.ssd import ssd

    t, h, p, g, n = 2048, 128, 64, 8, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, dt, a, b, c):
        return ssd(x, dt, a, b, c, chunk=128).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4))).lower(
        sds((1, t, h, p), jnp.bfloat16), sds((1, t, h), jnp.float32),
        sds((h,), jnp.float32), sds((1, t, g, n), jnp.bfloat16),
        sds((1, t, g, n), jnp.bfloat16)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def _compile_fused_round(devices, fsdp, layers=2, clients=8, steps=2,
                         cfg=None, seq_len=512):
    """``llm/fused_round`` at the 7B widths (or for ``cfg``, another
    family's configuration), lowered from shapes alone.

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
        max_seq_length = seq_len
        per_device_batch_size = 1
        learning_rate = 1e-4

    mesh = make_mesh(fsdp=fsdp, devices=devices[:fsdp])
    cfg = cfg or LlamaConfig.llama2_7b(
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


def test_fused_round_glm_moe_lite_widths_compiles_for_v5e(topo, monkeypatch):
    """``model: glm4_moe_lite`` at GLM-4.7-Flash's published widths, the
    leading dense layer and one expert layer at T4096 as
    ``glm-4.7-flash.round-4k`` runs them: the flash kernels at heads of 256
    (blocks of 1024) under their VMEM limit, the grouped products at 2048 x
    1536 with 64 experts held, each kernel under its own name, no sort but
    the router's top-k and no scatter, and a plan that fits the chip."""
    from fedml_tpu.models.llm.glm_moe_lite import GlmMoeLiteConfig
    from fedml_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "default_platform", lambda: "tpu")
    cfg = GlmMoeLiteConfig(
        num_hidden_layers=2, lora_rank=16, param_dtype=jnp.bfloat16,
        remat_policy="none", use_flash=True)
    compiled = _compile_fused_round(topo.devices, 1, cfg=cfg, seq_len=4096)
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert resident < V5E_HBM_BYTES
    assert mem.alias_size_in_bytes > 0.99 * mem.output_size_in_bytes
    text = compiled.as_text()
    calls = [line.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for line in text.splitlines() if "tpu_custom_call" in line]
    # two layers' flash kernels; the expert layer's gate, up and down
    # products and their row gradients
    assert sorted(calls) == sorted(
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"] * 2
        + ["moe_gmm"] * 3 + ["moe_gmm_t"] * 3)
    assert "bf16[1,20,4096,256]" in text
    # the one sort is the router's top-4 of 64; the layout has none
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert len(sorts) == 1 and "/moe/router/top_k" in sorts[0]
    assert " scatter(" not in text


def _unfused(text):
    """``(shapes, op_name)`` of every instruction the compiled module runs
    as one of its own (not inside a fused computation): what it writes is
    an array in memory."""
    fused = False
    for line in text.splitlines():
        if not line.startswith(" "):
            fused = line.startswith("%fused_computation")
            continue
        found = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (.*?) [\w\-]+\(", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if found and op_name and not fused:
            yield re.findall(r"(\w+)\[([\d,]+)\]", found.group(1)), \
                op_name.group(1)


@pytest.mark.parametrize("b,t,h,hkv,d", [(1, 4096, 32, 32, 64),
                                         (1, 512, 32, 4, 128)],
                         ids=["round_long", "round_short"])  # dense cells'
def test_rope_is_one_pass_each_way_for_v5e(one_chip, monkeypatch,
                                           b, t, h, hkv, d):
    """The attention sandwich (projections with adapters, ``attn_layout``,
    rope on q and k, the flash kernels, ``o_proj``) and its gradient:
    under ``rope`` there is one fusion a roped tensor a direction, and no
    array of a tensor's size but those fusions' bfloat16 outputs — no
    float32 copy of q or k, no half-width array, no concatenation but the
    tables' ``[T, D]``."""
    from fedml_tpu.models.llm.layers import rope_tables
    from fedml_tpu.models.llm.llama import LlamaAttention, LlamaConfig
    from fedml_tpu.ops import dispatch
    from fedml_tpu.train.llm.sharding import unbox

    monkeypatch.setattr(dispatch, "default_platform", lambda: "tpu")
    cfg = LlamaConfig(
        hidden_size=h * d, num_attention_heads=h, num_key_value_heads=hkv,
        lora_rank=16, param_dtype=jnp.bfloat16, use_flash=True)
    attn = LlamaAttention(cfg)

    def tables():
        with jax.named_scope("rope"):  # as the shell states them
            return rope_tables(jnp.arange(t), d, cfg.rope_theta)

    def loss(params, x):
        out, _ = attn.apply(params, x, *tables())
        return out.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        unbox(jax.eval_shape(
            lambda x: attn.init(jax.random.key(0), x, *tables()), x)))
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    assert _kernels(compiled) == 3

    roped = {"jvp": [], "transpose": []}
    for shapes, op_name in _unfused(compiled.as_text()):
        if not re.search(r"(^|/)rope(/|$)", op_name):
            continue
        for dtype, dims in shapes:
            dims = [int(n) for n in dims.split(",")]
            if math.prod(dims) <= t * d:
                continue  # the tables and what they are made of
            # all that is left is a roped tensor in the compute type
            assert "dot_general" in op_name, (shapes, op_name)
            assert dtype == "bf16" and dims[-1] == d, (shapes, op_name)
            assert math.prod(dims) in (b * t * h * d, b * t * hkv * d)
            roped["transpose" if "transpose(" in op_name else "jvp"].append(
                math.prod(dims))
    want = sorted([b * t * h * d, b * t * hkv * d])
    assert sorted(roped["jvp"]) == want  # q and k, forward
    assert sorted(roped["transpose"]) == want  # dq and dk, backward
