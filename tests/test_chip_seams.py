"""The seams the chip bring-up added: kernel dispatch, the compile-cache
rule, the catalog's refusal to swallow compiler errors, the per-device
batch rule, flash attention under a partitioned program, and the
multichip bench's efficiency basis."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- kernel dispatch ---------------------------------------------------------

@pytest.mark.parametrize("platform,interpret,off_tpu,want", [
    ("cpu", None, "reference", "reference"),
    ("cpu", None, "interpret", "interpret"),
    ("cpu", True, "reference", "interpret"),
    ("cpu", False, "reference", "compiled"),
    ("tpu", None, "reference", "compiled"),
    ("tpu", None, "interpret", "compiled"),
    ("tpu", False, "interpret", "compiled"),
])
def test_kernel_mode(monkeypatch, platform, interpret, off_tpu, want):
    from fedml_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "default_platform", lambda: platform)
    assert dispatch.kernel_mode(interpret, off_tpu=off_tpu) == want


def test_kernel_mode_refuses_interpreter_on_tpu(monkeypatch):
    from fedml_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "default_platform", lambda: "tpu")
    with pytest.raises(RuntimeError, match="interpret=True on a TPU"):
        dispatch.kernel_mode(True, off_tpu="reference")


def test_kernel_mode_does_not_swallow_device_discovery(monkeypatch):
    from fedml_tpu.ops import dispatch

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        dispatch.kernel_mode(None, off_tpu="reference")


@pytest.mark.parametrize("kernel", ["flash", "dequant_matmul"])
def test_explicit_compiled_kernel_off_tpu_raises(kernel):
    """``interpret=False`` never falls back to the reference / XLA path:
    off-TPU the compiled kernel is emitted and Pallas refuses to lower."""
    from fedml_tpu.ops.flash_attention import flash_attention
    from fedml_tpu.ops.quant import pallas_dequant_matmul

    with pytest.raises(ValueError, match="Only interpret mode"):
        if kernel == "flash":
            q = jnp.ones((1, 2, 128, 32), jnp.bfloat16)
            flash_attention(q, q, q, interpret=False)
        else:
            pallas_dequant_matmul(
                jnp.ones((8, 256), jnp.bfloat16), jnp.ones((256, 512), jnp.int8),
                jnp.ones((512,), jnp.float32), jnp.bfloat16, interpret=False)


def test_model_on_tpu_gets_the_compiled_kernel_or_raises(monkeypatch):
    """With the default backend answering "tpu", model code's
    ``interpret=None`` call emits the compiled kernel — which this CPU
    cannot lower — instead of quietly taking the reference."""
    from fedml_tpu.ops import dispatch
    from fedml_tpu.ops.flash_attention import flash_attention

    monkeypatch.setattr(dispatch, "default_platform", lambda: "tpu")
    q = jnp.ones((1, 2, 128, 32), jnp.bfloat16)
    with pytest.raises(ValueError, match="Only interpret mode"):
        flash_attention(q, q, q)


def test_sharded_flash_attention_matches_reference():
    """The shard_map wrapper the trainer hands the model: batch over
    (dp, fsdp), heads over tp, nothing gathered, same numbers."""
    from fedml_tpu.ops.flash_attention import (
        make_sharded_flash_attention,
        reference_attention,
    )
    from fedml_tpu.train.llm.sharding import make_mesh

    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 1), (4, 4, 64, 16))
    k = jax.random.normal(jax.random.fold_in(key, 2), (4, 2, 64, 16))
    v = jax.random.normal(jax.random.fold_in(key, 3), (4, 2, 64, 16))
    fn = make_sharded_flash_attention(mesh, ("dp", "fsdp"), "tp")
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(reference_attention(q, k, v)),
        rtol=1e-5, atol=1e-5)
    assert "all-gather" not in jax.jit(fn).lower(q, k, v).compile().as_text()


# -- compile cache -----------------------------------------------------------

def test_compile_cache_honours_env_and_sets_nothing(monkeypatch):
    from fedml_tpu.utils.compile_cache import (
        compile_cache_dir,
        configure_compile_cache,
    )

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache_dir() == "/some/dir"
    assert configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before  # untouched


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    from fedml_tpu.utils.compile_cache import (
        compile_cache_dir,
        configure_compile_cache,
    )

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        first = configure_compile_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert configure_compile_cache() == first == compile_cache_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_conftest_cache_follows_the_rule():
    """The suite's own cache is the env's (exported for subprocess
    tests) and no cache path is built from a temp name, pid or time."""
    assert (jax.config.jax_compilation_cache_dir
            == os.environ["JAX_COMPILATION_CACHE_DIR"])
    for rel in ("tests/conftest.py", "fedml_tpu/utils/compile_cache.py"):
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        assert not any(w in src for w in ("tempfile", "getpid", "time.")), rel


# -- program catalog ---------------------------------------------------------

def test_catalog_raises_compiler_errors_once():
    """A compiler refusal surfaces from the first call; the raw jit is
    not tried as a silent second compile."""
    from fedml_tpu.telemetry.profiling import wrap_jit

    traces = []

    def f(x):
        traces.append(1)
        from fedml_tpu.ops.flash_attention import flash_attention

        return flash_attention(x, x, x, interpret=False)  # CPU cannot lower

    prog = wrap_jit("test/refused", jax.jit(f))
    with pytest.raises(ValueError, match="Only interpret mode"):
        prog(jnp.ones((1, 2, 128, 32), jnp.bfloat16))
    assert len(traces) == 1
    assert prog.record.fallback_calls == 0
    assert prog.last_compiled is None


def test_catalog_keeps_fallback_for_unstageable_signature():
    from fedml_tpu.telemetry.profiling import wrap_jit

    class Opaque:  # not a pytree leaf the AOT staging API accepts
        pass

    prog = wrap_jit("test/unstageable", jax.jit(lambda x, o: x + 1))
    with pytest.raises(TypeError):
        prog(jnp.ones(2), Opaque())  # the raw jit raises the same TypeError
    assert prog.record.fallback_calls == 1


def test_catalog_exposes_the_executable_it_ran():
    from fedml_tpu.telemetry.profiling import get_catalog, wrap_jit

    prog = wrap_jit("test/held", jax.jit(lambda x: x * 2))
    assert prog.last_compiled is None
    prog(jnp.ones(4))
    assert "multiply" in prog.last_compiled.as_text()
    assert get_catalog().program("test/held") is prog


# -- batch rule --------------------------------------------------------------

@pytest.mark.parametrize("dp,fsdp,tp,want", [
    (1, 1, 1, 2), (1, 4, 1, 8), (2, 2, 2, 8), (1, 1, 4, 2)])
def test_per_device_batch_size_is_per_device(dp, fsdp, tp, want):
    from fedml_tpu.models.llm.llama import LlamaConfig
    from fedml_tpu.train.llm.sharding import make_mesh
    from fedml_tpu.train.llm.trainer import LLMTrainer

    class Args:
        max_seq_length = 8
        per_device_batch_size = 2

    mesh = make_mesh(dp=dp, fsdp=fsdp, tp=tp,
                     devices=jax.devices()[:dp * fsdp * tp])
    tr = LLMTrainer(LlamaConfig.tiny(lora_rank=2), Args(), mesh=mesh)
    assert tr.batch_size == want


# -- multichip bench ---------------------------------------------------------

def test_efficiency_basis_comes_from_the_platform():
    from fedml_tpu.parallel.multichip import efficiency_basis

    class Dev:
        def __init__(self, platform):
            self.platform = platform

    assert efficiency_basis(jax.devices()) == "serialized-virtual-mesh"
    assert efficiency_basis([Dev("cpu")] * 64) == "serialized-virtual-mesh"
    assert efficiency_basis([Dev("tpu")] * 4) == "wall-clock"
