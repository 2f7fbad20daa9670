"""chip_smoke.py rehearsed without the chip: the refusal to run off-TPU,
and its phase functions at tiny size on the CPU mesh (rehearsals (a) and
(b) of the on-chip-measurement guide; the real-shape compile for the
described v5e is tests/test_tpu_compile.py)."""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(seed, per_device_batch=1):
    return chip_smoke.round_config(
        seed, model_size="tiny", layers=2, per_device_batch=per_device_batch,
        seq_len=16, vocab=64, lora_rank=4, clients=4, samples_per_client=4)


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=120)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_fused_rounds_phase_tiny_on_cpu(monkeypatch):
    """Rehearsal (a): the default phase's functions, one device, no child
    process anywhere on the path."""
    def no_children(*a, **k):
        raise AssertionError(f"chip_smoke's path spawned a process: {a}")

    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.setattr(os, "fork", no_children)
    from fedml_tpu.train.llm.sharding import make_mesh

    live_before = {id(a) for a in jax.live_arrays()}
    run = chip_smoke.federated_rounds(
        _tiny(0), mesh=make_mesh(devices=jax.devices()[:1]))
    # nothing of the model outlives the phase (the process-wide program
    # catalog must not keep the trainer, and with it the base, resident)
    assert [a.shape for a in jax.live_arrays()
            if id(a) not in live_before] == []
    chip_smoke.check_rounds(run, require_kernel=False)
    assert run["mesh"]["fsdp"] == 1 and run["global_batch"] == 1
    assert run["catalog"]["name"] == "llm/fused_round"
    assert run["catalog"]["calls"] == 2
    assert run["catalog"]["n_signatures"] == 1  # round 1 did not recompile
    assert run["catalog"]["fallback_calls"] == 0
    assert run["flash_kernel_calls"] == 0  # CPU: the XLA reference path
    with pytest.raises(SystemExit, match="no Pallas kernel"):
        chip_smoke.check_rounds(run, require_kernel=True)


def test_multichip_phase_on_four_virtual_devices():
    """Rehearsal (b): --multichip's comparison, one-device mesh vs fsdp=4."""
    both = chip_smoke.multichip_compare(
        lambda b: _tiny(3, per_device_batch=b), jax.devices()[:4])
    one, sharded = both["one"], both["sharded"]
    assert one["mesh"]["fsdp"] == 1 and sharded["mesh"]["fsdp"] == 4
    assert one["global_batch"] == sharded["global_batch"] == 4
    assert chip_smoke.losses_agree(one["train_loss"], sharded["train_loss"])
    assert sharded["catalog"]["calls"] == 2  # per-run records, not summed
    assert sharded["catalog"]["mesh_spec"]["axes"]["fsdp"] == 4
    assert chip_smoke.losses_agree(one["test_loss"], sharded["test_loss"])
    assert "all-gather" in sharded["collectives"]  # ZeRO-3 weight gathers
    assert "all-gather" not in one["collectives"]


@pytest.mark.parametrize("a,b,ok", [
    ([10.4, 9.8], [10.41, 9.79], True),
    ([10.4, 9.8], [10.4, 9.2], False),
])
def test_losses_agree_tolerance(a, b, ok):
    assert chip_smoke.losses_agree(a, b) is ok


@pytest.mark.parametrize("per_device,ok", [
    ([100, 101, 99, 100], True),
    ([400, 10, 10, 10], False),   # the base piled on device 0
])
def test_balanced(per_device, ok):
    assert chip_smoke.balanced(per_device) is ok
