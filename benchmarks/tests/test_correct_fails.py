"""``correct`` has been shown to fail: the control, and a run with the
timed path broken underneath. Tiny widths on the CPU; the readings at the
cells' own size are in PERF.md (made on the chip by ``calibrate.py``)."""
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import check, program, spec

from .test_rehearsal import PEAKS, FakeDevice


def _broken_build(monkeypatch, breaker):
    """``program.build`` whose round program is wrapped by ``breaker``."""
    sound_build = program.build

    def build(seed, config, traffic):
        api = sound_build(seed, config, traffic)
        engine = api.client.engine
        compile_round = engine.compile_federated_round
        engine.compile_federated_round = lambda *a: breaker(compile_round(*a))
        return api

    monkeypatch.setattr(program, "build", build)


def state_unchanged(fed_round):
    """The round runs, and hands back the state it was given."""
    def broken(params, opt_state, global_lora, xs, ys, ms, weights):
        import jax
        import jax.numpy as jnp

        kept = jax.tree.map(jnp.copy, (params, opt_state, global_lora))
        loss = fed_round(params, opt_state, global_lora, xs, ys, ms,
                         weights)[3]
        return (*kept, loss)
    return broken


def half_left_out(fed_round):
    """Half of the clients never train; the mean is taken over the rest."""
    def broken(params, opt_state, global_lora, xs, ys, ms, weights):
        n = xs.shape[0] // 2
        return fed_round(params, opt_state, global_lora, xs[:n], ys[:n],
                         ms[:n], weights[:n])
    return broken


@pytest.mark.parametrize("breaker,fails", [
    (state_unchanged, {"count", "grad", "grad2", "change"}),
    (half_left_out, {"count", "grad", "grad2", "change"}),
], ids=["state_unchanged", "half_left_out"])
def test_a_broken_round_is_not_correct(tiny_root, monkeypatch, breaker, fails):
    _broken_build(monkeypatch, breaker)
    cell = spec.Cell("tiny.round-tiny", root=tiny_root)
    out = bench_run.measure(cell, 5, 0.1, False, [FakeDevice()], PEAKS)
    assert out["correct"] is False
    failed = {k for k, v in out["compared"].items() if not v["ok"]}
    assert failed == fails, out["compared"]
    if breaker is state_unchanged:
        # by the worst-leaf measure an unchanged state reads exactly 1
        assert out["compared"]["change"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_control_is_not_correct(tiny_root, seed):
    """The reference in fp8 put in the program's place fails a number; the
    reference in bfloat16 — what the program computes in — does not."""
    cell = spec.Cell("tiny.round-tiny", root=tiny_root)
    want = bench_run.reference_round(cell, seed)
    fp8 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="fp8"), want),
        cell.limits)
    assert fp8["correct"] is False, fp8
    bf16 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="bfloat16"), want),
        cell.limits)
    assert bf16["correct"] is True, bf16


def test_the_readings_limits_are_set_from(tiny_root, tmp_path):
    """``calibrate.py`` at tiny widths: one line a seed, the program's gaps
    on every seed, the control's, bfloat16's and the planted fault's on
    the control seeds, many seeds through one compiled round."""
    import json

    from benchmarks import calibrate

    out = tmp_path / "cal.jsonl"
    assert calibrate.main([
        "--workload", "tiny.round-tiny", "--seeds", f"21,{2 ** 31 + 22}",
        "--control-seeds", "21", "--out", str(out), "--root", tiny_root,
        "--any-device"]) == 0
    first, second = [json.loads(x) for x in out.read_text().splitlines()]
    assert second["seed"] == 2 ** 31 + 22 and "control_fp8" not in second
    for line in (first, second):
        assert line["program"]["count"] == 0
        assert line["program"]["grad"] < 0.015
    assert first["control_fp8"]["grad"] > 3 * first["program"]["grad"]
    assert first["as_bfloat16"]["grad"] < 0.015
    assert first["fault_half"]["count"] == 4
    assert first["as_float32_highest"]["grad"] < 1e-3
