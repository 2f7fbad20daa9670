"""The rest of a run after the look for a chip, at tiny widths on the CPU."""
import json

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import spec


class FakeDevice:
    platform, device_kind = "cpu", "test"

    def memory_stats(self):
        return None


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _measure(root, cell_name, seed=3, seconds=0.2, **kw):
    cell = spec.Cell(cell_name, root=root)
    return cell, bench_run.measure(cell, seed, seconds, False, [FakeDevice()],
                                   PEAKS, **kw)


@pytest.mark.parametrize("cell_name", ["tiny.round-tiny", "tiny-tied.round-tiny"])
def test_sound_run_is_correct(tiny_root, cell_name, capsys):
    cell, out = _measure(tiny_root, cell_name)
    print(json.dumps(out["compared"]))
    assert out["correct"], out["compared"]
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"train_tokens_per_s", "round_s", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["compared"]["count"]["value"] == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    prog = next(x for x in lines if x.get("phase") == "program")
    # one program, compiled once, no eval: only the round has calls
    called = {k for k, v in prog["counters"].items()
              if k.endswith(".calls") and v}
    assert called == {"llm/fused_round.calls"}
    assert prog["counters"]["llm/fused_round.n_signatures"] == 1
    assert prog["counters"]["llm/fused_round.fallback_calls"] == 0
