"""CPU rehearsals of the benchmark's own code: tiny widths, no subprocess."""
import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_CONFIG = {
    "source": "test only", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 256,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "run": {"lora_rank": 4, "lora_alpha": 4.0,
            "lora_targets": ["q_proj", "k_proj", "v_proj", "o_proj"],
            "base_dtype": "bfloat16", "compute_dtype": "bfloat16",
            "adapter_dtype": "float32", "use_flash_attention": True}}
TINY_TRAFFIC = {
    "kind": "federated_round", "clients_total": 4, "clients_per_round": 4,
    "local_steps": 2, "per_device_batch": 1, "seq_len": 32,
    "samples_per_client": 4, "remat_policy": "none", "learning_rate": 1e-4,
    "max_grad_norm": 1.0, "weight_decay": 0.0,
    "data": {"maker": "markov_band", "step_probs": [0.8, 0.2], "noise": 0.05},
}
# set from CPU readings of the tiny cell: program 0.2-0.9 %, fp8 control
# 2.2-5.3 %, half of the clients left out 20-46 % on grad/grad2/change. The
# loss is not compared, as in the real cells: no control reads 3x a sound run
TINY_LIMITS = {"limits": {"count": 0, "grad": 0.015, "grad2": 0.015,
                          "change": 0.02}}


@pytest.fixture()
def tiny_root(tmp_path):
    """A copy of the benchmark with one cell, configuration, mix and metric
    ADDED by new files and new entries alone — no file that exists edited."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = tmp_path / "benchmarks"
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    tied = dict(TINY_CONFIG, tie_word_embeddings=True, num_key_value_heads=4)
    (bench / "configs" / "tiny-tied.json").write_text(json.dumps(tied))
    (bench / "traffic" / "round-tiny.json").write_text(json.dumps(TINY_TRAFFIC))
    for cell in ("tiny.round-tiny", "tiny-tied.round-tiny"):
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(TINY_LIMITS))
    (bench / "metrics" / "rounds_traced.json").write_text(json.dumps(
        {"reads": "whole rounds in the traced window"}))
    (bench / "metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for name in ("tiny", "tiny-tied"):
        bm["configs"].append({"name": name, "source": "test only",
                              "file": f"benchmarks/configs/{name}.json",
                              "reduced": [], "why": "test"})
        bm["workloads"].append({"name": f"{name}.round-tiny", "config": name,
                                "traffic": "round-tiny", "chips": 1,
                                "why": "test"})
    bm["per_layer"].append({
        "name": "rounds_traced", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry",
        "moves": "train_tokens_per_s",
        "workloads": ["tiny.round-tiny", "tiny-tied.round-tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    return str(tmp_path)
