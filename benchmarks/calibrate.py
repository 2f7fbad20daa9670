#!/usr/bin/env python3
"""Read what the limits of ``correct`` are set from, on the chip, at the
cell's own size, many seeds in one process (set-up is long).

For every seed: the harness's weights and data, the program's first call
of ``train_one_round``, its state, then the plain reference of that call.
For the ``--control-seeds`` also the control (the reference in fp8 put in
the program's place), the reference in bfloat16 (what the program should
read like) and the planted fault (half of the clients left out, the mean
taken over the rest), each against the float32 reference; on the first of
them also the reference at ``Precision.HIGHEST`` against the reference as
run (``HIGH``). One JSON line per seed in ``--out``. Not part of a
benchmark run.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import check, program, spec  # noqa: E402


def gaps(nums: dict) -> dict:
    return {k: v["gap"] for k, v in nums.items()} | {
        k + "_leaf": v["leaf"] for k, v in nums.items() if "leaf" in v}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=spec.ROOT,
                    help="where BENCHMARK.json and benchmarks/ are")
    ap.add_argument("--any-device", action="store_true",
                    help="skip the look for a chip (CPU rehearsal only)")
    cli = ap.parse_args(argv)
    seeds = [int(s) for s in cli.seeds.split(",") if s]
    control = {int(s) for s in cli.control_seeds.split(",") if s}

    cell = spec.Cell(cli.workload, root=cli.root)
    if not cli.any_device:
        bench_run.find_chip(cell)
    bench_run.cache_everything()
    highest_done = False
    os.makedirs(os.path.dirname(os.path.abspath(cli.out)), exist_ok=True)
    api = None
    with open(cli.out, "a") as out:
        for seed in seeds:
            t0 = time.perf_counter()
            if api is None:
                api = program.build(seed, cell.config, cell.traffic)
                program.install_weights(api, seed, cell.config)
            else:
                program.reseed(api, seed, cell.config, cell.traffic)
            first = api.train_one_round(1)
            snap = program.snapshot(api)
            snap["loss"] = float(first["train_loss"])
            program.free(api)
            t1 = time.perf_counter()
            want = bench_run.reference_round(cell, seed)
            t2 = time.perf_counter()
            line = {"workload": cell.name, "seed": seed,
                    "program_s": t1 - t0, "reference_s": t2 - t1,
                    "loss_program": snap["loss"], "loss_reference": want["loss"],
                    "program": gaps(check.numbers(snap, want))}
            if seed in control:
                for key, kw in (("control_fp8", {"precision": "fp8"}),
                                ("as_bfloat16", {"precision": "bfloat16"}),
                                ("fault_half", {"fault": "half_clients"})):
                    got = bench_run.reference_round(cell, seed, **kw)
                    line[key] = gaps(check.numbers(got, want))
                    del got
                    gc.collect()
                line["control_s"] = time.perf_counter() - t2
                if not highest_done:  # once: HIGH against HIGHEST
                    highest_done = True
                    got = bench_run.reference_round(
                        cell, seed, precision="float32_highest")
                    line["as_float32_highest"] = gaps(check.numbers(got, want))
                    del got
                    gc.collect()
            del want, snap
            gc.collect()
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
