"""On-chip krum over a cohort whose stacked N×D fp32 exceeds 16 GB HBM
(VERDICT r4 task 3's measured proof).

N=8 clients x D=600M coords -> 19.2 GB stacked fp32: cannot be
device-resident on a v5e (16 GB). The blockwise path streams [N, C]
slices and accumulates the N x N gram on device; client 0 is a planted
byzantine (large-scale noise) that krum must drop.

Blocks are SYNTHESIZED ON DEVICE from per-(client, block) PRNG keys —
pushing 19 GB of host numpy to the device would measure the host link,
not the defense. The
math exercised (per-block generation + gram update + selection) is
byte-identical to what host-streamed blocks would run.

Timing: the gram carry chains every block program (real data
dependency); one readback at the end; long-minus-short over full passes.

Run:  python tools/defense_big_bench.py [--d 600000000] [--clients 8]
"""
import argparse
import json
import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp

from fedml_tpu.core.security.defense.blockwise import _gram_update
from fedml_tpu.core.security.defense.krum import select_krum

ap = argparse.ArgumentParser()
ap.add_argument("--d", type=int, default=600_000_000)
ap.add_argument("--clients", type=int, default=8)
ap.add_argument("--block", type=int, default=1 << 25)  # 1 GB at N=8
ap.add_argument("--evil-scale", type=float, default=30.0)
cli = ap.parse_args()

N, D, C = cli.clients, cli.d, cli.block
n_blocks = (D + C - 1) // C
stacked_gb = 4.0 * N * D / 1e9
dev = jax.devices()[0]
print(f"device={dev.device_kind}  N={N} D={D/1e9:.2f}B  "
      f"stacked={stacked_gb:.1f} GB (> HBM)  blocks={n_blocks}x{C}",
      flush=True)


@jax.jit
def make_block(key, scales):
    # benign rows ~ N(0, 0.01); the byzantine row is scaled noise —
    # same structure as ByzantineAttack(attack_mode="random")
    x = jax.random.normal(key, (N, C), jnp.float32)
    return x * scales[:, None]


scales = jnp.asarray([cli.evil_scale] + [0.01] * (N - 1), jnp.float32)
root = jax.random.key(7)


def full_pass(g, salt):
    for b in range(n_blocks):
        g = _gram_update(g, make_block(jax.random.fold_in(root, salt + b),
                                       scales))
    return g


def run_chain(n_passes):
    t0 = time.perf_counter()
    g = jnp.zeros((N, N), jnp.float32)
    for p in range(n_passes):
        g = full_pass(g, p * n_blocks)
    float(jnp.sum(g))  # single readback forces the whole chain
    return time.perf_counter() - t0


run_chain(1)  # compile + warm
t_short = run_chain(1)
t_long = run_chain(4)
sec_per_pass = (t_long - t_short) / 3
gbps = 4.0 * N * D / sec_per_pass / 1e9

# correctness on the same synthesized cohort: krum must drop client 0
g = full_pass(jnp.zeros((N, N), jnp.float32), 0)
import numpy as np

gh = np.asarray(g)
sq = np.diag(gh)
dmat = np.maximum(sq[:, None] + sq[None, :] - 2 * gh, 0.0)
keep = select_krum(jnp.asarray(dmat), f=1, k=N - 2)
assert 0 not in keep, f"krum failed to drop the planted byzantine: {keep}"

print(json.dumps({
    "defense": "krum (blockwise gram)",
    "stacked_gb": round(stacked_gb, 1),
    "sec_per_defense_pass": round(sec_per_pass, 3),
    "effective_gb_per_s": round(gbps, 1),
    "survivors": keep,
    "byzantine_dropped": 0 not in keep,
    "timing": "chained gram carry, long-minus-short readback",
}), flush=True)
