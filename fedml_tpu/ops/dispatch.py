"""Which form of a Pallas kernel a call gets.

Both kernels in this package (flash attention, the fused int8
dequant-matmul) exist in three forms: the compiled Mosaic kernel, the
same kernel under the Pallas interpreter, and a plain-XLA reference.
The choice is made HERE, from the default backend's platform, and
nowhere else — and device discovery is not wrapped: a machine whose
chip cannot be found must fail, not quietly train on a reference path.

On a TPU the answer is always the compiled kernel or an exception.
Off-TPU ``interpret=None`` takes whatever the kernel's CPU behaviour is
(``off_tpu``), ``interpret=True`` runs the interpreter, and
``interpret=False`` emits the compiled kernel regardless — that is how a
test compiles for a *described* chip (``jax.experimental.topologies``)
while the default backend is the CPU; executed on the CPU, Pallas itself
raises at lowering.
"""
from __future__ import annotations

from typing import Optional

import jax

COMPILED = "compiled"
INTERPRET = "interpret"
REFERENCE = "reference"


def default_platform() -> str:
    """Platform of the default backend's first device (no try/except)."""
    return jax.devices()[0].platform


def kernel_mode(interpret: Optional[bool], off_tpu: str) -> str:
    """``COMPILED`` / ``INTERPRET`` / ``REFERENCE`` for one kernel call.

    ``off_tpu`` is what ``interpret=None`` means away from a TPU:
    ``REFERENCE`` for flash attention, ``INTERPRET`` for the dequant
    matmul (the behaviour the CPU tests rely on).
    """
    if interpret is False:
        return COMPILED
    on_tpu = default_platform() == "tpu"
    if interpret is None:
        return COMPILED if on_tpu else off_tpu
    if on_tpu:
        raise RuntimeError(
            "interpret=True on a TPU backend: Pallas kernels run compiled "
            "on the chip or not at all")
    return INTERPRET
