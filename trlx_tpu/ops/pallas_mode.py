"""The one place that decides whether a ``pallas_call`` runs interpreted.

Both kernel modules (ops/pallas_attention.py, ops/paged_attention.py)
ask here at trace time. On a TPU backend the answer is always False:
the kernels are compiled by Mosaic or the program fails — an interpreted
kernel on a TPU would pass every parity check while measuring nothing.
Off-TPU (the CPU backend of tier-1 and local drives) the Pallas
interpreter is the only way the kernel logic can run at all.

Tests that LOWER a kernel for the TPU platform from a CPU host patch
``interpret`` on this module (tests/test_kernel_lowering.py); nothing
else overrides it.
"""

import jax


def interpret() -> bool:
    return jax.default_backend() != "tpu"
