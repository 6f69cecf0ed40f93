"""Where the Pallas kernels run: compiled on the chip, interpreted only on
the CPU backend the tests use. Any other backend is an error — a kernel
must never be interpreted silently."""

from __future__ import annotations

import jax


def interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels need the tpu backend (or cpu, interpreted, for "
        f"tests); the default backend is {backend!r}")
