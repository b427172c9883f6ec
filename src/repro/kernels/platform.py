"""Where the Pallas kernels run: Mosaic on a TPU, the interpreter on the
CPU, nowhere else.

Every kernel wrapper takes an ``interpret`` flag; ``interpret()`` is the
one place that picks it from the JAX backend.  There is no silent
fallback: a TPU always gets the compiled Mosaic kernel, and a backend
that is neither raises instead of running the interpreter on an
accelerator.  Kernels that Mosaic cannot lower yet call ``no_mosaic``
so that a TPU run stops with an error naming them (ROADMAP.md S1).
"""
from __future__ import annotations

import jax


def interpret() -> bool:
    """``True`` (interpret mode) on the CPU, ``False`` (Mosaic) on a TPU;
    any other backend raises."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (Mosaic) or 'cpu' (interpret "
        f"mode); the default backend is {backend!r}")


def no_mosaic(kernel: str, interpret_mode: bool) -> None:
    """Refuse to compile ``kernel`` for a TPU: it has no Mosaic lowering
    yet and runs only in interpret mode on the CPU."""
    if not interpret_mode:
        raise NotImplementedError(
            f"{kernel} has no Mosaic lowering yet (its in-kernel LUT "
            f"gather does not lower on TPU); it runs only in interpret "
            f"mode on the CPU — see ROADMAP.md S1")
