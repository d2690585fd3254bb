"""Where a Pallas kernel runs: compiled on a TPU, interpreted on the CPU."""

from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Interpreter mode for kernels whose caller did not say.

    Compiled (``False``) on a TPU; the Pallas interpreter on the CPU, which
    is how tests and rehearsals run the same kernel code. A chip run cannot
    land here by accident: every entry point refuses a CPU that was not
    asked for (``sav_tpu.utils.device_check``) before any kernel is traced.
    """
    return jax.default_backend() != "tpu"
