"""The device as JAX reports it, and the one table of peaks.

Peaks of one chip, keyed by ``device_kind``. Source for ``TPU v5 lite``:
Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s). A kind that is not in the table is an error,
never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class NoChipError(RuntimeError):
    """JAX found no accelerator, or not the number of chips the cell asks for."""


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {kind!r}; add a sourced row to PEAKS")
    return PEAKS[kind]


def describe() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_chips(chips: int) -> dict:
    """The device description, or :class:`NoChipError` when it is not
    ``chips`` TPU chips."""
    found = describe()
    if found["platform"] != "tpu" or found["count"] != chips:
        raise NoChipError(f"the cell needs {chips} TPU chip(s); JAX reports {found}")
    return found


def allocator_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, by the runtime's allocator
    statistics (0 where the backend keeps none, as the CPU does)."""
    import jax

    return int(max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()
    ))


def memory_report(memory: dict) -> dict:
    """The result line's memory keys, each number under its own name.

    On this runtime the allocator's peak counts the buffers a program holds
    and not an executable's temporaries (1.97 GB read after a step whose
    ``memory_analysis`` temporaries are 7.89 GB; PERF.md). So the line
    carries the measured ``memory_allocator_peak_bytes``, and beside it
    ``memory_program_bytes``: the allocator's reading of the buffers held
    while the timed program runs plus the compiler's count of that
    program's temporaries, which the driver gives (``resident_bytes``,
    ``step_temp_bytes``). ``memory_peak_bytes``, the key the contract
    reads, is the larger of the two, and ``memory_peak_origin`` names
    which it is."""
    measured = allocator_peak_bytes()
    program = memory["resident_bytes"] + memory["step_temp_bytes"]
    return {
        "memory_peak_bytes": max(measured, program),
        "memory_peak_origin": (
            "allocator peak_bytes_in_use" if measured >= program
            else "allocator bytes_in_use + compiled program's temp_size_in_bytes"
        ),
        "memory_allocator_peak_bytes": measured,
        "memory_program_bytes": program,
    }
