"""In-process device check: a run that expects the TPU fails without one.

Every entry point (``train.py``, ``bench.py``, ``tools/serve_bench.py``,
``tools/serve_fleet.py`` replicas) calls :func:`require_accelerator` right
after the backend comes up. The platform must be ``tpu`` unless the CPU
was asked for in so many words — ``JAX_PLATFORMS=cpu`` in the environment,
``--platform cpu``, or ``jax.config.update("jax_platforms", "cpu")`` (how
``tests/conftest.py`` pins the suite); all three land in
``jax.config.jax_platforms``, which is the one thing read here. A run that
silently landed on the CPU would otherwise complete, exit 0 and record
CPU numbers under a device's name.

A missing chip ends the run with exit code 3 and the
``backend_unreachable`` manifest outcome — the contract the elasticity
supervisor (``sav_tpu/train/supervisor.py``), the regression sentinel and
``tools/run_report.py`` key on. The check runs in the process that will
use the chip: a chip belongs to one process at a time, so a child that
initialised the backend first would hold it against its own parent.
"""

from __future__ import annotations

import json
import sys
from typing import Optional

EXIT_BACKEND_UNREACHABLE = 3


class BackendUnreachableError(RuntimeError):
    """No TPU was found and the CPU was not asked for."""


def cpu_requested() -> bool:
    """True when jax was pinned to the CPU explicitly (env, flag or config)."""
    import jax

    platforms = (jax.config.jax_platforms or "").strip().lower()
    return platforms == "cpu"


def check_accelerator() -> str:
    """Platform of device 0 (``"tpu"``, or ``"cpu"`` when it was asked for).

    Raises :class:`BackendUnreachableError` when the backend fails to
    initialise (a chip held by another process, no chip at all) or comes
    up as anything but a TPU without the CPU having been requested.
    """
    import jax

    try:
        platform = jax.devices()[0].platform
    except RuntimeError as e:  # jax raises RuntimeError on backend init failure
        raise BackendUnreachableError(
            f"backend initialisation failed: {e}"
        ) from e
    if platform == "tpu" or (platform == "cpu" and cpu_requested()):
        return platform
    raise BackendUnreachableError(
        f"expected a TPU, found platform {platform!r} "
        f"(jax_platforms={jax.config.jax_platforms!r}); pass --platform cpu "
        "or set JAX_PLATFORMS=cpu to run on the CPU on purpose"
    )


def abort_unreachable(
    tag: str,
    error: BaseException,
    manifest=None,
    record: Optional[dict] = None,
) -> int:
    """The abort contract in one place; returns the exit code (3).

    Stamps ``backend_unreachable`` on ``manifest`` (a
    :class:`~sav_tpu.obs.manifest.RunManifest`, so the run record never
    degrades to prose only), prints the one stderr line wrapper scripts
    grep for, and — for the CLIs whose stdout is one parseable JSON record
    (``bench.py``, ``tools/serve_bench.py``) — prints ``record`` with the
    outcome, what the check found and the manifest pointer added.
    """
    message = f"{tag}: accelerator backend unreachable: {error}; aborting"
    found = {"error": str(error)[:500]}
    if manifest is not None:
        manifest.finalize(
            "backend_unreachable",
            error=message,
            exit_code=EXIT_BACKEND_UNREACHABLE,
            notes={"device_check": found},
        )
    print(message, file=sys.stderr)
    if record is not None:
        print(json.dumps({
            **record,
            "outcome": "backend_unreachable",
            "device_check": found,
            "manifest": manifest.path if manifest is not None else None,
        }))
    return EXIT_BACKEND_UNREACHABLE


def require_accelerator(tag: str, manifest=None) -> str:
    """:func:`check_accelerator`, or :func:`abort_unreachable` and exit
    the process with code 3."""
    try:
        return check_accelerator()
    except BackendUnreachableError as e:
        raise SystemExit(abort_unreachable(tag, e, manifest))  # savlint: disable=SAV114 -- THE documented exit-3 abort contract wrapper scripts and the supervisor key on; the manifest was finalized above
