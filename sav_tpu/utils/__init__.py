"""Utility surface: metrics, parameter overviews, profiling, debug, writers.

Re-exports are lazy (PEP 562): importing a stdlib-only submodule such as
``sav_tpu.utils.device_check`` must not drag ``jax`` into the process —
the serve pool's parent imports it, and a parent that touched the backend
would hold the chip against its replicas.
"""

from __future__ import annotations

from sav_tpu._lazy import install_lazy_exports

_EXPORTS = {
    "topk_correct": "sav_tpu.utils.metrics",
    "accuracy_topk": "sav_tpu.utils.metrics",
    "cross_entropy": "sav_tpu.utils.metrics",
    "count_parameters": "sav_tpu.utils.param_overview",
    "parameter_overview": "sav_tpu.utils.param_overview",
    "log_parameter_overview": "sav_tpu.utils.param_overview",
    "StepTimer": "sav_tpu.utils.profiler",
    "benchmark_fn": "sav_tpu.utils.profiler",
    "trace": "sav_tpu.utils.profiler",
    "assert_all_finite": "sav_tpu.utils.debug",
    "checkify_step": "sav_tpu.utils.debug",
    "find_nonfinite": "sav_tpu.utils.debug",
    "global_norm_nonfinite": "sav_tpu.utils.debug",
    "JsonlWriter": "sav_tpu.utils.writers",
    "LoggingWriter": "sav_tpu.utils.writers",
    "MetricWriter": "sav_tpu.utils.writers",
    "MultiWriter": "sav_tpu.utils.writers",
    "TensorBoardWriter": "sav_tpu.utils.writers",
    "WandbWriter": "sav_tpu.utils.writers",
}

__all__ = list(_EXPORTS)


__getattr__, __dir__ = install_lazy_exports(
    globals(),
    _EXPORTS,
    {"device_check", "debug", "metrics", "param_overview", "profiler",
     "writers"},
)
