"""Training stack — pjit trainer, config, schedules, checkpointing,
and the elastic-training supervisor.

Re-exports are lazy (PEP 562 via :mod:`sav_tpu._lazy`, the same pattern
as :mod:`sav_tpu.obs` / :mod:`sav_tpu.utils`):
:mod:`sav_tpu.train.supervisor` is stdlib-only by contract (it runs in
the parent of on-chip jobs, and a parent that touched the backend would
hold the chip against its child), so the package import must not
drag jax/orbax in eagerly.
"""

from __future__ import annotations

from sav_tpu._lazy import install_lazy_exports

_EXPORTS = {
    "Checkpointer": "sav_tpu.train.checkpoint",
    "TrainConfig": "sav_tpu.train.config",
    "TrainState": "sav_tpu.train.state",
    "Trainer": "sav_tpu.train.trainer",
    "make_optimizer": "sav_tpu.train.optimizer",
    "warmup_cosine_schedule": "sav_tpu.train.optimizer",
    "weight_decay_mask": "sav_tpu.train.optimizer",
    "get_preset": "sav_tpu.train.presets",
    "preset_names": "sav_tpu.train.presets",
    "register_preset": "sav_tpu.train.presets",
    "Supervisor": "sav_tpu.train.supervisor",
}

__all__ = list(_EXPORTS)

__getattr__, __dir__ = install_lazy_exports(
    globals(),
    _EXPORTS,
    {"checkpoint", "config", "optimizer", "presets", "state", "supervisor",
     "trainer"},
)
