"""Orbax checkpointing with restore (the reference only ever saved —
/root/reference/train.py:123-127; restore was never wired, SURVEY.md §5).

Async, sharded-aware saves via ``orbax.checkpoint.CheckpointManager``;
``restore_latest`` makes runs preemption-safe: on restart the trainer
resumes from the last step automatically, falling back to the previous
step when the newest checkpoint is unreadable (a preemption can land
anywhere; one torn artifact must not strand the whole run). ``wait``
takes an optional bound so crash paths can drain an in-flight async save
without inheriting the hang they are escaping (docs/elasticity.md).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Optional

import jax


def _tree_paths(tree: Any, prefix: tuple = ()) -> list:
    """Flatten any nested dict/list/tuple metadata tree into path tuples
    (leaves = anything non-container). Orbax item metadata arrives as
    plain containers, so no pytree registry is needed."""
    if isinstance(tree, dict):
        out = []
        for key, value in tree.items():
            out.extend(_tree_paths(value, prefix + (str(key),)))
        return out
    if isinstance(tree, (list, tuple)):
        out = []
        for i, value in enumerate(tree):
            out.extend(_tree_paths(value, prefix + (str(i),)))
        return out
    return [prefix]


def detect_opt_layout(paths: list) -> dict:
    """Classify a checkpoint's optimizer-state layout from its tree paths.

    Two things a file from outside may differ in (a mismatch at restore
    otherwise surfaces as an opaque tree-structure error):

    - the moments' layout: ``optax.flatten`` (what trainers up to PR 41
      could write) stores the Adam moments as ONE flat array per moment —
      the ``mu``/``nu`` segments are leaves. The per-leaf layout, the one
      the trainer builds, mirrors the parameter tree below them.
    - ``ema_decay``: ``track_params_ema`` adds an ``ema`` subtree.

    Returns ``{"fused": bool|None, "ema": bool}`` — ``None`` when the
    checkpoint has no recognizable Adam moments (nothing to detect).
    """
    fused: Optional[bool] = None
    ema = False
    for path in paths:
        for i, seg in enumerate(path):
            if seg == "ema":
                ema = True
            if seg in ("mu", "nu"):
                # Leaf directly at mu/nu → flat buffer; anything nested
                # below it → per-leaf moment tree.
                fused = (i == len(path) - 1) if fused is None else (
                    fused and i == len(path) - 1
                )
    return {"fused": fused, "ema": ema}


class Checkpointer:
    def __init__(self, directory: str, *, keep: int = 3, read_only: bool = False):
        """``read_only`` opens an existing checkpoint dir for restore-only
        use (warm starts): no directory creation — a typo'd path raises
        instead of materializing an empty dir — and no retention policy."""
        # Imported with the first Checkpointer, not with the module: orbax
        # brings google.cloud.logging, 12 s of a bare import on the TPU's
        # host and 25-30 s in a process that already holds jax (PERF.md
        # section 5, ``startup.import_s``). A run that saves nothing and
        # restores nothing never pays it.
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self._dir = os.path.abspath(directory)
        if read_only:
            if not os.path.isdir(self._dir):
                raise FileNotFoundError(
                    f"checkpoint directory does not exist: {self._dir!r}"
                )
            options = self._ocp.CheckpointManagerOptions(read_only=True)
        else:
            os.makedirs(self._dir, exist_ok=True)
            options = self._ocp.CheckpointManagerOptions(
                max_to_keep=keep, create=True, enable_async_checkpointing=True
            )
        # The item handler is registered up front so ``item_metadata``
        # (the opt-state layout probe) works on a FRESH manager — a
        # restarted process probes before its first save/restore, and
        # without the registration orbax returns a placeholder.
        self._mgr = self._ocp.CheckpointManager(
            self._dir,
            options=options,
            item_handlers=self._ocp.StandardCheckpointHandler(),
        )

    @property
    def directory(self) -> str:
        return self._dir

    def save(self, step: int, state: Any) -> None:
        self._mgr.save(step, args=self._ocp.args.StandardSave(state))

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self) -> list:
        """Committed checkpoint steps, ascending."""
        return sorted(self._mgr.all_steps())

    def opt_layout(self, step: Optional[int] = None) -> dict:
        """Probe the saved opt-state layout without loading any arrays
        (:func:`detect_opt_layout` over the checkpoint's metadata tree).
        ``{}`` when there is no checkpoint or the probe fails — callers
        treat that as "nothing to detect", never as an error."""
        if step is None:
            step = self._mgr.latest_step()
        if step is None:
            return {}
        try:
            meta = self._mgr.item_metadata(step)
            # CompositeArgs-style wrappers hold the real tree under the
            # item name; unwrap defensively across orbax versions.
            for attr in ("tree", "item_metadata"):
                meta = getattr(meta, attr, meta)
            paths = [
                p for p in _tree_paths(_plain(meta)) if "opt_state" in p
            ]
            if not paths:
                return {}
            return detect_opt_layout(paths)
        except Exception:
            return {}

    def restore_latest(self, template: Any) -> Optional[Any]:
        """Restore the newest loadable checkpoint into ``template``'s
        structure/shardings.

        Returns None when no checkpoint exists. When the newest step
        fails to load (torn by a preemption mid-save, bit rot), older
        steps are tried in turn — a warning names the fallback — and the
        *newest* step's error is re-raised only when every retained step
        fails (so structural mismatches keep their original diagnosis).
        """
        steps = self.all_steps()
        if not steps:
            return None
        abstract = jax.tree.map(self._ocp.utils.to_shape_dtype_struct, template)
        first_error: Optional[Exception] = None
        for step in reversed(steps):
            try:
                restored = self._mgr.restore(
                    step, args=self._ocp.args.StandardRestore(abstract)
                )
            except Exception as e:  # noqa: BLE001 — every orbax failure
                if first_error is None:
                    first_error = e
                else:
                    logging.warning(
                        "checkpoint step %d also failed to restore: %r",
                        step, e,
                    )
                continue
            if first_error is not None:
                logging.warning(
                    "newest checkpoint failed to restore (%r); resumed "
                    "from the older step %d instead",
                    first_error, step,
                )
            return restored
        raise first_error

    def restore_params_only(
        self, template: Any, step: Optional[int] = None
    ) -> Optional[Any]:
        """Restore ``params``/``batch_stats``/``step`` WITHOUT reading
        opt_state — the serving path (docs/serving.md).

        A training checkpoint's optimizer state is 2-3x the parameter
        bytes (Adam moments, optionally EMA); an inference engine that
        restored the full TrainState would spend most of its HBM on
        buffers it immediately drops. This restores through orbax's
        partial-tree path (``PyTreeRestore(item=subset, transforms={})``)
        so the opt_state arrays are never read off disk, let alone
        materialized on device — and because opt_state is skipped
        entirely, flat-buffer and per-leaf moment layouts
        (:func:`detect_opt_layout`) are both accepted; the probed layout is
        only logged for provenance.

        Args:
          template: ``{"params": ..., "batch_stats": ..., "step": ...}``
            of concrete arrays or ``jax.ShapeDtypeStruct`` leaves;
            leaves carrying a ``sharding`` restore directly onto it.
          step: checkpoint step (default: newest).

        Returns the restored template-structured dict, or None when the
        directory holds no checkpoint.
        """
        if step is None:
            step = self._mgr.latest_step()
        if step is None:
            return None
        layout = self.opt_layout(step)
        if layout:
            logging.info(
                "params-only restore from step %d (skipping %s opt-state "
                "layout%s)",
                step,
                "flat-buffer" if layout.get("fused") else "per-leaf",
                " + EMA" if layout.get("ema") else "",
            )
        abstract = jax.tree.map(self._ocp.utils.to_shape_dtype_struct, template)
        restore_args = jax.tree.map(
            lambda s: self._ocp.ArrayRestoreArgs(
                dtype=s.dtype, sharding=getattr(s, "sharding", None)
            ),
            abstract,
        )
        # A read-only PyTree-handler manager over the same directory:
        # StandardSave writes through PyTreeCheckpointHandler, so the
        # on-disk layout is shared; only PyTreeRestore exposes the
        # partial-tree ``transforms`` path.
        reader = self._ocp.CheckpointManager(
            self._dir,
            options=self._ocp.CheckpointManagerOptions(read_only=True),
            item_handlers=self._ocp.PyTreeCheckpointHandler(),
        )
        try:
            return reader.restore(
                step,
                args=self._ocp.args.PyTreeRestore(
                    item=abstract, transforms={}, restore_args=restore_args
                ),
            )
        finally:
            reader.close()

    def restore_raw(self, step: Optional[int] = None) -> Optional[Any]:
        """Restore a checkpoint in its *saved* structure (no template).

        For warm starts across architectures/resolutions, where the saved
        shapes deliberately differ from the current state's (e.g. the
        224-pretrain position table loaded into a 384 finetune —
        ``sav_tpu.models.surgery`` resamples it afterwards).
        """
        if step is None:
            step = self._mgr.latest_step()
        if step is None:
            return None
        return self._mgr.restore(step, args=self._ocp.args.StandardRestore())

    def wait(self, timeout_s: Optional[float] = None) -> bool:
        """Block until in-flight async saves commit.

        ``timeout_s`` bounds the wait (crash paths and the watchdog's
        pre-exit drain must not inherit the hang they are escaping —
        docs/elasticity.md); returns False when the bound expired with a
        save still in flight. Orbax commits each step by atomic rename,
        so an abandoned wait can leave a *missing* newest step, never a
        torn one — ``restore_latest``'s fallback covers the rest.
        """
        if timeout_s is None:
            self._mgr.wait_until_finished()
            return True
        done = threading.Event()

        def _wait():
            try:
                self._mgr.wait_until_finished()
            finally:
                done.set()

        threading.Thread(
            target=_wait, name="checkpoint-wait", daemon=True
        ).start()
        return done.wait(timeout_s)

    def close(self) -> None:
        self._mgr.close()


def _plain(meta: Any) -> Any:
    """Orbax metadata tree → plain containers (best effort): metadata
    objects occasionally wrap dicts in Mapping views."""
    if isinstance(meta, dict):
        return {k: _plain(v) for k, v in meta.items()}
    if isinstance(meta, (list, tuple)):
        # Lists ARE the result (namedtuple-saved nodes come back as
        # sequences whose constructors don't take an iterable).
        return [_plain(v) for v in meta]
    try:  # Mapping-like (orbax CompositeResults)
        items = dict(meta.items())
    except (AttributeError, TypeError):
        return meta
    return {k: _plain(v) for k, v in items.items()}
