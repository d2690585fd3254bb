"""Runtime sanitizers: hard-fail the invariants savlint cannot prove.

Static analysis (savlint) catches the *lexical* shapes of the classic
TPU hot-loop regressions; this module catches the *dynamic* ones, on an
opt-in flag (``TrainConfig.sanitize`` / ``train.py --sanitize``), in the
spirit of ASan/TSan: cheap enough to leave on for smoke runs, loud the
instant the discipline breaks instead of hours later in a goodput
report.

Two sanitizers, both scoped to the steady-state hot loop (armed after
the first completed step, so compilation and one-time setup transfers
are exempt):

- **Transfer sanitizer** — ``jax.transfer_guard_host_to_device
  ("disallow")``: implicit host→device transfers (a numpy batch leaking
  into the jitted step, a Python scalar silently uploaded per step)
  raise immediately. Explicit transfers stay legal, which is exactly
  the repo's contract: the feeder's ``device_put`` (on its own thread —
  the guard is thread-local and never sees it) and the serial
  fallback's explicit placement both pass. The device→host direction is
  deliberately unguarded: the loop's intentional syncs (log window,
  checkpoint serialization) are statically audited instead — each
  carries a savlint SAV101 pragma with its justification.
- **Retrace sanitizer** — a :class:`~sav_tpu.obs.memory.RetraceCounter`
  on the jitted step that raises :class:`RetraceSanitizerError` the
  moment the compile cache grows after warmup. PR 1's ``retraces``
  metric *reports* silent recompilation at the next log window; the
  sanitizer turns it into a step-attributed hard failure (each silent
  retrace is seconds to minutes of compile, so "fail at the step that
  caused it" beats "notice it in telemetry later").
"""

from __future__ import annotations

import contextlib
from typing import Optional

from sav_tpu.obs.memory import RetraceCounter


class RetraceSanitizerError(RuntimeError):
    """The jitted step re-traced after the sanitizer was armed."""


class StepSanitizer:
    """Arms both hot-loop sanitizers around a jitted step function.

    Lifecycle (mirrors fit()'s loop):

    - construct before the loop (counts any pre-loop traces as warmup);
    - :meth:`arm` after the FIRST completed step — swallows the warmup
      trace(s) and enters the transfer guard;
    - :meth:`check` after every subsequent dispatch — raises on a fresh
      trace (tracing is synchronous at call time, so a retrace is
      visible the moment the dispatch returns);
    - :meth:`close` in the loop's ``finally`` — exits the transfer
      guard (it is a thread-local config context and must unwind on the
      thread that entered it).

    ``transfer_guard=None`` disables the transfer arm (retrace checking
    only) for callers embedded in code that legitimately relies on
    implicit transfers.
    """

    def __init__(
        self,
        jit_fn,
        *,
        transfer_guard: Optional[str] = "disallow",
        tag: str = "sanitize",
    ):
        self._retraces = RetraceCounter(jit_fn)
        self._transfer_guard = transfer_guard
        self._tag = tag
        self._stack = contextlib.ExitStack()
        self.armed = False

    def arm(self) -> None:
        """Enter steady state: warmup traces forgiven, guards live."""
        if self.armed:
            return
        if self._transfer_guard is not None:
            import jax

            self._stack.enter_context(
                jax.transfer_guard_host_to_device(self._transfer_guard)
            )
        self._retraces.delta()  # the first compile is expected, not a retrace
        self.armed = True

    def check(self, step: int) -> None:
        """Raise if the step function traced again since the last check."""
        if not self.armed:
            return
        new = self._retraces.delta()
        if new:
            raise RetraceSanitizerError(
                f"{self._tag}: jitted step re-traced {new}x at step {step} — "
                "steady-state dispatch must hit the compile cache. Usual "
                "causes: a batch whose shape/dtype drifted, a Python scalar "
                "argument that changed value, or a leaked weak type. "
                "Reproduce the trigger with savlint (SAV104) or "
                "TrainConfig.diagnostics retrace telemetry, then pin the "
                "offending argument."
            )

    def close(self) -> None:
        """Unwind the transfer guard; idempotent, safe before arm()."""
        self._stack.close()
        self.armed = False

    def __enter__(self) -> "StepSanitizer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def active(self) -> bool:
        """False when the running jax cannot count traces (the counter
        degrades to zero — the retrace arm is then a no-op)."""
        return self._retraces.active
