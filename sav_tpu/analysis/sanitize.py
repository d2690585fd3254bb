"""Runtime sanitizer: hard-fail the invariant savlint cannot prove.

Static analysis (savlint) catches the *lexical* shapes of the classic
TPU hot-loop regressions; this module catches the *dynamic* one, on an
opt-in flag (``TrainConfig.sanitize`` / ``train.py --sanitize``), in the
spirit of ASan/TSan: cheap enough to leave on for smoke runs, loud the
instant the discipline breaks instead of hours later in a goodput
report.

**Transfer sanitizer** — ``jax.transfer_guard_host_to_device
("disallow")``, scoped to the steady-state hot loop (armed after the
first completed step, so compilation and one-time setup transfers are
exempt): implicit host→device transfers (a numpy batch leaking into the
step, a Python scalar silently uploaded per step) raise immediately.
Explicit transfers stay legal, which is exactly the repo's contract: the
feeder's ``device_put`` (on its own thread — the guard is thread-local
and never sees it) and the serial fallback's explicit placement both
pass. The device→host direction is deliberately unguarded: the loop's
intentional syncs (log window, checkpoint serialization) are statically
audited instead — each carries a savlint SAV101 pragma with its
justification.

A step that would have to compile again (a batch whose shape or dtype
drifted) needs no sanitizer: ``Trainer.fit`` calls one executable, which
refuses arguments it was not compiled for, at the offending step, with
this flag or without it.
"""

from __future__ import annotations

import contextlib


class StepSanitizer:
    """Arms the transfer guard around the hot loop.

    Lifecycle (mirrors fit()'s loop):

    - :meth:`arm` after the FIRST completed step enters the guard;
    - :meth:`close` in the loop's ``finally`` exits it (it is a
      thread-local config context and must unwind on the thread that
      entered it).
    """

    def __init__(self):
        self._stack = contextlib.ExitStack()
        self.armed = False

    def arm(self) -> None:
        """Enter steady state: the guard is live."""
        if self.armed:
            return
        import jax

        self._stack.enter_context(
            jax.transfer_guard_host_to_device("disallow")
        )
        self.armed = True

    def close(self) -> None:
        """Unwind the transfer guard; idempotent, safe before arm()."""
        self._stack.close()
        self.armed = False

    def __enter__(self) -> "StepSanitizer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
