"""Events of a device trace that span a body.

A ``while`` or ``conditional`` instruction's event lasts as long as its body,
whose operations are events of their own: a sum over all events counts a
loop twice (ROADMAP B5). The readers that came with ``ling.train_ep64_4k``
count a loop by its body alone.
"""

from __future__ import annotations

import re

SPANS_ITS_BODY = re.compile(r"^(while|conditional)([._]\d+)*$")


def once(op_seconds: dict) -> dict:
    """The operations' seconds without the events that span a body."""
    return {name: s for name, s in op_seconds.items() if not SPANS_ITS_BODY.match(name)}
