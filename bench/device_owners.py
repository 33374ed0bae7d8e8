"""Device time a step by owner: the traced window's per-op seconds
(``trace_reduce``) joined with the instruction-to-owner table that the step
program recorded when it compiled (``repro.obs.device_ops``).

An owner is an IR op's scope (``l1.traversal.h_out``), ``loss`` or
``optimizer``, each forward or backward; instructions without one are
unattributed. A program without the table reads nothing.
"""
from __future__ import annotations

from typing import Callable, Optional

STEP_MODULE = "jit_hector_train_step"


def ms_per_step(data: dict, keep: Callable) -> Optional[float]:
    """Device ms a step in the ops whose owner ``keep`` accepts (``keep``
    gets an ``Owner``, or None for an op without one); None where the
    program recorded no table for the step module."""
    try:
        from repro.obs import device_ops
    except ImportError:
        return None
    owners = device_ops.attribute(data["trace"].op_seconds, STEP_MODULE)
    if owners is None or not data.get("steps"):
        return None
    return 1e3 * sum(s for o, s in owners.items() if keep(o)) / data["steps"]


def model(o) -> bool:
    """Owned by an IR op or the loss (not the optimizer)."""
    return o is not None and o.owner != "optimizer"
