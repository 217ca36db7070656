"""End-to-end arithmetic, kept apart so that it can be tested alone."""
from __future__ import annotations


def per_family_seconds(elapsed_s: float, completed: int) -> float:
    """Window seconds over families completed; the family in flight at
    the end of the window is finished first, and counted, by the caller."""
    if completed <= 0:
        raise ValueError("no family completed in the window")
    return elapsed_s / completed
