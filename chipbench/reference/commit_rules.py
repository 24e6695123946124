"""What `VerifyCommit` and `VerifyCommitLight` must answer, from per-row
verdicts (reference: types/validator_set.go :662-712 and :720-766).

A commit here is equal-power and complete: every validator precommits the
block.  `row_ok(i)` is the verdict of the plain reference for row i.  The
answer is ("accept", None) or ("wrong_signature", i) with i the first
consulted row that fails, in order — exactly what the served path has to
say; ("insufficient_power", None) cannot occur on a complete commit
without a failing row and is kept for completeness.
"""

from __future__ import annotations


def consulted_rows(mode: str, powers: list[int]) -> int:
    """How many leading rows the mode consults: all of them for "full";
    for "light" the shortest prefix whose power exceeds two thirds."""
    if mode == "full":
        return len(powers)
    needed = sum(powers) * 2 // 3
    running = 0
    for i, p in enumerate(powers):
        running += p
        if running > needed:
            return i + 1
    return len(powers)


def expected_outcome(mode: str, powers: list[int], suspects, row_ok) -> tuple:
    """`suspects`: the rows that may fail (every other row was signed
    honestly and is assumed valid here; a sample of them is verified by
    the reference in the check itself)."""
    n = consulted_rows(mode, powers)
    for i in sorted(suspects):
        if i < n and not row_ok(i):
            return ("wrong_signature", i)
    needed = sum(powers) * 2 // 3
    if sum(powers[:n]) <= needed:
        return ("insufficient_power", None)
    return ("accept", None)


def implied(outcome: tuple, row: int) -> bool | None:
    """What a call's answer says of a consulted row: valid, invalid, or
    nothing (rows after the one it refused)."""
    kind, at = outcome
    if kind == "accept":
        return True
    if kind == "wrong_signature":
        return True if row < at else (False if row == at else None)
    return None
