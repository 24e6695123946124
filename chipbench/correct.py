"""How `correct` is decided: every call of the window against the plain
reference, and the path the calls took against the counters.

Each number compared stands beside its limit.  All are exact comparisons,
so every limit is 0:

  calls_wrong        calls whose answer (accept / refuse naming a row / an
                     error) is not the one the commit rules give from the
                     plain reference's verdicts on that commit's suspect
                     rows (small-order keys, corrupted rows)
  sampled_rows_wrong honestly signed rows, drawn from the seed among the
                     rows the window's calls consulted, on which the plain
                     reference's verdict differs from what the call's
                     answer says of the row
  rows_off_device    signatures submitted in the window minus those whose
                     verdict came from the chip (path="device")
  host_flushes       flushes the service resolved on the host
  device_errors      device failures the service counted
  cache_hits         calls answered from the verified-signature cache: the
                     cell would have measured the cache
  compiles_in_window compile events inside the window
  route_other        1 if the last flush was not routed as the cell's
                     chips imply (device/pipelined on one chip)
"""

from __future__ import annotations

import random

from chipbench.reference import ed25519_zip215 as ref
from chipbench.reference.commit_rules import expected_outcome

HONEST_SAMPLE = 96


def _implied(outcome: tuple, row: int) -> bool | None:
    """What a call's answer says of a consulted row: valid, invalid, or
    nothing (rows after the one it refused)."""
    kind, at = outcome
    if kind == "accept":
        return True
    if kind == "wrong_signature":
        return True if row < at else (False if row == at else None)
    return None


def check_calls(data, calls, seed: int) -> dict:
    """calls_wrong and sampled_rows_wrong, from the plain reference's
    verdicts on the rows the window's calls consulted."""
    verdicts: dict[tuple[int, int], bool] = {}

    def row_ok(ci: int, i: int) -> bool:
        if (ci, i) not in verdicts:
            verdicts[ci, i] = ref.verify(*data.pool[ci].row(data.pubs, i))
        return verdicts[ci, i]

    expected: dict[int, tuple] = {}
    wrong = []
    for c in calls:
        if c.commit not in expected:
            pc = data.pool[c.commit]
            expected[c.commit] = expected_outcome(
                data.mode, data.powers, pc.suspects,
                lambda i, ci=c.commit: row_ok(ci, i))
        if c.outcome != expected[c.commit]:
            wrong.append((c.commit, c.outcome, expected[c.commit]))

    rng = random.Random(seed ^ 0x5EED)
    called = sorted({c.commit for c in calls})
    last = {c.commit: c.outcome for c in calls}
    rows_wrong = []
    sample = 0
    while called and sample < HONEST_SAMPLE:
        ci = rng.choice(called)
        i = rng.randrange(data.consulted)
        sample += 1
        if i in data.pool[ci].suspects:
            continue
        said = _implied(last[ci], i)
        if said is not None and said != row_ok(ci, i):
            rows_wrong.append((ci, i, said))
    return {"calls_wrong": len(wrong), "sampled_rows_wrong": len(rows_wrong),
            "detail": {"wrong": wrong[:8], "rows_wrong": rows_wrong[:8],
                       "reference_rows": len(verdicts)}}


def check_path(before: dict, after: dict, rows_submitted: int,
               compiles_in_window: int, route, want_route) -> dict:
    return {
        "rows_off_device": rows_submitted - (after["resolved_on_device"]
                                             - before["resolved_on_device"]),
        "host_flushes": after["host_flushes"] - before["host_flushes"],
        "device_errors": after["device_errors"],
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "compiles_in_window": compiles_in_window,
        "route_other": 0 if tuple(route or ()) == tuple(want_route) else 1,
    }


def compared(numbers: dict) -> tuple[bool, dict]:
    """({name: {"value", "limit"}}, all within their limits)."""
    out = {k: {"value": v, "limit": 0} for k, v in numbers.items() if k != "detail"}
    return all(abs(e["value"]) <= e["limit"] for e in out.values()), out
