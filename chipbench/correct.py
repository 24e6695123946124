"""How `correct` is decided: every call of the window against the plain
reference, and the path the calls took against the counters.

Each number compared stands beside its limit.  All are exact comparisons,
so every limit is 0.  The first two are the same for every entry point
(the entry's module, chipbench/entries/<entry>.py, gives the rule):

  calls_wrong        calls whose answer is not the one the entry's rule
                     (`expected`) gives from the plain reference's verdicts
                     on that item's suspect rows (small-order keys,
                     corrupted rows)
  sampled_rows_wrong honestly signed rows, drawn from the seed among the
                     rows the window's calls consulted, on which the plain
                     reference's verdict differs from what the call's
                     answer says of the row (`implied`)

The path numbers are the entry's (`path`); `device_path` below is the
one every entry that must resolve on the device shares:

  rows_off_device    signatures submitted in the window minus those whose
                     verdict came from the chip (path="device")
  host_flushes       flushes the service resolved on the host
  device_errors      device failures the service counted
  cache_hits         calls answered from the verified-signature cache: the
                     cell would have measured the cache
  compiles_in_window compile events inside the window
  route_other        1 if the last flush was not routed as the cell's
                     chips imply (device/pipelined on one chip,
                     device/mesh_sharded on more)
"""

from __future__ import annotations

import random

from chipbench.reference import ed25519_zip215 as ref

HONEST_SAMPLE = 96


def check_calls(entry, data, calls, seed: int) -> dict:
    """calls_wrong and sampled_rows_wrong, from the plain reference's
    verdicts on the rows the window's calls consulted.  `entry`: the
    module of the cell's entry point; `data.pool[c.item]` is the item a
    call took."""
    verdicts: dict[tuple[int, int], bool] = {}

    def row_ok(k: int, i: int) -> bool:
        if (k, i) not in verdicts:
            verdicts[k, i] = ref.verify(*data.pool[k].row(i))
        return verdicts[k, i]

    expected: dict[int, tuple] = {}
    wrong = []
    for c in calls:
        if c.item not in expected:
            expected[c.item] = entry.expected(
                data, data.pool[c.item], lambda i, k=c.item: row_ok(k, i))
        if c.outcome != expected[c.item]:
            wrong.append((c.item, c.outcome, expected[c.item]))

    rng = random.Random(seed ^ 0x5EED)
    called = sorted({c.item for c in calls})
    last = {c.item: c.outcome for c in calls}
    rows_wrong = []
    sample = 0
    while called and sample < HONEST_SAMPLE:
        k = rng.choice(called)
        i = rng.randrange(data.pool[k].n_rows)
        sample += 1
        if i in data.pool[k].suspects:
            continue
        said = entry.implied(last[k], i)
        if said is not None and said != row_ok(k, i):
            rows_wrong.append((k, i, said))
    return {"calls_wrong": len(wrong), "sampled_rows_wrong": len(rows_wrong),
            "detail": {"wrong": wrong[:8], "rows_wrong": rows_wrong[:8],
                       "reference_rows": len(verdicts)}}


def device_route(chips: int) -> tuple:
    """How the service routes a flush every chip of the cell takes part in."""
    return ("device", "pipelined") if chips == 1 else ("device", "mesh_sharded")


def device_path(before: dict, after: dict, calls, compiles: int, route,
                chips: int) -> dict:
    """The path numbers of an entry whose every flush must resolve on the
    device.  `before`/`after`: system.counters() around `calls`;
    `compiles`: compile events between them; `route`: the service's last."""
    return {
        "rows_off_device": sum(c.rows for c in calls) - (
            after["resolved_on_device"] - before["resolved_on_device"]),
        "host_flushes": after["host_flushes"] - before["host_flushes"],
        "device_errors": after["device_errors"],
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "compiles_in_window": compiles,
        "route_other": 0 if tuple(route or ()) == device_route(chips) else 1,
    }


def compared(numbers: dict) -> tuple[bool, dict]:
    """({name: {"value", "limit"}}, all within their limits)."""
    out = {k: {"value": v, "limit": 0} for k, v in numbers.items() if k != "detail"}
    return all(abs(e["value"]) <= e["limit"] for e in out.values()), out
