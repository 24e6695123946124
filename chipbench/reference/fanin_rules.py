"""What a gateway that verifies for many light clients at once must answer
each of them, and what serving them may cost (reference: `tendermint
light`, cmd/tendermint/commands/light.go -> light/proxy -> light/rpc.Client:
one verifying process, many RPC clients behind it, every header through
light/verifier.go VerifyAdjacent -> VerifyCommitLight).

Several clients' headers may share ONE flush of the verifier.  Two rules
hold whatever shared it:

  isolation  a header's answer is the answer `VerifyCommitLight` gives
             that header ALONE: ("accept", None), or ("wrong_signature",
             i) with i its first failing consulted row.  A forged header
             fails its own client and no other.
  once       every job's consulted rows reach the device once.  From the
             calls made, and nothing else: the rows the device resolved
             are the calls' consulted rows; the verify service made as
             many flushes as the gateway did (a refused flush is not
             verified again); the gateway flushed as many jobs as were
             sent (none dropped, none doubled); no job joined another
             (the clients ask for distinct headers), none was shed, and
             no row was answered from the verified-signature cache.

Imports nothing of the program.
"""

from __future__ import annotations

from chipbench.reference.commit_rules import consulted_rows, expected_outcome


def expected_alone(powers: list[int], suspects, row_ok) -> tuple:
    """Isolation: what the client that sent this header is told, from the
    plain reference's verdicts on the header's own suspect rows."""
    return expected_outcome("light", powers, suspects, row_ok)


def consulted(powers: list[int]) -> int:
    return consulted_rows("light", powers)


def once(jobs_sent: int, rows_sent: int, seen: dict) -> dict:
    """The path numbers, each 0 where the rule holds.  `jobs_sent`,
    `rows_sent`: the jobs of the calls made and the rows they consult;
    `seen`: what the program's counters moved by meanwhile —
    `rows_resolved_on_device`, `service_flushes`, `gateway_flushes`,
    `gateway_jobs_flushed`, `gateway_coalesced`, `gateway_shed`,
    `cache_hits`."""
    return {
        "rows_off_device": rows_sent - seen["rows_resolved_on_device"],
        "cache_hits": seen["cache_hits"],
        "flushes_off": seen["service_flushes"] - seen["gateway_flushes"],
        "jobs_off": seen["gateway_jobs_flushed"] - jobs_sent,
        "coalesced": seen["gateway_coalesced"],
        "shed": seen["gateway_shed"],
    }
