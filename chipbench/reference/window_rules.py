"""What one step of a fast-sync catch-up must do with a run of downloaded
blocks, and what it must answer (reference: blockchain/v0/reactor.go
poolRoutine, generalised from two peeked blocks to a window that one
flush of the verifier holds).

A run is consecutive blocks of one validator set; block i carries the
LastCommit that proves block i - 1, so the run's commits are numbered
like its blocks: commit i is block i's LastCommit.  A step takes the k
leading blocks and verifies k + 1 commits: `VerifyCommit` ("full") on
commits 0 .. k-1, then `VerifyCommitLight` ("light") on commit k, which
the successor carries and which proves the newest block taken.

The cut: a job is counted at its commit's signature count, and a step
takes the most blocks whose jobs together count at most `max_rows`; never
fewer than one, never the run's last block (nothing proves it yet).

The answer is ("accept", k), or ("wrong_signature", (height, row)) for the
first failing consulted row of the first failing job, in job order, with
`height` the height that job's commit is for.  Commits past k are never
consulted.
"""

from __future__ import annotations

from chipbench.reference.commit_rules import consulted_rows, expected_outcome


def cut(sig_counts: list[int], max_rows: int) -> int:
    """Blocks a step takes off a run whose commits carry `sig_counts`
    signatures (one count a downloaded block, in order; at least two)."""
    if len(sig_counts) < 2:
        raise ValueError("a step needs a block and its successor")
    k, rows = 1, sig_counts[0] + sig_counts[1]
    while k < len(sig_counts) - 1 and rows + sig_counts[k + 1] <= max_rows:
        rows += sig_counts[k + 1]
        k += 1
    return k


def jobs(sig_counts: list[int], max_rows: int) -> list[tuple[str, int]]:
    """(mode, commit) of the step's jobs, in the order they are verified."""
    k = cut(sig_counts, max_rows)
    return [("full", i) for i in range(k)] + [("light", k)]


def consulted(step: list[tuple[str, int]], powers: list[int]) -> list[int]:
    """Leading rows each job consults of its commit."""
    return [consulted_rows(mode, powers) for mode, _ in step]


def expected_step(step: list[tuple[str, int]], powers: list[int],
                  heights: list[int], suspects: list, row_ok) -> tuple:
    """`heights[i]`: the height commit i is for; `suspects[i]`: the rows of
    commit i that may fail (every other row is assumed valid); `row_ok(i,
    r)`: the plain reference's verdict on row r of commit i."""
    for mode, i in step:
        kind, at = expected_outcome(mode, powers, suspects[i],
                                    lambda r, i=i: row_ok(i, r))
        if kind == "wrong_signature":
            return (kind, (heights[i], at))
        if kind != "accept":
            return (kind, heights[i])
    return ("accept", len(step) - 1)
