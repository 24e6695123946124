"""What a light client that SKIPS must do between a trusted height and a
target, and what it must answer (reference: light/client.go verifySkipping,
light/verifier.go VerifyNonAdjacent / VerifyAdjacent,
types/validator_set.go VerifyCommitLightTrusting / VerifyCommitLight).

Plain data, nothing of the program:

    a validator set   [(address, power), ...] in the set's own order
    a commit          [(flag, address), ...], row i signed by validator i of
                      the set the commit is of; flag "commit" (for the
                      block), "nil" or "absent"
    a chain           chain(height) -> (set, commit) of that height
    row_ok(h, i)      the plain reference's verdict on row i of the commit
                      of height h

**The trusting rule** (a jump over a gap): the commit's for-block rows in
commit order, each matched to the TRUSTED set by address (an unknown
address is passed over), a second vote of one trusted validator refused,
until the matched power exceeds total x numerator // denominator (the
reference's integer division).  A consulted row that fails is named before
anything the walk meets after it; too little power is an answer of its own.
**The light rule** (every accepted jump, and an adjacent step alone): the
new set's own for-block rows in order until power > 2/3.

**The error mapping**: too little TRUSTED power refuses the jump, and the
client pivots; anything else — a wrong signature, a double vote, a new set
short of 2/3 — fails the verification between those two heights.

**The schedule** (`bisect`): a cache of candidates, deepest = lowest, with
the target at depth 0.  The jump from the verified height to the candidate
at the current depth is tried; refused at the cache's end, the pivot
(verified + candidate) // 2 is fetched and pushed, and the depth grows by
one; accepted, the candidate becomes the verified height, leaves the cache,
and the depth falls by one (the client this benchmark runs; see `assumed`
in chipbench/configs/skip-1000.json for what upstream's v0.34 does there);
accepted at depth 0 ends the walk.  A pivot that equals either end:
bisection exhausted.

**The flushes and the cache** (`walk`): a check hands the verifier the rows
it selected, all of them, whatever their verdicts (a batch is verified
whole).  A row proven valid earlier IN THE SAME WALK is answered from the
verified-signature cache: those are the rows two checks share.  The others
are fresh, and a check with a fresh row makes one flush.

Header-level checks (hashes, times, expiry, the next-validators link of an
adjacent step) are honest in every item the benchmark builds and are not
re-derived here.
"""

from __future__ import annotations

from dataclasses import dataclass, field

FOR_BLOCK = "commit"


def trusting_select(commit, trusted, num: int, den: int):
    """(rows, ended): the commit rows the trusting walk reaches if every
    signature holds, and how it ends: ("enough", None), ("double_vote",
    (trusted index, first row, second row)) or ("not_enough", (power
    matched, power needed))."""
    needed = sum(p for _, p in trusted) * num // den
    index = {addr: (v, p) for v, (addr, p) in enumerate(trusted)}
    seen: dict[int, int] = {}
    rows, power = [], 0
    for i, (flag, addr) in enumerate(commit):
        if flag != FOR_BLOCK or addr not in index:
            continue
        v, p = index[addr]
        if v in seen:
            return rows, ("double_vote", (v, seen[v], i))
        seen[v] = i
        rows.append(i)
        power += p
        if power > needed:
            return rows, ("enough", None)
    return rows, ("not_enough", (power, needed))


def light_select(commit, powers):
    """(rows, ended) of the new set's own check: for-block rows in order
    until power > 2/3; ended "enough" or "not_enough"."""
    needed = sum(powers) * 2 // 3
    rows, power = [], 0
    for i, (flag, _) in enumerate(commit):
        if flag != FOR_BLOCK:
            continue
        rows.append(i)
        power += powers[i]
        if power > needed:
            return rows, ("enough", None)
    return rows, ("not_enough", (power, needed))


def check_answer(rows, ended, ok) -> tuple:
    """What a check says: its first failing selected row, in order, or how
    the selection ended.  `ok(i)`: the verdict on commit row i."""
    for i in rows:
        if not ok(i):
            return ("wrong_signature", i)
    return ended


def trusting_answer(commit, trusted, num, den, ok) -> tuple:
    """The trusting rule's answer on one commit: ("enough", None),
    ("wrong_signature", row), ("double_vote", ...), ("not_enough", ...)."""
    rows, ended = trusting_select(commit, trusted, num, den)
    return check_answer(rows, ended, ok)


@dataclass
class Check:
    kind: str               # "trusting" | "light"
    height: int             # the height whose commit it consults
    rows: list              # commit rows selected, in order
    shared: list = field(default_factory=list)   # of them, proven valid earlier in the walk
    fresh: list = field(default_factory=list)    # the others: one flush if any


def jump(chain, trusted_h: int, new_h: int, num: int, den: int, row_ok):
    """One attempt to trust `new_h` from `trusted_h`: (outcome, checks).
    outcome: ("accepted", None) | ("refused", (matched, needed)) |
    ("failed", reason), reason = ("wrong_signature", row) | ("double_vote",
    ...) | ("insufficient_power", new_h)."""
    new_set, commit = chain(new_h)
    ok = lambda i: row_ok(new_h, i)  # noqa: E731
    checks = []
    if new_h != trusted_h + 1:
        rows, ended = trusting_select(commit, chain(trusted_h)[0], num, den)
        checks.append(Check("trusting", new_h, rows))
        kind, what = check_answer(rows, ended, ok)
        if kind == "not_enough":
            return ("refused", what), checks
        if kind != "enough":
            return ("failed", (kind, what)), checks
    rows, ended = light_select(commit, [p for _, p in new_set])
    checks.append(Check("light", new_h, rows))
    kind, what = check_answer(rows, ended, ok)
    if kind == "not_enough":
        return ("failed", ("insufficient_power", new_h)), checks
    if kind != "enough":
        return ("failed", (kind, what)), checks
    return ("accepted", None), checks


def pivot(verified: int, candidate: int) -> int:
    return (verified + candidate) // 2


def bisect(trusted_h: int, target_h: int, try_jump, pivot_of=pivot):
    """The schedule.  `try_jump(from, to)` -> jump's outcome.  Returns
    (answer, attempts, fetched): answer = ("accept", heights trusted after
    `trusted_h`, in order) or ("failed", from, to, reason); attempts =
    [(from, to, "accepted" | "refused" | "failed")]; fetched = the heights
    asked of the provider, in order, the target first."""
    cache, depth = [target_h], 0
    verified, trusted, attempts, fetched = trusted_h, [], [], [target_h]
    while True:
        candidate = cache[depth]
        kind, what = try_jump(verified, candidate)
        attempts.append((verified, candidate, kind))
        if kind == "refused":
            if depth == len(cache) - 1:
                p = pivot_of(verified, candidate)
                if p in (verified, candidate):
                    return (("failed", verified, candidate, ("exhausted", None)),
                            attempts, fetched)
                cache.append(p)
                fetched.append(p)
            depth += 1
        elif kind == "accepted":
            verified = candidate
            trusted.append(verified)
            if depth == 0:
                return ("accept", tuple(trusted)), attempts, fetched
            cache.pop(depth)
            depth -= 1
        else:
            return ("failed", verified, candidate, what), attempts, fetched


@dataclass
class Walk:
    answer: tuple           # bisect's
    attempts: list          # [(from, to, outcome)]
    fetched: list           # heights asked of the provider, in order
    checks: list            # Check, in the order they are made
    flushes: int            # checks with a fresh row

    def consulted(self) -> list:
        """(height, row) of every row a check selected, in order, a row
        that two checks share twice."""
        return [(c.height, i) for c in self.checks for i in c.rows]

    def shared(self) -> int:
        return sum(len(c.shared) for c in self.checks)

    def fresh(self) -> int:
        return sum(len(c.fresh) for c in self.checks)


def walk(chain, trusted_h: int, target_h: int, num: int, den: int, row_ok,
         pivot_of=pivot) -> Walk:
    """The whole verification of `target_h` from `trusted_h`."""
    checks: list[Check] = []
    valid: set = set()          # (height, row) proven valid so far

    def try_jump(a: int, b: int):
        outcome, made = jump(chain, a, b, num, den, row_ok)
        for c in made:
            c.shared = [i for i in c.rows if (c.height, i) in valid]
            c.fresh = [i for i in c.rows if (c.height, i) not in valid]
            valid.update((c.height, i) for i in c.fresh if row_ok(c.height, i))
        checks.extend(made)
        return outcome

    answer, attempts, fetched = bisect(trusted_h, target_h, try_jump, pivot_of)
    return Walk(answer, attempts, fetched, checks,
                sum(1 for c in checks if c.fresh))
