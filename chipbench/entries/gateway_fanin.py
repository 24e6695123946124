"""Entry point `gateway.Gateway.verify_commits`: one verifying process
with many light clients behind it.  A call is one client handing the
gateway the header it wants checked (a `CommitVerifyJob(mode="light")` of
the configuration's one validator set) and waiting for its own answer;
the gateway's coalescer gathers what the clients in flight sent inside its
linger window and verifies it as ONE batched call.

The data is `light-1000`'s (`data.build`, the same adversarial rows), the
pool topped up to a multiple of the clients in flight so that every
caller's lap is as long.  Warm-up item k is k never-seen headers sent in
ONE call, k = 1 … the traffic mix's `warmup_commits`: a flush of k jobs
meets a rung of its own, and each is compiled or loaded through the
gateway itself before the window.  The rule — what each client is told,
what serving them may cost — is chipbench/reference/fanin_rules.py.

One `Gateway` a process (`Gateway.from_env()`: the documented defaults),
kept here with the last reading of its counters; `path` compares what
they moved by with the calls made since.
"""

import sys
from collections import deque
from dataclasses import dataclass

from chipbench import correct, data
from chipbench.reference import commit_rules, fanin_rules

_gateway = None             # the process's Gateway, made at the first bind
_last = {}                  # its counters as the last `path` read them
_sent = deque()             # jobs of each call since then (append is atomic)
GATEWAY_COUNTERS = ("verify_flushes", "verify_flushed_jobs", "verify_coalesced", "shed")


@dataclass
class Burst:
    """A warm-up item: several headers one call hands over together."""

    commits: list
    n_rows: int


@dataclass
class FaninData:
    vset: object
    powers: list
    pool: list              # data.PoolCommit: one header a call
    warmup: list            # Burst k of k headers


def _the_gateway():
    """The gateway on its normal path.  A program without the package
    cannot run the deployment: say so and leave as a failed set-up does,
    before anything is compiled."""
    global _gateway
    if _gateway is None:
        try:
            from tendermint_tpu.gateway.service import Gateway
        except ImportError as e:
            print(f"chipbench: stage 'bind' failed (exit 4): the program has no "
                  f"gateway to bind ({e})", file=sys.stderr, flush=True)
            raise SystemExit(4)
        _gateway = Gateway.from_env()
        _last.update(_gateway_counters())
    return _gateway


def _gateway_counters() -> dict:
    st = _gateway.coalescer.stats_snapshot()
    return {k: st[k] for k in GATEWAY_COUNTERS}


def build(seed, cfg, sizes, cache_capacity, pool_rule, warmup_commits):
    n, fan = sizes["validators"], cfg["clients_in_flight"]
    count = data.pool_size(cache_capacity, fanin_rules.consulted([data.POWER] * n), **pool_rule)
    count += -count % fan   # every caller's lap the same length
    d = data.build(seed, cfg["name"], {"validators": n}, cfg["adversarial"], cfg["mode"],
                   cache_capacity, {**pool_rule, "min_commits": count},
                   warmup_commits * (warmup_commits + 1) // 2)
    fresh = iter(d.warmup)
    warmup = []
    for k in range(1, warmup_commits + 1):
        commits = [next(fresh) for _ in range(k)]
        warmup.append(Burst(commits, sum(pc.n_rows for pc in commits)))
    return FaninData(d.vset, d.powers, d.pool, warmup)


def bind(d):
    from tendermint_tpu.types.validator import CommitVerifyJob

    verify_commits = _the_gateway().verify_commits
    vset = d.vset

    def call(item):
        commits = item.commits if isinstance(item, Burst) else (item,)
        _sent.append(len(commits))
        verify_commits([CommitVerifyJob(vset, data.CHAIN_ID, pc.block_id, pc.height,
                                        pc.commit, mode="light") for pc in commits])

    return call


def expected(d, item, row_ok):
    return fanin_rules.expected_alone(d.powers, item.suspects, row_ok)


implied = commit_rules.implied


def path(before, after, calls, compiles, route, chips):
    """`correct.device_path`'s six — of which `rows_off_device` and
    `cache_hits` are the rule's — and the rule's four of the gateway:
    service flushes − gateway flushes, jobs flushed − jobs sent, jobs that
    joined another, jobs shed."""
    now = _gateway_counters()
    moved = {k: now[k] - _last[k] for k in GATEWAY_COUNTERS}
    _last.update(now)
    jobs_sent = 0
    while _sent:
        jobs_sent += _sent.popleft()
    base = correct.device_path(before, after, calls, compiles, route, chips)
    rule = fanin_rules.once(jobs_sent, sum(c.rows for c in calls), {
        "rows_resolved_on_device": after["resolved_on_device"] - before["resolved_on_device"],
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "service_flushes": after["flushes"] - before["flushes"],
        "gateway_flushes": moved["verify_flushes"],
        "gateway_jobs_flushed": moved["verify_flushed_jobs"],
        "gateway_coalesced": moved["verify_coalesced"],
        "gateway_shed": moved["shed"],
    })
    return {**base, **rule}
