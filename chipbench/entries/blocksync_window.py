"""Entry point `blocksync.reactor.verify_window`: one step of a fast-sync
catch-up.  A call is offered a run of `downloaded_blocks` consecutive real
`Block`s of the configuration's one validator set, as the pool's window
hands them to the reactor, with the state the reactor holds at the run's
first height; the step takes the leading blocks that one flush holds and
verifies their commits as one batched call (`VerifyCommit` on every block
taken, `VerifyCommitLight` on the newest from its successor's LastCommit).

The blocks are the program's own (`State.make_block`: header with the
set's `validators_hash` and the chained `last_block_id`, empty data); each
LastCommit is signed through the reference's encoder over the program's
hash and part-set header of the block it proves.  The rule — the cut, the
jobs, the answer — is chipbench/reference/window_rules.py.
"""

import bisect
import dataclasses
import hashlib
import itertools
import random
import re
import sys
from dataclasses import dataclass

from chipbench import correct, data
from chipbench.reference import window_rules
from chipbench.reference.signbytes import PrecommitTemplate

WRONG = re.compile(r"wrong signature \(#(\d+)\) in commit for height (\d+)")
RUN_STRIDE = 1000           # heights between the first blocks of two runs


@dataclass
class Run:
    """What one step is offered, and the rows it consults: those of its
    jobs in job order (`n_rows`, `row(i)`, `suspects`)."""

    state: object           # the program's State at blocks[0].height - 1
    blocks: list            # the program's Blocks, consecutive
    commits: list           # data.PoolCommit: commit i is blocks[i].last_commit
    step: list              # window_rules.jobs: (mode, commit) in job order
    offsets: list           # first flat row of each job
    n_rows: int
    suspects: dict          # flat row -> kind
    warm: bool

    def row(self, i: int):
        j = bisect.bisect_right(self.offsets, i) - 1
        return self.commits[self.step[j][1]].row(i - self.offsets[j])


@dataclass
class WindowData:
    vset: object
    pubs: list
    powers: list
    step_args: tuple        # (max_rows,) where the sizes carry one
    pool: list
    warmup: list


def _window_step():
    """The program's window step.  A program without one (the parent of
    the PR that brought this entry) cannot run the deployment: say so and
    leave as a failed set-up does, before anything is compiled."""
    try:
        from tendermint_tpu.blocksync.reactor import verify_window
    except ImportError as e:
        print(f"chipbench: stage 'bind' failed (exit 4): the program has no "
              f"window step to bind ({e})", file=sys.stderr, flush=True)
        raise SystemExit(4)
    return verify_window


def _commit(rng, who, height, block_id):
    """`data.build_commit`'s complete commit, over a block id given."""
    from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig

    psh = block_id.part_set_header
    tpl = PrecommitTemplate(data.CHAIN_ID, height, 0, block_id.hash, psh.total, psh.hash)
    base = data.T0_NS + height * 10**9
    timestamps, signatures = [], []
    for i, (_, key) in enumerate(who.keys):
        ts = base + i + 1  # every validator signs its own timestamp
        timestamps.append(ts)
        signatures.append(rng.choice(who.encs) + bytes(32) if key is None
                          else key.sign(tpl.sign_bytes(ts)))
    commit = Commit(height=height, round=0, block_id=block_id, signatures=[
        CommitSig(block_id_flag=BlockIDFlag.COMMIT, validator_address=addr,
                  timestamp_ns=ts, signature=sig)
        for (addr, _), ts, sig in zip(who.keys, timestamps, signatures)])
    return data.PoolCommit(height, block_id, commit, tpl, timestamps, signatures,
                           who.pubs, len(signatures),
                           {i: "small_order" for i in who.small_order})


def _run(seed, name, rng, who, genesis, first, n_blocks, step, consulted, bad, warm):
    """`n_blocks` consecutive blocks from height `first`, each carrying
    the commit of the one before; `bad` = (commit, row, kind) or None is
    corrupted before the block that carries it is built, so the chain of
    hashes holds."""
    from tendermint_tpu.types.basic import BlockID, PartSetHeader

    tag = b"%d|%s|before|%d" % (seed, name.encode(), first)
    block_id = BlockID(hash=hashlib.sha256(tag).digest(), part_set_header=PartSetHeader(
        total=1, hash=hashlib.sha256(tag + b"|parts").digest()))
    state = dataclasses.replace(genesis, last_block_height=first - 1, last_block_id=block_id)
    blocks, commits = [], []
    at = state
    for i in range(n_blocks):
        height = first + i
        pc = _commit(rng, who, height - 1, block_id)
        if bad is not None and bad[0] == i:
            data.corrupt(pc, bad[1], bad[2])
        block = at.make_block(height, [], pc.commit, [], who.keys[height % len(who.keys)][0],
                              data.T0_NS + height * 10**9)
        block_id = BlockID(hash=block.hash(), part_set_header=block.make_part_set().header())
        at = dataclasses.replace(at, last_block_height=height, last_block_id=block_id)
        blocks.append(block)
        commits.append(pc)
    offsets = [0, *itertools.accumulate(consulted[:-1])]
    suspects = {offsets[j] + r: kind for j, (_, c) in enumerate(step)
                for r, kind in commits[c].suspects.items() if r < consulted[j]}
    return Run(state, blocks, commits, step, offsets, sum(consulted), suspects, warm)


def build(seed, cfg, sizes, cache_capacity, pool_rule, warmup_commits):
    _window_step()
    from tendermint_tpu.state.state import State
    from tendermint_tpu.types.basic import BlockID
    from tendermint_tpu.types.params import ConsensusParams
    from tendermint_tpu.crypto.async_verify import MAX_COALESCE

    name = cfg["name"]
    rng = random.Random(seed)
    n, n_blocks = sizes["validators"], sizes["downloaded_blocks"]
    who = data.validator_set(seed, name, rng, n, cfg["adversarial"]["small_order_validators"])
    powers = [data.POWER] * n
    step = window_rules.jobs([n] * n_blocks, sizes.get("max_rows", MAX_COALESCE))
    consulted = window_rules.consulted(step, powers)
    taken = len(step) - 1
    genesis = State(
        chain_id=data.CHAIN_ID, initial_height=1, last_block_height=0,
        last_block_id=BlockID(), last_block_time_ns=data.T0_NS, validators=who.vset,
        next_validators=who.vset, last_validators=who.vset,
        last_height_validators_changed=1, consensus_params=ConsensusParams(),
        last_height_consensus_params_changed=1, last_results_hash=b"", app_hash=b"")

    count = data.pool_size(cache_capacity, sum(consulted), **pool_rule)
    honest = [r for r in range(n) if r not in who.small_order]
    where = {  # a corrupted row's commit and row, by where the configuration wants it
        "full": lambda: (rng.randrange(taken), rng.choice(honest)),
        "pair": lambda: (taken, rng.choice([r for r in honest if r < consulted[-1]])),
        "past_cut": lambda: (rng.randrange(taken + 1, n_blocks), rng.choice(honest)),
    }
    bad_runs = cfg["adversarial"]["bad_runs"]
    bad = {k: where[place]() + (kind,)
           for k, (place, kind) in zip(rng.sample(range(count), len(bad_runs)), bad_runs)}
    pool = [_run(seed, name, rng, who, genesis, (k + 1) * RUN_STRIDE + 1, n_blocks, step,
                 consulted, bad.get(k), False) for k in range(count)]
    warmup = [_run(seed, name, rng, who, genesis, data.WARMUP_HEIGHT + j * RUN_STRIDE + 1,
                   n_blocks, step, consulted, None, True) for j in range(warmup_commits)]
    args = (sizes["max_rows"],) if "max_rows" in sizes else ()
    return WindowData(who.vset, who.pubs, powers, args, pool, warmup)


def bind(d):
    verify_window = _window_step()

    def call(run):
        try:
            applied = verify_window(run.state, run.blocks, *d.step_args)
        except ValueError as e:
            m = WRONG.search(str(e))
            if not m:
                raise
            return ("wrong_signature", (int(m.group(2)), int(m.group(1))))
        if run.warm and len(applied) == len(run.step) - 1:
            return None     # what the harness's warm-up asks of an accepted item
        return ("accept", len(applied))

    return call


def expected(d, item, row_ok):
    return window_rules.expected_step(
        item.step, d.powers, [pc.height for pc in item.commits],
        [pc.suspects for pc in item.commits],
        # job j verifies commit j, so commit c's row r is flat row offsets[c] + r
        lambda c, r: row_ok(item.offsets[c] + r))


def implied(outcome, row):
    """An accepted step says every consulted row is valid; a refusal names
    a height and a row, which a flat row alone cannot be held against."""
    return True if outcome[0] == "accept" else None


def path(before, after, calls, compiles, route, chips):
    """`correct.device_path`, and every step ONE flush: a step that the
    service cuts in two, or that never reaches it, reads other than 0."""
    return {**correct.device_path(before, after, calls, compiles, route, chips),
            "flushes_per_call_off": (after["flushes"] - before["flushes"]) - len(calls)}
