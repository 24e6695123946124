"""Entry point `ValidatorSet.verify_commit_light`: the same data and rule
as `verify_commit` (the configuration's `mode` is "light": rows in order
until power > 2/3, never the rest), another method of the set."""

from chipbench import data
from chipbench.entries.verify_commit import build, expected, implied, path  # noqa: F401


def bind(d):
    entry = d.vset.verify_commit_light
    return lambda pc: entry(data.CHAIN_ID, pc.block_id, pc.height, pc.commit)
