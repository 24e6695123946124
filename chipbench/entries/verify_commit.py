"""Entry point `ValidatorSet.verify_commit`: a call takes one commit of
the configuration's one validator set (`validators` equal-power keys,
`adversarial` rows) and consults the rows the configuration's `mode`
gives ("full": every row)."""

from chipbench import correct, data
from chipbench.reference import commit_rules


def build(seed, cfg, sizes, cache_capacity, pool_rule, warmup_commits):
    return data.build(seed, cfg["name"], {"validators": sizes["validators"]},
                      cfg["adversarial"], cfg["mode"], cache_capacity, pool_rule,
                      warmup_commits)


def bind(d):
    entry = d.vset.verify_commit
    return lambda pc: entry(data.CHAIN_ID, pc.block_id, pc.height, pc.commit)


def expected(d, item, row_ok):
    return commit_rules.expected_outcome(d.mode, d.powers, item.suspects, row_ok)


implied = commit_rules.implied
path = correct.device_path
