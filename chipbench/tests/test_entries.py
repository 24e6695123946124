"""The entry seam: the data of the accepted configurations is what it was
before their entry points moved into chipbench/entries/, and an entry
point of another shape is added as files only."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from chipbench import manifest

M = manifest.load()
SEQ = manifest._read_json(os.path.join(manifest.HERE, "traffic", "seq.json"))

# recorded from `data.build` of the parent commit (9f9fe4d), before the move
DIGESTS = {
    ("commit-10k", 7): "e8f6fff31d44e24c04974ebbd3021ff07acb710ecb137def13f0aad6cebbc259",
    ("commit-10k", 2**31 + 5): "1142e1397757c8840a9851712a44de130b02a7f1418c8668036f16cee715d5b7",
    ("light-1000", 7): "a5012af27c6f5e9fc5dcd8ec56c33f3ebeb17f6fdbf2f9bde1e8723fd25d8fed",
    ("light-1000", 2**31 + 5): "8f26a23a92f400b3867b9f27b9ecae9c4c6d60ca39941042f3b573503f41e7fb",
}


def _digest(d) -> str:
    h = hashlib.sha256()

    def put(*parts):
        for p in parts:
            b = p if isinstance(p, bytes) else repr(p).encode()
            h.update(len(b).to_bytes(4, "big"))
            h.update(b)

    put(d.mode, d.consulted, d.powers, *d.pubs)
    put(*[v.address for v in d.vset.validators])
    for pc in d.pool + d.warmup:
        put(pc.height, pc.block_id.hash, pc.block_id.part_set_header.hash,
            pc.timestamps, sorted(pc.suspects.items()), *pc.signatures)
        put(pc.commit.height, pc.commit.round, pc.commit.block_id.hash)
        for cs in pc.commit.signatures:
            put(int(cs.block_id_flag), cs.validator_address, cs.timestamp_ns, cs.signature)
    return h.hexdigest()


@pytest.mark.parametrize("name,seed", sorted(DIGESTS))
def test_seeded_data_is_what_the_parent_built(name, seed):
    """Rehearse sizes, the default cache's capacity, the `seq` mix: keys,
    pool, corrupted rows and warm-ups, byte for byte."""
    cfg = manifest._read_json(os.path.join(
        manifest.ROOT, next(c["file"] for c in M["configs"] if c["name"] == name)))
    entry = manifest.entry(cfg["entry"])
    d = entry.build(seed, cfg, {**cfg, **cfg["rehearse"]}, 65536, SEQ["pool"],
                    SEQ["warmup_commits"])
    assert _digest(d) == DIGESTS[name, seed]
    assert all(item.n_rows == d.consulted for item in d.pool + d.warmup)


def test_every_configuration_names_an_entry_file_with_the_five_functions():
    for c in M["configs"]:
        cfg = manifest._read_json(os.path.join(manifest.ROOT, c["file"]))
        mod = manifest.entry(cfg["entry"])
        assert all(callable(getattr(mod, f)) for f in manifest.ENTRY_FUNCTIONS)
    with pytest.raises(manifest.ManifestError, match="no file at chipbench/entries/nope.py"):
        manifest.entry("nope")


def test_no_file_outside_entries_names_an_entry_point():
    names = {os.path.splitext(f)[0] for f in os.listdir(os.path.join(manifest.HERE, "entries"))
             if f.endswith(".py")}
    for base, _dirs, files in os.walk(manifest.HERE):
        if base.endswith(("entries", "tests", "__pycache__")):
            continue
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(base, f)) as fh:
                code = fh.read()
            for n in names:
                assert f'"{n}"' not in code and f".{n}(" not in code, (f, n)


# An entry point of another shape, as a later PR would bring it: a call
# takes a WINDOW of k commits of a second validator set and verifies them
# as one flush (`batch_verify_commits`, the blocksync surface); k differs
# from item to item, so rows differ per call; its answer names a height
# and a row; its path rule is its own (it counts jobs, and wants its own
# route).  Only files are added: the entry, a configuration, two manifest
# entries.
WINDOW_ENTRY = r'''
"""A window of commits of a second validator set through
types.validator.batch_verify_commits."""
import random
import re
from dataclasses import dataclass

from chipbench import data
from chipbench.reference import commit_rules

WRONG = re.compile(r"wrong signature \(#(\d+)\) in commit for height (\d+)")


@dataclass
class Window:
    commits: list            # data.PoolCommit, all rows consulted
    n_rows: int
    suspects: dict           # flat row -> kind

    def row(self, i):
        k, j = divmod(i, self.commits[0].n_rows)
        return self.commits[k].row(j)


@dataclass
class WindowData:
    vset: object
    powers: list
    pool: list
    warmup: list
    jobs: int = 0


def _window(commits):
    v = commits[0].n_rows
    return Window(commits, v * len(commits),
                  {k * v + j: kind for k, pc in enumerate(commits)
                   for j, kind in pc.suspects.items()})


def build(seed, cfg, sizes, cache_capacity, pool_rule, warmup_commits):
    name = cfg["name"] + "|second-set"
    rng = random.Random(seed)
    v = sizes["validators"]
    who = data.validator_set(seed, name, rng, v, cfg["adversarial"]["small_order_validators"])
    height = iter(range(1, 10**6))

    def commits(k):
        return [data.build_commit(seed, name, rng, next(height), who, v) for _ in range(k)]

    pool = [_window(commits(cfg["window"][i % len(cfg["window"])]))
            for i in range(5 * pool_rule["min_commits"])]
    bad = pool[rng.randrange(4)]
    row = rng.choice([i for i in range(bad.n_rows) if i not in bad.suspects])
    data.corrupt(bad.commits[row // v], row % v, "sig_bit")
    bad.suspects[row] = "sig_bit"
    warm = [_window(commits(max(cfg["window"]))) for _ in range(warmup_commits)]
    return WindowData(who.vset, [data.POWER] * v, pool, warm)


def bind(d):
    from tendermint_tpu.types.validator import CommitVerifyJob, batch_verify_commits

    def call(w):
        d.jobs += len(w.commits)
        try:
            batch_verify_commits([CommitVerifyJob(d.vset, data.CHAIN_ID, pc.block_id,
                                                  pc.height, pc.commit) for pc in w.commits])
        except ValueError as e:
            m = WRONG.search(str(e))
            if not m:
                raise
            return ("wrong_signature", (int(m.group(2)), int(m.group(1))))

    return call


def expected(d, item, row_ok):
    v = item.commits[0].n_rows
    for k, pc in enumerate(item.commits):
        kind, at = commit_rules.expected_outcome(
            "full", d.powers, pc.suspects, lambda j, k=k: row_ok(k * v + j))
        if kind != "accept":
            return (kind, (pc.height, at))
    return ("accept", None)


def implied(outcome, row):
    return True if outcome[0] == "accept" else None


def path(before, after, calls, compiles, route, chips):
    return {"rows_off_device": sum(c.rows for c in calls)
            - (after["resolved_on_device"] - before["resolved_on_device"]),
            "flushes_per_call_off": (after["flushes"] - before["flushes"]) - len(calls),
            "compiles_in_window": compiles,
            "route_other": 0 if tuple(route or ()) == ("device", "pipelined") else 1}
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with the benchmark's files, the program beside them, and
    the new entry point ADDED: no file that is there is edited."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(manifest.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for program in ("tendermint_tpu", "src"):    # the package and its native sources
        os.symlink(os.path.join(manifest.ROOT, program), root / program)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*") if p.is_file()}
    (root / "chipbench" / "entries" / "verify_window.py").write_text(
        textwrap.dedent(WINDOW_ENTRY))
    cfg = {"name": "window-3x", "source": "in-test", "entry": "verify_window",
           "validators": 24, "window": [3, 4], "reduced": [],   # 72 and 96 rows: one rung
           "adversarial": {"small_order_validators": 2},
           "rehearse": {"validators": 24}}
    (root / "chipbench" / "configs" / "window-3x.json").write_text(json.dumps(cfg))
    m = json.loads(json.dumps(M))
    m["configs"].append({"name": "window-3x", "source": "in-test",
                         "file": "chipbench/configs/window-3x.json", "reduced": [],
                         "why": "an entry of another shape"})
    m["workloads"].append({"name": "window-3x.seq", "config": "window-3x",
                           "traffic": "seq", "chips": 1, "why": "in-test"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root, before


def test_an_entry_of_another_shape_is_files_only(checkout):
    root, before = checkout
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           # the rung-96 program of the repo's own CPU cache
           "JAX_COMPILATION_CACHE_DIR": os.path.join(manifest.ROOT, ".jax_cache")}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "window-3x.seq", "--seed",
         str(2**31 + 77), "--seconds", "3", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=root, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["compared"]
    assert set(res["compared"]) == {"calls_wrong", "sampled_rows_wrong", "rows_off_device",
                                    "flushes_per_call_off", "compiles_in_window",
                                    "route_other"}
    summary = json.loads(next(ln for ln in p.stdout.splitlines()
                              if ln.startswith("summary: "))[len("summary: "):])
    # the corrupted window is among the first four; 72 and 96 rows a call
    assert summary["calls"] >= 4 and 72.0 < summary["rows_per_call"] < 96.0
    after = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts and p in before}
    assert after == before
