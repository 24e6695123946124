"""Shared test fixtures: deterministic validator keys and a chain builder
that produces exactly what consensus would have committed (used by
blocksync / light client / statesync suites).

Models the reference's shared fixtures (consensus/common_test.go,
types/test_util.go makeCommit, state/helpers_test.go makeBlock).
"""

from __future__ import annotations

from tendermint_tpu.abci import AppConns
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.state import BlockExecutor, StateStore, make_genesis_state
from tendermint_tpu.store import BlockStore, MemDB
from tendermint_tpu.types import GenesisDoc, GenesisValidator
from tendermint_tpu.types.basic import BlockID, PartSetHeader
from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.vote import SignedMsgType, vote_sign_bytes_raw


def make_keys(n, power=10, chain_id="test-chain", seed_mult=11, seed_add=3):
    # single-byte repeating seeds while they fit (the historical scheme —
    # existing suites derive fixtures from these); 4-byte little-endian
    # seeds beyond that (the 200-validator bench overflows bytes([x]))
    def seed(i):
        x = seed_mult * i + seed_add
        return bytes([x]) * 32 if x < 256 else x.to_bytes(4, "little") * 8

    keys = [priv_key_from_seed(seed(i)) for i in range(n)]
    genesis = GenesisDoc(
        chain_id=chain_id,
        genesis_time_ns=1_700_000_000 * 10**9,
        validators=[GenesisValidator(pub_key=k.pub_key(), power=power) for k in keys],
    )
    return keys, genesis


def sign_commit(chain_id, height, round_, block_id, val_set, key_by_addr, time_ns):
    """Every validator precommits for the block (makeCommit equivalent)."""
    sigs = []
    for v in val_set.validators:
        k = key_by_addr[v.address]
        sb = vote_sign_bytes_raw(
            chain_id, SignedMsgType.PRECOMMIT, height, round_, block_id, time_ns
        )
        sigs.append(
            CommitSig(
                block_id_flag=BlockIDFlag.COMMIT,
                validator_address=v.address,
                timestamp_ns=time_ns,
                signature=k.sign(sb),
            )
        )
    return Commit(height=height, round=round_, block_id=block_id, signatures=sigs)


class ChainBuilder:
    """Produce + apply + store blocks exactly as consensus would."""

    def __init__(self, n_vals=4, chain_id="test-chain", app=None):
        self.keys, self.genesis = make_keys(n_vals, chain_id=chain_id)
        self.state = make_genesis_state(self.genesis)
        self.key_by_addr = {k.pub_key().address(): k for k in self.keys}
        self.app = app or KVStoreApplication()
        self.conns = AppConns(self.app)
        self.state_store = StateStore(MemDB())
        self.block_store = BlockStore(MemDB())
        self.state_store.save(self.state)
        self.state_store.save_genesis_doc_hash(self.genesis.doc_hash())
        self.executor = BlockExecutor(self.state_store, self.conns.consensus())
        self.last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])

    def step(self, txs=()):
        state = self.state
        height = (
            state.initial_height
            if state.last_block_height == 0
            else state.last_block_height + 1
        )
        proposer = state.validators.get_proposer()
        block = self.executor.create_proposal_block(
            height, state, self.last_commit, proposer.address
        )
        block.data.txs = list(txs)
        block.header.data_hash = block.data.hash()
        part_set = block.make_part_set()
        block_id = BlockID(hash=block.hash(), part_set_header=part_set.header())
        new_state, _ = self.executor.apply_block(state, block_id, block)
        seen_commit = sign_commit(
            state.chain_id,
            height,
            0,
            block_id,
            state.validators,
            self.key_by_addr,
            block.header.time_ns + 10**9,
        )
        self.block_store.save_block(block, part_set, seen_commit)
        self.last_commit = seen_commit
        self.state = new_state
        return block, block_id

    def build(self, n_blocks, tx_fn=None):
        for h in range(1, n_blocks + 1):
            txs = tx_fn(h) if tx_fn else [b"k%d=v%d" % (h, h)]
            self.step(txs)
        return self


def small_commit(n_vals=24, height=3, corrupt=()):
    """A validator set under the device threshold with one fully signed
    commit: (chain_id, val_set, block_id, commit).  `corrupt` flips a bit
    in those rows' signatures."""
    keys, genesis = make_keys(n_vals)
    val_set = make_genesis_state(genesis).validators
    key_by_addr = {k.pub_key().address(): k for k in keys}
    block_id = BlockID(hash=b"\xbb" * 32,
                       part_set_header=PartSetHeader(total=1, hash=b"\xcc" * 32))
    commit = sign_commit(genesis.chain_id, height, 0, block_id, val_set,
                         key_by_addr, 1_700_000_123 * 10**9)
    for idx in corrupt:
        sig = commit.signatures[idx].signature
        commit.signatures[idx].signature = sig[:-1] + bytes([sig[-1] ^ 1])
    return genesis.chain_id, val_set, block_id, commit
