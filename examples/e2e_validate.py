"""End-to-end slice: endorse -> block -> verify-then-gate -> MVCC -> commit.

Drives the public framework surface the way a peer's commit path does
(SURVEY.md §3.2): builds a block of endorser transactions, validates it
with one batched signature dispatch, commits, and prints the tx filter
bitmap plus per-phase timings.

Run CPU-only:
  JAX_PLATFORMS=cpu python examples/e2e_validate.py
"""

import sys

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import (Envelope, KVRead, KVWrite, NsRwSet, TxRwSet,
                                 ValidationCode, Version, build)


def main(n_txs: int = 20, provider_name: str = "SW") -> int:
    provider = init_factories(FactoryOpts(default=provider_name))
    org1, org2 = DevOrg("Org1"), DevOrg("Org2")
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2)}
    policies = PolicyRegistry()
    policies.set_policy("mycc", parse_policy("AND('Org1.member', 'Org2.member')"))

    ledger = KVLedger("demo", LedgerConfig())
    committer = Committer(ledger, TxValidator("demo", msps, provider, policies))

    endorsers = [org1.new_identity("peer0"), org2.new_identity("peer0")]
    client = org1.new_identity("client")

    def tx(i, reads=(), writes=()):
        rwset = TxRwSet((NsRwSet("mycc", reads=tuple(reads),
                                 writes=tuple(writes)),))
        return build.endorser_tx("demo", "mycc", "1.0", rwset, client, endorsers)

    # block 0: writes
    envs = [tx(i, writes=[KVWrite(f"key{i}", f"val{i}".encode())])
            for i in range(n_txs)]
    # one corrupted creator signature
    envs[3] = Envelope(envs[3].payload, envs[3].signature[:-2] + b"\x00\x00")
    block = build.new_block(0, b"\x00" * 32, envs)
    res = committer.store_block(block)

    # block 1: a valid read-modify-write plus one stale read (MVCC conflict)
    v0 = Version(0, 0)
    b1 = build.new_block(1, block.hash(), [
        tx(0, reads=[KVRead("key0", v0)], writes=[KVWrite("key0", b"updated")]),
        tx(1, reads=[KVRead("key0", v0)], writes=[KVWrite("key0", b"loser")]),
    ])
    res1 = committer.store_block(b1)

    flags0 = res.final_flags
    flags1 = res1.final_flags
    print(f"block 0: {flags0.valid_count()}/{len(flags0)} valid | "
          f"collect={res.validation.collect_s*1e3:.1f}ms "
          f"dispatch={res.validation.dispatch_s*1e3:.1f}ms "
          f"({res.validation.n_unique_items} uniq sigs of "
          f"{res.validation.n_items} refs) "
          f"gate={res.validation.gate_s*1e3:.1f}ms")
    print(f"block 0 codes: {flags0.codes()}")
    print(f"block 1 codes: {flags1.codes()} (expect [0, MVCC={int(ValidationCode.MVCC_READ_CONFLICT)}])")
    print(f"state key0 = {ledger.get_state('mycc', 'key0')}")
    print(f"height={ledger.height} commit_hash={ledger.commit_hash.hex()[:16]}…")

    ok = (flags0.valid_count() == n_txs - 1
          and flags0.flag(3) == ValidationCode.BAD_CREATOR_SIGNATURE
          and flags1.codes() == [0, int(ValidationCode.MVCC_READ_CONFLICT)]
          and ledger.get_state("mycc", "key0") == b"updated")
    print("E2E OK" if ok else "E2E MISMATCH")
    return 0 if ok else 1


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    prov = sys.argv[2] if len(sys.argv) > 2 else "SW"
    raise SystemExit(main(n, prov))
