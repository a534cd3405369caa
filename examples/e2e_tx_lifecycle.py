"""Full transaction lifecycle, every plane of the framework in one run:

  client proposal -> 2 endorsing orgs simulate + sign (ESCC)
  -> client assembles the tx -> orderer broadcast (admission filters)
  -> solo chain cuts blocks -> deliver stream to the peer
  -> orderer-signature check + verify-then-gate block validation
  -> MVCC -> ledger commit.

Run: JAX_PLATFORMS=cpu \
       PYTHONPATH=. python examples/e2e_tx_lifecycle.py
"""

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.chaincode import (
    ChaincodeDefinition,
    ChaincodeRegistry,
    LifecyclePolicyProvider,
    SimulationError,
)
from fabric_tpu.chaincode.runtime import FuncContract
from fabric_tpu.committer import Committer, TxValidator
from fabric_tpu.endorser import Endorser, assemble_transaction, signed_proposal
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.orderer import (
    BatchConfig,
    BroadcastHandler,
    DeliverHandler,
    Registrar,
    SeekInfo,
    block_signature_items,
)
from fabric_tpu.policy import parse_policy


def asset_contract():
    def create(stub, key, value):
        if stub.get_state(key.decode()) is not None:
            raise SimulationError("asset exists")
        stub.put_state(key.decode(), value)
        return b"created"

    def transfer(stub, key, owner):
        v = stub.get_state(key.decode())
        if v is None:
            raise SimulationError("no such asset")
        stub.put_state(key.decode(), owner)
        return b"transferred"

    return FuncContract(create=create, transfer=transfer)


def main():
    provider = init_factories(FactoryOpts(default="SW"))
    org1, org2, ord_org = DevOrg("Org1"), DevOrg("Org2"), DevOrg("OrdererOrg")
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2, ord_org)}

    # ---- peer side: ledger, chaincode, endorsers, committer
    ledger = KVLedger("ch", LedgerConfig())
    registry = ChaincodeRegistry()
    registry.install(ChaincodeDefinition("assets", "1.0"), asset_contract())
    policies = LifecyclePolicyProvider(ledger.statedb)
    policies.set_policy("assets",
                        parse_policy("AND('Org1.member', 'Org2.member')"))
    endorsers = [Endorser("ch", ledger.statedb, registry, msps, provider,
                          org.new_identity(f"peer.{org.mspid}"))
                 for org in (org1, org2)]
    committer = Committer(ledger, TxValidator("ch", msps, provider, policies))

    # ---- orderer side
    registrar = Registrar()
    registrar.create_channel(
        "ch", msps, provider,
        writers_policy=parse_policy(
            "OR('Org1.member', 'Org2.member', 'OrdererOrg.member')"),
        signer=ord_org.new_identity("orderer1"),
        batch_config=BatchConfig(max_message_count=4))
    broadcast = BroadcastHandler(registrar)

    # ---- client: endorse + submit 8 transactions
    client = org1.new_identity("alice")
    for i in range(8):
        sp = signed_proposal("ch", "assets", "create",
                             [b"asset%d" % i, b"alice"], client)
        responses = [e.process_proposal(sp) for e in endorsers]
        assert all(r.status == 200 for r in responses), responses
        env = assemble_transaction(sp, responses, client)
        resp = broadcast.handle(env)
        assert resp.status == 200, resp.info
    registrar.get("ch").chain.tick(now=float("inf"))  # flush pending batch

    # ---- delivery + commit on the peer
    deliver = DeliverHandler(registrar)
    for block in deliver.deliver("ch", SeekInfo(start=0, stop="newest")):
        items = block_signature_items(block, msps)
        assert items and bool(provider.batch_verify(items).all()), \
            "orderer block signature must verify"
        res = committer.store_block(block)
        print(f"block {block.header.number}: "
              f"{res.validation.flags.valid_count()}/{len(block.data)} valid, "
              f"{res.validation.n_unique_items} unique sigs in one dispatch")

    assert ledger.get_state("assets", "asset7") == b"alice"

    # a double-create must fail at simulation time
    sp = signed_proposal("ch", "assets", "create", [b"asset0", b"bob"], client)
    r = endorsers[0].process_proposal(sp)
    assert r.status == 500 and "exists" in r.message
    print(f"height={ledger.height} | double-create rejected at simulation")
    print("TX LIFECYCLE OK")


if __name__ == "__main__":
    main()
