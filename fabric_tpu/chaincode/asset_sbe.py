"""Assets that carry their owner's endorsement policy, as an in-process
contract.

The chaincode of Hyperledger `fabric-samples` `asset-transfer-sbe` (the
Fabric documentation's state-based endorsement tutorial): an asset is one
state value, JSON `{"ID", "Value", "Owner", "OwnerOrg"}`, and its key
carries a validation parameter naming the owner's organisation alone —
`OutOf(1, '<OwnerOrg>.peer')`, an N-of-N policy over the listed orgs'
peers — which overrides the chaincode-level endorsement policy for every
later write of that key.  `CreateAsset` sets it to the creator's
organisation, `TransferAsset` re-sets it to the new owner's.  Update,
transfer and delete ask nothing of the caller, as in the sample: any
org's peer will endorse them, and the key's parameter is what protects
the asset at validation (committer/sbe.py).

Read-write sets: CreateAsset 1r/1w + 1 `#meta` w, UpdateAsset 1r/1w,
TransferAsset 1r/1w + 1 `#meta` w, DeleteAsset 1r/1 delete (the
parameter goes with the key at commit), ReadAsset 1r, AssetExists 1r.
"""

from __future__ import annotations

import json

from fabric_tpu.chaincode.runtime import FuncContract
from fabric_tpu.chaincode.stub import SimulationError
from fabric_tpu.policy import parse_policy
from fabric_tpu.utils import serde


def owner_policy(*orgs: str):
    """The sample's `setStateBasedEndorsement(id, orgs)`: every listed
    org's peer must endorse."""
    return parse_policy("OutOf(%d, %s)" % (
        len(orgs), ", ".join(f"'{org}.peer'" for org in orgs)))


def _record(asset_id: str, value: int, owner: str, owner_org: str) -> bytes:
    return json.dumps({"ID": asset_id, "Value": value, "Owner": owner,
                       "OwnerOrg": owner_org},
                      separators=(",", ":")).encode()


def _held(stub, asset_id: str) -> dict:
    raw = stub.get_state(asset_id)
    if raw is None:
        raise SimulationError(f"asset {asset_id} does not exist")
    return json.loads(raw)


def _value(raw: bytes) -> int:
    try:
        return int(raw)
    except ValueError:
        raise SimulationError(f"not a value: {raw!r}")


def create_asset(stub, asset_id, value, owner):
    key = asset_id.decode()
    if stub.get_state(key) is not None:
        raise SimulationError(f"asset {key} already exists")
    # the client's own organisation owns what it creates
    owner_org = serde.decode(stub.creator)["mspid"]
    stub.put_state(key, _record(key, _value(value), owner.decode(),
                                owner_org))
    stub.set_state_validation_parameter(key, owner_policy(owner_org))
    return b"created"


def read_asset(stub, asset_id):
    key = asset_id.decode()
    raw = stub.get_state(key)
    if raw is None:
        raise SimulationError(f"asset {key} does not exist")
    return raw


def update_asset(stub, asset_id, value):
    key = asset_id.decode()
    held = _held(stub, key)
    stub.put_state(key, _record(key, _value(value), held["Owner"],
                                held["OwnerOrg"]))
    return b"updated"


def transfer_asset(stub, asset_id, new_owner, new_owner_org):
    key = asset_id.decode()
    held = _held(stub, key)
    org = new_owner_org.decode()
    stub.put_state(key, _record(key, held["Value"], new_owner.decode(), org))
    stub.set_state_validation_parameter(key, owner_policy(org))
    return b"transferred"


def delete_asset(stub, asset_id):
    key = asset_id.decode()
    _held(stub, key)
    stub.del_state(key)
    return b"deleted"


def asset_exists(stub, asset_id):
    return b"true" if stub.get_state(asset_id.decode()) is not None \
        else b"false"


def contract() -> FuncContract:
    return FuncContract(CreateAsset=create_asset, ReadAsset=read_asset,
                        UpdateAsset=update_asset,
                        TransferAsset=transfer_asset,
                        DeleteAsset=delete_asset, AssetExists=asset_exists)
