"""An asset registry indexed by colour, as an in-process contract.

The chaincode of Hyperledger `fabric-samples`
`asset-transfer-ledger-queries` (the successor of `marbles02`; the
sample the Fabric documentation points to for range queries and
composite keys): an asset is one state value under its id, JSON
`{"docType":"asset","ID","color","size","owner","appraisedValue"}` in
the sample's field order, and every asset has one entry in the index
`color~name` — the composite key (`color~name`, [color, id]) with the
one-byte value `\\x00` — so that a whole colour is one partial-key range.

`TransferAssetByColor` is the reason the index exists: it hands every
asset of a colour to a new owner in one transaction, found by a range
query over the index.  Committing peers run that range again and
invalidate the transaction when its result set has changed
(PHANTOM_READ_CONFLICT), which is what makes a range query a safe base
for an update.  No rich-query function: those are evaluated at a peer
and never ordered.

Read-write sets: CreateAsset 1r/2w, ReadAsset 1r, AssetExists 1r,
DeleteAsset 1r/2 deletes, TransferAsset 1r/1w, TransferAssetByColor 1
range query of K raw reads + K r/K w, GetAssetsByRange 1 range query.
"""

from __future__ import annotations

import json

from fabric_tpu.chaincode.runtime import FuncContract
from fabric_tpu.chaincode.stub import (SimulationError, create_composite_key,
                                       split_composite_key)

INDEX = "color~name"
INDEX_VALUE = b"\x00"            # the sample's: an empty value would delete


def _record(asset_id: str, color: str, size: int, owner: str,
            appraised_value: int) -> bytes:
    return json.dumps({"docType": "asset", "ID": asset_id, "color": color,
                       "size": size, "owner": owner,
                       "appraisedValue": appraised_value},
                      separators=(",", ":")).encode()


def _held(stub, asset_id: str) -> dict:
    raw = stub.get_state(asset_id)
    if raw is None:
        raise SimulationError(f"asset {asset_id} does not exist")
    return json.loads(raw)


def _number(raw: bytes) -> int:
    try:
        return int(raw)
    except ValueError:
        raise SimulationError(f"not a number: {raw!r}")


def _with_owner(asset: dict, owner: str) -> bytes:
    return _record(asset["ID"], asset["color"], asset["size"], owner,
                   asset["appraisedValue"])


def create_asset(stub, asset_id, color, size, owner, appraised_value):
    key = asset_id.decode()
    if stub.get_state(key) is not None:
        raise SimulationError(f"asset {key} already exists")
    stub.put_state(key, _record(key, color.decode(), _number(size),
                                owner.decode(), _number(appraised_value)))
    stub.put_state(create_composite_key(INDEX, [color.decode(), key]),
                   INDEX_VALUE)
    return b"created"


def read_asset(stub, asset_id):
    key = asset_id.decode()
    raw = stub.get_state(key)
    if raw is None:
        raise SimulationError(f"asset {key} does not exist")
    return raw


def asset_exists(stub, asset_id):
    return b"true" if stub.get_state(asset_id.decode()) is not None \
        else b"false"


def delete_asset(stub, asset_id):
    key = asset_id.decode()
    asset = _held(stub, key)             # for its colour: the index entry
    stub.del_state(key)
    stub.del_state(create_composite_key(INDEX, [asset["color"], key]))
    return b"deleted"


def transfer_asset(stub, asset_id, new_owner):
    key = asset_id.decode()
    stub.put_state(key, _with_owner(_held(stub, key), new_owner.decode()))
    return b"transferred"


def transfer_asset_by_color(stub, color, new_owner):
    owner = new_owner.decode()
    entries = stub.get_state_by_partial_composite_key(INDEX, [color.decode()])
    for index_key, _ in entries:
        _, (_, key) = split_composite_key(index_key)
        stub.put_state(key, _with_owner(_held(stub, key), owner))
    return str(len(entries)).encode()


def get_assets_by_range(stub, start_key, end_key):
    rows = stub.get_state_by_range(start_key.decode(), end_key.decode())
    return ("[" + ",".join(value.decode() for _, value in rows)
            + "]").encode()


def contract() -> FuncContract:
    return FuncContract(CreateAsset=create_asset, ReadAsset=read_asset,
                        AssetExists=asset_exists, DeleteAsset=delete_asset,
                        TransferAsset=transfer_asset,
                        TransferAssetByColor=transfer_asset_by_color,
                        GetAssetsByRange=get_assets_by_range)
