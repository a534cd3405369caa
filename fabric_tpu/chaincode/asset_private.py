"""A registry whose assets trade at a private price, as an in-process
contract.

The chaincode of Hyperledger `fabric-samples`
`asset-transfer-private-data` (chaincode-go; the sample the tutorial
"Using Private Data in Fabric" deploys with `collections_config.json`).
An asset's public half `{objectType, assetID, color, size, owner}` lives
in the collection `assetCollection`, which both trading orgs are members
of; what each org thinks the asset is worth, `{assetID, appraisedValue}`,
lives in that org's own collection `<MSPID>PrivateCollection`, which has
one member, a short block-to-live and its own endorsement policy.  Every
private input arrives in the proposal's transient map, never as an
argument.  A trade is

  CreateAsset       by the owner: the asset, and the owner's appraisal,
  AgreeToTransfer   by the buyer: the buyer's appraisal in the buyer's
                    collection, and the composite key
                    (`transferAgreement`, [assetID]) -> the buyer's id
                    in `assetCollection`,
  TransferAsset     by the owner: `verifyAgreement` — the submitter owns
                    the asset, and `GetPrivateDataHash` of the owner's
                    and of the buyer's appraisal are both present and
                    equal, which proves the two agreed on a price
                    without either reading the other's collection — then
                    the asset's owner becomes the buyer, the owner's
                    appraisal and the agreement are deleted.

Every write function refuses a client whose org is not the endorsing
peer's (`verifyClientOrgMatchesPeerOrg`): an org's private data is
written through its own peer.  A client's id is its certificate's common
name (the sample's is the base64 of the x509 subject and issuer).
`PurgeAsset` (v2.5's `PurgePrivateData`) is left out: the shim has no
purge verb.

Hashed rw-sets, by collection (a = assetCollection, o = the submitter's
org collection, b = the buyer's): CreateAsset a 1r/1w + o 1w;
AgreeToTransfer a 1r/1w + o 1w; TransferAsset a 2r/1w/1 delete + o 1r/1
delete + b 1r; DeleteAsset a 1r/1 delete + o 1r/1 delete;
DeleteTransferAgreement a 1r/1 delete + o 1 delete; the three reads 1r.
"""

from __future__ import annotations

import json

from fabric_tpu.chaincode.runtime import FuncContract
from fabric_tpu.chaincode.stub import SimulationError, create_composite_key

ASSET_COLLECTION = "assetCollection"
AGREEMENT = "transferAgreement"


def org_collection(mspid: str) -> str:
    return mspid + "PrivateCollection"


def _compact(doc: dict) -> bytes:
    return json.dumps(doc, separators=(",", ":")).encode()


def _asset(object_type: str, asset_id: str, color: str, size: int,
           owner: str) -> bytes:
    return _compact({"objectType": object_type, "assetID": asset_id,
                     "color": color, "size": size, "owner": owner})


def _details(asset_id: str, appraised_value: int) -> bytes:
    return _compact({"assetID": asset_id, "appraisedValue": appraised_value})


def client_id(stub) -> str:
    """The submitting client's id: its certificate's common name."""
    from cryptography.x509.oid import NameOID

    from fabric_tpu.msp.identity import Identity
    try:
        cert = Identity.deserialize(stub.creator).cert
        return cert.subject.get_attributes_for_oid(
            NameOID.COMMON_NAME)[0].value
    except Exception:
        raise SimulationError("creator identity has no common name")


def _verify_client_org_matches_peer_org(stub) -> str:
    org = stub.creator_mspid()
    if org != stub.peer_mspid:
        raise SimulationError(
            f"client from org {org} is not authorized to read or write "
            f"private data from an org {stub.peer_mspid} peer")
    return org


def _transient_json(stub, name: str, fields: tuple) -> dict:
    raw = stub.get_transient().get(name)
    if raw is None:
        raise SimulationError(f"{name} key not found in the transient map")
    try:
        doc = json.loads(raw)
    except ValueError:
        raise SimulationError(f"{name}: not JSON")
    if not isinstance(doc, dict):
        raise SimulationError(f"{name}: not an object")
    for field, kind in fields:
        value = doc.get(field)
        # the exact type: a bool is an int to Python, and no size or price
        if type(value) is not kind or not value or (kind is int
                                                    and value < 0):
            raise SimulationError(
                f"{field} field must be a "
                + ("positive integer" if kind is int
                   else "non-empty string"))
    return doc


def _agreement_key(asset_id: str) -> str:
    return create_composite_key(AGREEMENT, [asset_id])


def _held_asset(stub, asset_id: str) -> dict:
    raw = stub.get_private_data(ASSET_COLLECTION, asset_id)
    if raw is None:
        raise SimulationError(f"{asset_id} does not exist")
    return json.loads(raw)


def create_asset(stub):
    doc = _transient_json(stub, "asset_properties", (
        ("objectType", str), ("assetID", str), ("color", str),
        ("size", int), ("appraisedValue", int)))
    asset_id = doc["assetID"]
    if stub.get_private_data(ASSET_COLLECTION, asset_id) is not None:
        raise SimulationError(f"this asset already exists: {asset_id}")
    owner = client_id(stub)
    org = _verify_client_org_matches_peer_org(stub)
    stub.put_private_data(ASSET_COLLECTION, asset_id, _asset(
        doc["objectType"], asset_id, doc["color"], doc["size"], owner))
    stub.put_private_data(org_collection(org), asset_id,
                          _details(asset_id, doc["appraisedValue"]))
    return b""


def agree_to_transfer(stub):
    buyer = client_id(stub)
    doc = _transient_json(stub, "asset_value", (
        ("assetID", str), ("appraisedValue", int)))
    asset_id = doc["assetID"]
    _held_asset(stub, asset_id)
    org = _verify_client_org_matches_peer_org(stub)
    stub.put_private_data(org_collection(org), asset_id,
                          _details(asset_id, doc["appraisedValue"]))
    stub.put_private_data(ASSET_COLLECTION, _agreement_key(asset_id),
                          buyer.encode())
    return b""


def _verify_agreement(stub, asset_id: str, owner: str, owner_org: str,
                      buyer_msp: str) -> None:
    if client_id(stub) != owner:
        raise SimulationError(
            "submitting client identity does not own asset")
    mine = stub.get_private_data_hash(org_collection(owner_org), asset_id)
    if mine is None:
        raise SimulationError(
            f"hash of appraised value for {asset_id} does not exist in "
            f"collection {org_collection(owner_org)}")
    theirs = stub.get_private_data_hash(org_collection(buyer_msp), asset_id)
    if theirs is None:
        raise SimulationError(
            f"hash of appraised value for {asset_id} does not exist in "
            f"collection {org_collection(buyer_msp)}: "
            "AgreeToTransfer must be called by the buyer first")
    if mine != theirs:
        raise SimulationError(
            f"hash for appraised value for owner {mine.hex()} does not "
            f"match value for buyer {theirs.hex()}")


def transfer_asset(stub):
    doc = _transient_json(stub, "asset_owner", (
        ("assetID", str), ("buyerMSP", str)))
    asset_id = doc["assetID"]
    org = _verify_client_org_matches_peer_org(stub)
    asset = _held_asset(stub, asset_id)
    _verify_agreement(stub, asset_id, asset["owner"], org, doc["buyerMSP"])
    buyer = stub.get_private_data(ASSET_COLLECTION, _agreement_key(asset_id))
    if not buyer:
        raise SimulationError(f"BuyerID not found in TransferAgreement "
                              f"for {asset_id}")
    stub.put_private_data(ASSET_COLLECTION, asset_id, _asset(
        asset["objectType"], asset_id, asset["color"], asset["size"],
        buyer.decode()))
    stub.del_private_data(org_collection(org), asset_id)
    stub.del_private_data(ASSET_COLLECTION, _agreement_key(asset_id))
    return b""


def delete_asset(stub):
    doc = _transient_json(stub, "asset_delete", (("assetID", str),))
    asset_id = doc["assetID"]
    org = _verify_client_org_matches_peer_org(stub)
    if stub.get_private_data(ASSET_COLLECTION, asset_id) is None:
        raise SimulationError(f"asset not found: {asset_id}")
    if stub.get_private_data(org_collection(org), asset_id) is None:
        raise SimulationError(
            f"asset private details does not exist in client org's "
            f"collection: {asset_id}")
    stub.del_private_data(ASSET_COLLECTION, asset_id)
    stub.del_private_data(org_collection(org), asset_id)
    return b""


def delete_transfer_agreement(stub):
    doc = _transient_json(stub, "agreement_delete", (("assetID", str),))
    asset_id = doc["assetID"]
    org = _verify_client_org_matches_peer_org(stub)
    key = _agreement_key(asset_id)
    if stub.get_private_data(ASSET_COLLECTION, key) is None:
        raise SimulationError(
            f"asset's transfer_agreement does not exist: {asset_id}")
    stub.del_private_data(org_collection(org), asset_id)
    stub.del_private_data(ASSET_COLLECTION, key)
    return b""


def read_asset(stub, asset_id):
    raw = stub.get_private_data(ASSET_COLLECTION, asset_id.decode())
    if raw is None:
        raise SimulationError(f"{asset_id.decode()} does not exist")
    return raw


def read_asset_private_details(stub, collection, asset_id):
    raw = stub.get_private_data(collection.decode(), asset_id.decode())
    if raw is None:
        raise SimulationError(
            f"{asset_id.decode()} does not exist in collection "
            f"{collection.decode()}")
    return raw


def read_transfer_agreement(stub, asset_id):
    key = asset_id.decode()
    buyer = stub.get_private_data(ASSET_COLLECTION, _agreement_key(key))
    if buyer is None:
        raise SimulationError(f"no transfer agreement for {key}")
    return _compact({"assetID": key, "buyerID": buyer.decode()})


def contract() -> FuncContract:
    return FuncContract(
        CreateAsset=create_asset, AgreeToTransfer=agree_to_transfer,
        TransferAsset=transfer_asset, DeleteAsset=delete_asset,
        DeleteTransferAgreement=delete_transfer_agreement,
        ReadAsset=read_asset,
        ReadAssetPrivateDetails=read_asset_private_details,
        ReadTransferAgreement=read_transfer_agreement)
