"""Collection configuration + the hashed-namespace convention.

Reference parity: the collection config package (core/common/privdata,
collection criteria in gossip/privdata) reduced to the fields this
framework's planes consume: membership policy (org list), BTL, the
required/max peer counts that drive distribution, the member-only
flags the shim enforces at simulation, and the collection's own
endorsement policy (v2.0: it takes the chaincode's place for the
collection's keys).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

PVT_SEP = "$"


def pvt_namespace(namespace: str, collection: str) -> str:
    """Public-ledger namespace carrying a collection's write HASHES."""
    return f"{namespace}{PVT_SEP}{collection}"


def chaincode_of(namespace: str) -> str:
    """The chaincode a namespace belongs to: `ns` of a collection's hashed
    namespace `ns$collection`, the namespace itself otherwise.  Where the
    collection has no endorsement policy of its own, its chaincode's
    governs."""
    return namespace.split(PVT_SEP, 1)[0]


def hash_key(key: str) -> str:
    return hashlib.sha256(key.encode()).hexdigest()


def hash_value(value: bytes) -> bytes:
    return hashlib.sha256(value).digest()


@dataclass(frozen=True)
class CollectionConfig:
    """StaticCollectionConfig equivalent."""
    name: str
    member_orgs: Tuple[str, ...]
    block_to_live: int = 0          # 0 = never purge
    required_peer_count: int = 0    # distribution ack threshold
    maximum_peer_count: int = 2
    member_only_read: bool = False  # a non-member client's read is refused
    member_only_write: bool = False
    # signature-policy text (policy/ parse_policy); "" = the chaincode's
    endorsement_policy: str = ""

    def is_member(self, mspid: str) -> bool:
        return mspid in self.member_orgs

    @staticmethod
    def from_node_config(col: dict) -> "CollectionConfig":
        """One entry of a node config's `collections`: `name`, `members`,
        and optionally `btl`, `required_peer_count`, `max_peer_count`,
        `member_only_read`, `member_only_write`, `endorsement_policy`."""
        return CollectionConfig(
            col["name"], member_orgs=tuple(col["members"]),
            block_to_live=int(col.get("btl", 0)),
            required_peer_count=int(col.get("required_peer_count", 0)),
            maximum_peer_count=int(col.get("max_peer_count", 2)),
            member_only_read=bool(col.get("member_only_read", False)),
            member_only_write=bool(col.get("member_only_write", False)),
            endorsement_policy=col.get("endorsement_policy", ""))


class CollectionRegistry:
    """(namespace, collection) -> CollectionConfig; committed with the
    chaincode definition in the reference (_lifecycle), registered on the
    lifecycle object here."""

    def __init__(self):
        self._configs: Dict[Tuple[str, str], CollectionConfig] = {}

    def define(self, namespace: str, cfg: CollectionConfig) -> None:
        self._configs[(namespace, cfg.name)] = cfg

    def get(self, namespace: str, collection: str) -> Optional[CollectionConfig]:
        return self._configs.get((namespace, collection))

    def for_namespace(self, namespace: str) -> List[CollectionConfig]:
        return [c for (ns, _), c in self._configs.items() if ns == namespace]

    def block_to_live(self) -> Dict[str, int]:
        """{hashed namespace `ns$collection`: BTL} of the collections
        whose keys expire: what the ledger's expiry step is given."""
        return {pvt_namespace(ns, c.name): c.block_to_live
                for (ns, _), c in self._configs.items() if c.block_to_live}
