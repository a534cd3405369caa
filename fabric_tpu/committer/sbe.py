"""State-based (key-level) endorsement — SBE.

Reference parity (VERDICT.md missing #5):
/root/reference/core/common/validation/statebased/validator_keylevel.go:244
and the shim's SetStateValidationParameter.  A key's validation parameter
(a signature policy) OVERRIDES the chaincode endorsement policy for
transactions that write that key; keys without one fall back to the
chaincode policy.  Policy transitions take effect at the point the
metadata-updating transaction commits: later transactions in the SAME
block that touch the key are judged under the new policy when the updater
was valid (the reference's intra-block dependency tracking), and
transactions in later blocks read the committed metadata.

Storage model: validation parameters live in the companion namespace
`<ns>#meta` as ordinary versioned writes — MVCC orders concurrent policy
updates exactly like state writes, and the statedb is the committed
lookup source.

A deleted key drops its parameter.  Upstream keeps a key's metadata
inside its versioned value, so deleting the key deletes the parameter
and a key created again starts under the chaincode policy.  Here the
COMMIT decides the same, not the contract: a VALID delete of (ns, key)
removes (`<ns>#meta`, key) in the block's update batch whatever the
rw-set said of `#meta` (`ledger/mvcc._stage_writes`), and a transaction
that passed the gate and deletes a key clears the key's parameter in the
block's overlay for the transactions after it (`apply_valid_tx`).

The same-block rule is this repository's: a later transaction of the
block is judged under the parameter an earlier one that PASSED THE GATE
(MVCC runs afterwards, in the ledger) set, cleared or deleted with its
key; upstream is recalled to refuse such a later transaction outright —
both leave it invalid, the code may differ.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from fabric_tpu.ledger.statedb import META_SUFFIX
from fabric_tpu.policy import SignaturePolicy
from fabric_tpu.utils import serde


def meta_namespace(namespace: str) -> str:
    return namespace + META_SUFFIX


def is_meta_namespace(namespace: str) -> bool:
    return namespace.endswith(META_SUFFIX)


def base_namespace(meta_ns: str) -> str:
    return meta_ns[:-len(META_SUFFIX)]


def encode_policy(policy: SignaturePolicy) -> bytes:
    return serde.encode(policy.to_dict())


def decode_policy(data: bytes) -> SignaturePolicy:
    return SignaturePolicy.from_dict(serde.decode(data))


class SbeOverlay:
    """Intra-block view of key-level policies: committed statedb metadata
    plus updates from already-validated transactions of this block."""

    def __init__(self, lookup=None):
        # lookup: (base_ns, key) -> policy bytes | None (committed state)
        self._lookup = lookup or (lambda ns, key: None)
        self._updates: Dict[Tuple[str, str], Optional[bytes]] = {}
        # decoded-policy intern table, keyed by the policy BYTES: repeat
        # lookups return the SAME object, so consumers may key caches on
        # object identity for the overlay's lifetime (one block).  A
        # fresh decode per call would free+reuse ids and let one
        # policy's cached verdict answer for another's.
        self._decoded: Dict[bytes, Optional[SignaturePolicy]] = {}
        # what the gate asked, in plain ints (the validator adds them to
        # its counters once a block): keys answered by a committed
        # parameter, by none (the namespace policy governs), by this
        # block's own updates; transactions a key's parameter failed
        self.by_parameter = self.by_namespace = self.by_overlay = 0
        self.failures = 0

    @property
    def policies(self) -> int:
        """Distinct parameters decoded for this block."""
        return len(self._decoded)

    def policy_for(self, namespace: str, key: str) -> Optional[SignaturePolicy]:
        k = (namespace, key)
        if k in self._updates:
            raw = self._updates[k]
            self.by_overlay += 1
        else:
            raw = self._lookup(namespace, key)
            if raw:
                self.by_parameter += 1
            else:
                self.by_namespace += 1
        if not raw:
            return None
        raw = bytes(raw)
        if raw in self._decoded:
            return self._decoded[raw]
        try:
            pol = decode_policy(raw)
        except Exception:
            pol = None
        self._decoded[raw] = pol
        return pol

    def apply_valid_tx(self, meta_writes, deletes=()) -> None:
        """Record the metadata writes of a transaction that passed the
        gate — meta_writes: iterable of (base_ns, key, policy_bytes|None)
        — and the keys it deletes, (ns, key): a deleted key's parameter
        goes with it, whatever the same rw-set wrote to `#meta`."""
        for ns, key, raw in meta_writes:
            self._updates[(ns, key)] = raw
        for k in deletes:
            self._updates[k] = None


def statedb_lookup(statedb):
    """Adapter: committed key-level policies from the state DB."""
    def lookup(namespace: str, key: str):
        vv = statedb.get(meta_namespace(namespace), key)
        return None if vv is None else vv.value
    return lookup
