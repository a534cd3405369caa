"""Per-block transaction validation-code bitmap.

Reference parity: internal/pkg/txflags/validation_flags.go and the
TxValidationCode enum from fabric-protos.  The committer writes this
bitmap into block metadata (validator.go:214-260) and the ledger treats
code==VALID as the commit predicate.
"""

from __future__ import annotations

import enum
from typing import Iterable, List


class ValidationCode(enum.IntEnum):
    VALID = 0
    NIL_ENVELOPE = 1
    BAD_PAYLOAD = 2
    BAD_COMMON_HEADER = 3
    BAD_CREATOR_SIGNATURE = 4
    INVALID_ENDORSER_TRANSACTION = 5
    INVALID_CONFIG_TRANSACTION = 6
    UNSUPPORTED_TX_PAYLOAD = 7
    BAD_PROPOSAL_TXID = 8
    DUPLICATE_TXID = 9
    ENDORSEMENT_POLICY_FAILURE = 10
    MVCC_READ_CONFLICT = 11
    PHANTOM_READ_CONFLICT = 12
    UNKNOWN_TX_TYPE = 13
    TARGET_CHAIN_NOT_FOUND = 14
    MARSHAL_TX_ERROR = 15
    NIL_TXACTION = 16
    EXPIRED_CHAINCODE = 17
    CHAINCODE_VERSION_CONFLICT = 18
    BAD_HEADER_EXTENSION = 19
    BAD_CHANNEL_HEADER = 20
    BAD_RESPONSE_PAYLOAD = 21
    BAD_RWSET = 22
    ILLEGAL_WRITESET = 23
    INVALID_WRITESET = 24
    INVALID_CHAINCODE = 25
    NOT_VALIDATED = 254
    INVALID_OTHER_REASON = 255


class TxFlags:
    """Mutable per-block validation bitmap (txflags.ValidationFlags)."""

    def __init__(self, n: int, fill: ValidationCode = ValidationCode.NOT_VALIDATED):
        self._codes: List[int] = [int(fill)] * n

    @staticmethod
    def from_codes(codes: Iterable[int]) -> "TxFlags":
        f = TxFlags(0)
        f._codes = [int(c) for c in codes]
        return f

    def __len__(self) -> int:
        return len(self._codes)

    def set(self, i: int, code: ValidationCode) -> None:
        self._codes[i] = int(code)

    def load(self, data: bytes) -> None:
        """Every code at once, in place: `to_bytes` back in."""
        if len(data) != len(self._codes):
            raise ValueError("load: one code a transaction")
        self._codes = list(data)

    def flag(self, i: int) -> ValidationCode:
        return ValidationCode(self._codes[i])

    def is_valid(self, i: int) -> bool:
        return self._codes[i] == int(ValidationCode.VALID)

    def is_set_to(self, i: int, code: ValidationCode) -> bool:
        return self._codes[i] == int(code)

    def all_validated(self) -> bool:
        return all(c != int(ValidationCode.NOT_VALIDATED) for c in self._codes)

    def valid_count(self) -> int:
        return sum(1 for c in self._codes if c == int(ValidationCode.VALID))

    def codes(self) -> List[int]:
        return list(self._codes)

    def to_bytes(self) -> bytes:
        return bytes(self._codes)

    @staticmethod
    def from_bytes(data: bytes) -> "TxFlags":
        return TxFlags.from_codes(data)
