"""Zero-copy wire views: lazy Block/Envelope access over raw frame bytes.

The committer's deliver path and the gateway's submit path both used to
turn every received frame into a full Python object tree
(Block.deserialize -> per-envelope bytes -> per-field dataclasses)
before any validation ran.  native/fastparse.c extracts the byte SPANS
those paths actually touch — envelope positions, header fields, the
metadata splice point — in one C walk, and this module wraps them:

  parse_block(raw)      -> BlockView (native parse) | Block (fallback)
  BlockView             duck-types Block for every consumer on the
                        covered path; materializes .data / .metadata
                        lazily only when a consumer truly needs Python
                        objects (config handling, a tx its lane table
                        does not speak for)
  envelope_summary(raw) -> (type, channel_id, txid) | None — the gateway
                        header peek, no Envelope/Header trees
  parse_block_py / envelope_summary_py
                        pure-Python line-for-line mirrors of the native
                        accept/reject decisions and extracted fields,
                        used by the differential fuzz suite
  n_txs(block)          len(block.data) without forcing a BlockView to
                        materialize its envelope list
  lane_table(block)     -> LaneTable: the block's rw-sets and txids as
                        fixed-width lanes, extracted once a block by the
                        C walker; what the commit path reads after the
                        validator instead of decoding envelopes again
  prepare_lanes(block, at)
                        the same table, opened ahead of the commit by a
                        caller with time to spare

Fallback semantics: the native parser accepts EXACTLY the strict
canonical block shape; anything else (including every malformed input)
returns None and parse_block falls back to Block.deserialize, so
accept/reject behavior — down to the exception raised — is unchanged
from the pure-Python path.  A BlockView is only ever produced for bytes
Block.deserialize would have accepted.

Key layout fact (fabric_tpu/utils/serde.py): block encodings are
canonical dicts with sorted keys data < header < metadata.  So the data
LIST's value span inside the raw bytes IS serde.encode(list(data)) —
sha256 over it equals block_data_hash(block.data) — and metadata is the
LAST value, so a metadata-mutated block re-serializes as
raw[:meta_val_off] + serde.encode(metadata), a pure splice.
"""

from __future__ import annotations

import hashlib
import struct
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as np

from fabric_tpu.utils import serde
from fabric_tpu.protocol.types import (
    Block,
    BlockHeader,
    BlockMetadata,
    Envelope,
    block_header_hash,
)

try:
    from fabric_tpu import native as _native_pkg
    _fastparse = _native_pkg.load("_fastparse")
except Exception:  # pragma: no cover - broken toolchain
    _fastparse = None

_Raw = Union[bytes, bytearray, memoryview]


class BlockView:
    """A Block over raw wire bytes; Python objects are built on demand.

    Cheap always: .header, .n_data, .raw, .data_spans, .computed_data_hash,
    .hash(), .serialize() (identity until .metadata is touched).
    Materializing: .data (full envelope bytes list, cached), .metadata
    (decoded dict, cached — after first access serialize() re-splices,
    which is bit-identical for unmutated metadata by serde bijection).
    """

    __slots__ = ("raw", "header", "n_data", "_data_off", "_data_end",
                 "_spans", "_meta_off", "_data", "_metadata", "_dhash",
                 "_lanes", "_table", "parsed", "intake")

    def __init__(self, raw: _Raw, number: int, previous_hash: bytes,
                 data_hash: bytes, data_off: int, data_end: int,
                 n_data: int, spans, meta_off: int):
        self.raw = raw
        self.header = BlockHeader(number, previous_hash, data_hash)
        self.n_data = n_data
        self._data_off = data_off
        self._data_end = data_end
        self._spans = spans
        self._meta_off = meta_off
        self._data: Optional[List[bytes]] = None
        self._metadata: Optional[BlockMetadata] = None
        self._dhash: Optional[bytes] = None
        self._lanes: Optional[tuple] = None     # (rwset_lanes result,)
        self._table: Optional["LaneTable"] = None

    # -- covered-path accessors (no per-tx objects) ---------------------

    @property
    def data_spans(self):
        """(base, spans) pair for _fastcollect.digest_spans."""
        return self.raw, self._spans

    @property
    def rwset_lanes(self):
        """Fixed-width uint64 lanes of the block's rw-sets and txids:
        (flags, n_tx, n_keys, n_reads, n_writes, arena) — see
        rwset_lanes() below.  Zero-copy like data_spans: no per-tx
        Python objects are built.  Extracted at the first access and
        kept: its four readers take it as a LaneTable through
        lane_table() — the ledger's MVCC walk, the block store's txid
        index, the commit notifier and the private-data coordinator."""
        if self._lanes is None:
            self._lanes = (rwset_lanes(self.raw, self._spans),)
        return self._lanes[0]

    @property
    def computed_data_hash(self) -> bytes:
        """sha256 over the data list's value span ==
        block_data_hash(self.data), computed without materializing."""
        if self._dhash is None:
            self._dhash = hashlib.sha256(
                self.raw[self._data_off:self._data_end]).digest()
        return self._dhash

    def hash(self) -> bytes:
        return block_header_hash(self.header)

    def serialize(self) -> _Raw:
        if self._metadata is None:
            return self.raw
        return (bytes(self.raw[:self._meta_off])
                + serde.encode(self._metadata.to_dict()))

    # -- materializing accessors ---------------------------------------

    @property
    def data(self) -> List[bytes]:
        if self._data is None:
            raw = self.raw
            tab = memoryview(self._spans).cast("Q")
            self._data = [bytes(raw[tab[2 * i]:tab[2 * i] + tab[2 * i + 1]])
                          for i in range(self.n_data)]
        return self._data

    @property
    def metadata(self) -> BlockMetadata:
        if self._metadata is None:
            md = serde.decode(bytes(self.raw[self._meta_off:]))
            self._metadata = BlockMetadata.from_dict(md)
        return self._metadata

    def envelopes(self) -> List[Envelope]:
        return [Envelope.deserialize(b) for b in self.data]

    def to_dict(self) -> dict:
        return {"header": self.header.to_dict(), "data": list(self.data),
                "metadata": self.metadata.to_dict()}

    def to_block(self) -> Block:
        return Block(self.header, list(self.data), self.metadata)


def parse_block(raw: _Raw) -> Union[BlockView, Block]:
    """Wire bytes -> BlockView (native fast path) or Block (fallback).

    Raises exactly what Block.deserialize raises for bytes neither
    accepts; never raises for bytes Block.deserialize accepts.
    """
    t0 = time.perf_counter()
    block = None
    if _fastparse is not None:
        r = _fastparse.parse_block(raw)
        if r is not None:
            block = BlockView(raw, *r)
    if block is None:
        block = Block.deserialize(raw)
    # when the parse ran, on perf_counter: the committer records it as
    # the `wire.parse_block` span of the block's trace
    block.parsed = (t0, time.perf_counter())
    return block


def n_txs(block) -> int:
    """len(block.data) without forcing a BlockView to materialize."""
    n = getattr(block, "n_data", None)
    return len(block.data) if n is None else n


def envelope_summary(raw: _Raw) -> Optional[Tuple[str, str, str]]:
    """(type, channel_id, txid) of a serialized Envelope, or None when
    the bytes deviate from the strict shape (caller falls back to the
    Envelope.deserialize path, preserving its exact error behavior)."""
    if _fastparse is None:
        return None
    return _fastparse.envelope_summary(raw)


# ---------------------------------------------------------------------------
# pure-Python mirrors — the differential-fuzz reference implementations.
# Native accept/reject and every extracted field must match these
# byte-for-byte (tests/test_fastparse.py); like collect_py they are the
# plain-language statement of what the C walk does.


def parse_block_py(raw: _Raw):
    """Mirror of _fastparse.parse_block: (number, previous_hash,
    data_hash, data list, metadata dict, meta_val_off) or None."""
    try:
        d = serde.decode_py(bytes(raw))
    except Exception:
        return None
    if not isinstance(d, dict) or sorted(d) != ["data", "header", "metadata"]:
        return None
    h = d["header"]
    if (not isinstance(h, dict)
            or sorted(h) != ["data_hash", "number", "previous_hash"]):
        return None
    number = h["number"]
    # native reads a fixed 'I' i64; bignum ('V') numbers fall back
    if (not isinstance(number, int) or isinstance(number, bool)
            or not -(2 ** 63) <= number < 2 ** 63):
        return None
    if not isinstance(h["previous_hash"], bytes):
        return None
    if not isinstance(h["data_hash"], bytes):
        return None
    if not isinstance(d["data"], list):
        return None
    for item in d["data"]:
        if not isinstance(item, bytes):
            return None
    if not isinstance(d["metadata"], dict):
        return None
    # metadata is the top dict's last key: its value span runs to the end
    meta_off = len(bytes(raw)) - len(serde.encode_py(d["metadata"]))
    return (number, h["previous_hash"], h["data_hash"], d["data"],
            d["metadata"], meta_off)


# ---------------------------------------------------------------------------
# rw-set validation lanes
#
# rwset_lanes(base, spans) classifies every envelope span against the
# exact semantics of ledger/mvcc.parse_endorser_tx and emits fixed-width
# uint64 lane tables for the lane table's four readers (LaneTable below:
# the ledger's MVCC walk, the block store's txid index, the commit
# notifier, the private-data coordinator).  Statuses:
#
#   0 OK       strict endorser tx, lanes emitted
#   1 SKIP     parse_endorser_tx provably returns None
#   2 BAD      parse_endorser_tx provably raises (oracle stamps
#              BAD_RWSET on a gate-valid tx)
#   3 RANGE    endorser tx with a non-empty range_queries list
#   4 UNKNOWN  host outcome deterministic but no lane can say it
#
# Result tuple (flags, n_tx, n_keys, n_reads, n_writes, arena):
#   flags  0 ok | 1 key-hash collision (arena is None; caller demotes)
#   arena  native-endian u64 cells in four sections
#          tx      n_tx    x 3  [status, txid_off, txid_len]
#          reads   n_reads x 5  [tx, slot, has_version, block, txn]
#          writes  n_writes x 5 [tx, slot, is_delete, value_off, value_len]
#          keys    n_keys  x 5  [hash, ns_off, ns_len, key_off, key_len]
# or None when spans is not a valid span table over base.

LANE_OK, LANE_SKIP, LANE_BAD, LANE_RANGE, LANE_UNKNOWN = 0, 1, 2, 3, 4


def rwset_lanes(base: _Raw, spans) -> Optional[tuple]:
    """Native lane extraction when available, else the Python mirror."""
    if _fastparse is not None:
        return _fastparse.rwset_lanes(base, spans)
    return rwset_lanes_py(base, spans)


class LaneTable:
    """One block's rwset_lanes arena, opened for the host: what the
    commit path reads instead of decoding envelopes again.  Rows keep
    the arena's order (lanes ascend by tx); every offset indexes `base`.

      tx      (n_tx, 3) int64      [status, txid_off, txid_len]
      status  [n_tx]  LANE_* of each tx (tx's first column)
      reads   (n_reads, 5) int64   [tx, slot, has_version, block, txnum]
      writes  (n_writes, 5) int64  [tx, slot, is_delete, value_off, len]
      keys    (n_keys, 5) int64    [hash, ns_off, ns_len, key_off, key_len]
    """

    __slots__ = ("base", "n_tx", "tx", "status", "reads", "writes",
                 "keys", "opened_at", "_txids", "_key_strs")

    def __init__(self, base: _Raw, lanes: tuple, opened_at: str = "commit"):
        _flags, n_tx, n_keys, n_reads, n_writes, arena = lanes
        # where the first reader asked: lane_table's `at`
        self.opened_at = opened_at
        # int64: versions are two's-complement i32 in u64 cells
        cells = np.frombuffer(arena, dtype=np.int64)
        o = 3 * n_tx
        self.base = base
        self.n_tx = n_tx
        self.tx = cells[:o].reshape(n_tx, 3)
        self.status = self.tx[:, 0]
        self.reads = cells[o:o + 5 * n_reads].reshape(n_reads, 5)
        o += 5 * n_reads
        self.writes = cells[o:o + 5 * n_writes].reshape(n_writes, 5)
        o += 5 * n_writes
        self.keys = cells[o:o + 5 * n_keys].reshape(n_keys, 5)
        self._txids: Optional[List[Optional[str]]] = None
        self._key_strs: Optional[List[Tuple[str, str]]] = None

    @property
    def txids(self) -> List[Optional[str]]:
        """Each tx's txid, None where the status is not OK (the table
        does not speak for that tx: decode its envelope).  An OK txid
        is what Envelope.header().channel_header.txid gives."""
        if self._txids is None:
            base = self.base
            if _fastparse is not None:
                self._txids = _fastparse.arena_txids(base, self.tx)
            else:
                self._txids = [
                    str(base[off:off + n], "utf-8") if st == LANE_OK
                    else None for st, off, n in self.tx.tolist()]
        return self._txids

    @property
    def key_strs(self) -> List[Tuple[str, str]]:
        """(namespace, key) of each interned slot, decoded once."""
        if self._key_strs is None:
            base = self.base
            if _fastparse is not None:
                self._key_strs = _fastparse.arena_keys(base, self.keys)
            else:
                self._key_strs = [
                    (str(base[no:no + nn], "utf-8"),
                     str(base[ko:ko + kn], "utf-8"))
                    for _h, no, nn, ko, kn in self.keys.tolist()]
        return self._key_strs

    def txs_writing_under(self, marker: str) -> List[int]:
        """The OK txs, ascending, with a write whose namespace holds
        `marker`."""
        slot_has = np.fromiter((marker in ns for ns, _key in self.key_strs),
                               dtype=bool, count=len(self.keys))
        if not slot_has.any():
            return []
        w = self.writes
        return np.unique(w[slot_has[w[:, 1]], 0]).tolist()


def lane_table(block, at: str = "commit"
               ) -> Tuple[Optional[LaneTable], Optional[str]]:
    """(the block's LaneTable, None), or (None, why there is none):
    "no_view" the block is not a BlockView, "no_native" the extractor is
    the Python mirror (byte-by-byte hashing: never a fast path),
    "collision" two keys of the block share a hash, "count" the span
    table did not extract.  Opened once a block and kept on the view,
    with the `at` of the call that opened it (`LaneTable.opened_at`)."""
    if not isinstance(block, BlockView):
        return None, "no_view"
    if block._table is not None:
        return block._table, None
    if _fastparse is None:
        return None, "no_native"
    lanes = block.rwset_lanes
    if lanes is None:
        return None, "count"
    if lanes[0]:
        return None, "collision"
    block._table = LaneTable(block.raw, lanes, at)
    return block._table, None


def prepare_lanes(block, at: str) -> Optional[LaneTable]:
    """The block's lane table, opened ahead of the commit by a caller
    with time to spare before it (the validator, while the device
    verifies: `at`): the extractor's pass over every envelope, which
    runs without the interpreter lock.  The table's strings (`txids`,
    `key_strs`) are NOT decoded here: building them holds the lock for
    milliseconds, and a thread that wakes meanwhile to stamp the end of
    a device program (bccsp/dispatch_account.py) would stamp it late;
    their first reader in the commit decodes them, as before.  -> the
    table where this call opened it; None, and no work, for a block
    whose table is open already or that has none (`lane_table`'s
    reasons: the commit decodes its envelopes, as it decides by
    itself)."""
    if not isinstance(block, BlockView) or block._table is not None:
        return None
    return lane_table(block, at)[0]


def lane_txids(block) -> List[Optional[str]]:
    """A txid a tx of the block, off its lane table: None for a tx the
    table does not speak for (status not OK), and for every tx of a
    block without a table — the reader decodes those envelopes itself."""
    table, _ = lane_table(block)
    return table.txids if table is not None else [None] * n_txs(block)


def envelope_summary_py(raw: _Raw) -> Optional[Tuple[str, str, str]]:
    """Mirror of _fastparse.envelope_summary."""
    try:
        d = serde.decode_py(bytes(raw))
        if not isinstance(d, dict) or "payload" not in d or "signature" not in d:
            return None
        payload = d["payload"]
        if not isinstance(payload, bytes):
            return None
        p = serde.decode_py(payload)
        header = p["header"]
        ch = header["channel_header"]
        sh = header["signature_header"]
        if not isinstance(ch, dict) or not isinstance(sh, dict):
            return None
        if "creator" not in sh or "nonce" not in sh:
            return None
        t, cid, txid = ch["type"], ch["channel_id"], ch["txid"]
        if not (isinstance(t, str) and isinstance(cid, str)
                and isinstance(txid, str)):
            return None
        return (t, cid, txid)
    except Exception:
        return None


# -- rwset_lanes mirror ------------------------------------------------------
# Line-for-line mirror of the C lane extractor (native/fastparse.c
# py_rwset_lanes and its walk_* helpers).  Every status decision and
# every emitted cell must match the native output byte-for-byte
# (tests/test_fastparse.py and tests/test_commit_lanes.py drive them
# differentially); it is also the no-compiler fallback wired through
# rwset_lanes() above.

_M64 = (1 << 64) - 1


class _LaneStat(Exception):
    """Terminal per-envelope lane status (first terminal wins)."""

    def __init__(self, st: int):
        self.st = st


class _LaneColl(Exception):
    """Two distinct rw keys share a hash: the whole call demotes."""


class _LaneCur:
    """Byte cursor over the base buffer (mirror of the C cur_t)."""

    __slots__ = ("b", "p", "end")

    def __init__(self, b: bytes, p: int, end: int):
        self.b = b
        self.p = p
        self.end = end


class _LaneState:
    """Per-call lane accumulators (mirror of the C module globals)."""

    __slots__ = ("base", "reads", "writes", "keys", "by_hash")

    def __init__(self, base: bytes):
        self.base = base
        self.reads: list = []
        self.writes: list = []
        self.keys: list = []
        self.by_hash: dict = {}

    def intern(self, ns_off, ns_len, key_off, key_len) -> int:
        base = self.base
        h = 5381
        for byte in base[ns_off:ns_off + ns_len]:
            h = (h * 33 + byte) & _M64
        h = (h * 33) & _M64            # the 0x00 ns/key separator
        for byte in base[key_off:key_off + key_len]:
            h = (h * 33 + byte) & _M64
        rec = self.by_hash.get(h)
        if rec is not None:
            slot, noff, nlen, koff, klen = rec
            if (nlen == ns_len and klen == key_len
                    and base[noff:noff + nlen] == base[ns_off:ns_off + ns_len]
                    and base[koff:koff + klen]
                    == base[key_off:key_off + key_len]):
                return slot
            raise _LaneColl()
        slot = len(self.keys)
        self.keys.append((h, ns_off, ns_len, key_off, key_len))
        self.by_hash[h] = (slot, ns_off, ns_len, key_off, key_len)
        return slot


def _lane_u32(c: _LaneCur) -> int:
    if c.end - c.p < 4:
        raise _LaneStat(LANE_BAD)
    v = int.from_bytes(c.b[c.p:c.p + 4], "big")
    c.p += 4
    return v


def _lane_i64(c: _LaneCur):
    """rd_i64 mirror: None on non-'I' tag / truncation, else the int."""
    if c.p >= c.end or c.b[c.p] != 0x49 or c.end - c.p < 10:
        return None
    v = int.from_bytes(c.b[c.p + 1:c.p + 9], "big", signed=True)
    c.p += 9
    return v


def _lane_str(c: _LaneCur):
    """rd_str mirror: (off, len) span of an 'S' value, BAD otherwise."""
    if c.p >= c.end or c.b[c.p] != 0x53:
        raise _LaneStat(LANE_BAD)
    c.p += 1
    n = _lane_u32(c)
    if c.end - c.p < n:
        raise _LaneStat(LANE_BAD)
    try:
        c.b[c.p:c.p + n].decode("utf-8")
    except UnicodeDecodeError:
        raise _LaneStat(LANE_BAD) from None
    off = c.p
    c.p += n
    return off, n


def _lane_bytes(c: _LaneCur):
    """rd_bytes mirror: (off, len) span of a 'B' value, BAD otherwise."""
    if c.p >= c.end or c.b[c.p] != 0x42:
        raise _LaneStat(LANE_BAD)
    c.p += 1
    n = _lane_u32(c)
    if c.end - c.p < n:
        raise _LaneStat(LANE_BAD)
    off = c.p
    c.p += n
    return off, n


def _lane_canon(c: _LaneCur, depth: int) -> None:
    """canon_value_d mirror: skip one strict-canonical value or BAD."""
    if depth > serde.MAX_DEPTH or c.p >= c.end:
        raise _LaneStat(LANE_BAD)
    tag = c.b[c.p]
    c.p += 1
    if tag in (0x4E, 0x54, 0x46):              # N T F
        return
    if tag == 0x49:                            # I
        if c.end - c.p < 8:
            raise _LaneStat(LANE_BAD)
        c.p += 8
        return
    if tag == 0x56:                            # V
        n = _lane_u32(c)
        if (c.end - c.p < n or n < 8 or c.b[c.p] == 0
                or (n == 8 and c.b[c.p] < 0x80)):
            raise _LaneStat(LANE_BAD)
        c.p += n
        return
    if tag == 0x42:                            # B
        n = _lane_u32(c)
        if c.end - c.p < n:
            raise _LaneStat(LANE_BAD)
        c.p += n
        return
    if tag == 0x53:                            # S
        c.p -= 1
        _lane_str(c)
        return
    if tag == 0x4C:                            # L
        n = _lane_u32(c)
        for _ in range(n):
            _lane_canon(c, depth + 1)
        return
    if tag == 0x44:                            # D
        n = _lane_u32(c)
        prev = [None]
        for _ in range(n):
            _lane_dict_key(c, prev)
            _lane_canon(c, depth + 1)
        return
    raise _LaneStat(LANE_BAD)


def _lane_dict_enter(c: _LaneCur) -> int:
    if c.p >= c.end or c.b[c.p] != 0x44:
        raise _LaneStat(LANE_BAD)
    c.p += 1
    return _lane_u32(c)


def _lane_dict_key(c: _LaneCur, prev: list) -> bytes:
    kn = _lane_u32(c)
    if c.end - c.p < kn:
        raise _LaneStat(LANE_BAD)
    k = c.b[c.p:c.p + kn]
    c.p += kn
    try:
        k.decode("utf-8")
    except UnicodeDecodeError:
        raise _LaneStat(LANE_BAD) from None
    if prev[0] is not None and prev[0] >= k:
        raise _LaneStat(LANE_BAD)
    prev[0] = k
    return k


def _lane_dict_find(c: _LaneCur, want: bytes):
    """dict_find mirror: value span (off, end) or None; BAD on
    malformation.  Canon-validates the full dict either way."""
    n = _lane_dict_enter(c)
    prev = [None]
    found = None
    for _ in range(n):
        k = _lane_dict_key(c, prev)
        vstart = c.p
        _lane_canon(c, 1)
        if k == want:
            found = (vstart, c.p)
    return found


def _lane_version(c: _LaneCur):
    if c.p >= c.end:
        raise _LaneStat(LANE_BAD)
    tag = c.b[c.p]
    if tag == 0x4E:                            # N: absent version
        c.p += 1
        return 0, 0, 0
    if tag != 0x4C:
        raise _LaneStat(LANE_UNKNOWN)
    c.p += 1
    n = _lane_u32(c)
    if n < 2:
        raise _LaneStat(LANE_BAD)              # v[0]/v[1] IndexError
    v0 = _lane_i64(c)
    if v0 is None or not -(2 ** 31) <= v0 <= 2 ** 31 - 1:
        raise _LaneStat(LANE_UNKNOWN)
    v1 = _lane_i64(c)
    if v1 is None or not -(2 ** 31) <= v1 <= 2 ** 31 - 1:
        raise _LaneStat(LANE_UNKNOWN)
    for _ in range(n - 2):
        _lane_canon(c, 1)
    return 1, v0 & _M64, v1 & _M64


def _lane_read(c: _LaneCur, st: _LaneState, emit: bool, tx: int,
               ns_off: int, ns_len: int) -> None:
    n = _lane_dict_enter(c)
    prev = [None]
    key_off = key_len = 0
    has = blk = txn = 0
    have_key = False
    for _ in range(n):
        k = _lane_dict_key(c, prev)
        if k == b"key":
            if c.p >= c.end or c.b[c.p] != 0x53:
                raise _LaneStat(LANE_UNKNOWN)
            key_off, key_len = _lane_str(c)
            have_key = True
        elif k == b"version":
            has, blk, txn = _lane_version(c)
        else:
            _lane_canon(c, 1)
    if not have_key:
        raise _LaneStat(LANE_BAD)
    if emit:
        slot = st.intern(ns_off, ns_len, key_off, key_len)
        st.reads.append((tx, slot, has, blk, txn))


def _lane_write(c: _LaneCur, st: _LaneState, emit: bool, tx: int,
                ns_off: int, ns_len: int) -> None:
    n = _lane_dict_enter(c)
    prev = [None]
    key_off = key_len = 0
    delete = voff = vlen = 0
    have_key = False
    for _ in range(n):
        k = _lane_dict_key(c, prev)
        if k == b"key":
            if c.p >= c.end or c.b[c.p] != 0x53:
                raise _LaneStat(LANE_UNKNOWN)
            key_off, key_len = _lane_str(c)
            have_key = True
        elif k == b"is_delete":
            if c.p >= c.end:
                raise _LaneStat(LANE_BAD)
            if c.b[c.p] == 0x54:               # T
                delete = 1
            elif c.b[c.p] == 0x46:             # F
                delete = 0
            else:
                raise _LaneStat(LANE_UNKNOWN)  # truthy non-bool
            c.p += 1
        elif k == b"value":
            if c.p >= c.end or c.b[c.p] != 0x42:
                raise _LaneStat(LANE_UNKNOWN)
            voff, vlen = _lane_bytes(c)
        else:
            _lane_canon(c, 1)
    if not have_key:
        raise _LaneStat(LANE_BAD)
    if emit:
        slot = st.intern(ns_off, ns_len, key_off, key_len)
        st.writes.append((tx, slot, delete, voff, vlen))


def _lane_ns(c: _LaneCur, st: _LaneState, emit: bool, tx: int) -> bool:
    """One NsRwSet dict; True when a non-empty range_queries list was
    seen (caller escalates the whole envelope to RANGE)."""
    n = _lane_dict_enter(c)
    prev = [None]
    ns_off = ns_len = 0
    have_ns = have_reads = have_writes = saw_range = False
    for _ in range(n):
        k = _lane_dict_key(c, prev)
        if k == b"namespace":
            if c.p >= c.end or c.b[c.p] != 0x53:
                raise _LaneStat(LANE_UNKNOWN)
            ns_off, ns_len = _lane_str(c)
            have_ns = True
        elif k == b"reads":
            if not have_ns:
                raise _LaneStat(LANE_BAD)
            if c.p >= c.end or c.b[c.p] != 0x4C:
                raise _LaneStat(LANE_UNKNOWN)
            c.p += 1
            for _ in range(_lane_u32(c)):
                _lane_read(c, st, emit, tx, ns_off, ns_len)
            have_reads = True
        elif k == b"writes":
            if not have_ns:
                raise _LaneStat(LANE_BAD)
            if c.p >= c.end or c.b[c.p] != 0x4C:
                raise _LaneStat(LANE_UNKNOWN)
            c.p += 1
            for _ in range(_lane_u32(c)):
                _lane_write(c, st, emit, tx, ns_off, ns_len)
            have_writes = True
        elif k == b"range_queries":
            if c.p >= c.end or c.b[c.p] != 0x4C:
                raise _LaneStat(LANE_UNKNOWN)
            peek = _LaneCur(c.b, c.p + 1, c.end)
            qn = _lane_u32(peek)
            _lane_canon(c, 1)
            if qn > 0:
                saw_range = True
        else:
            _lane_canon(c, 1)
    if not (have_ns and have_reads and have_writes):
        raise _LaneStat(LANE_BAD)
    return saw_range


def _lane_rwset(c: _LaneCur, st: _LaneState, emit: bool, tx: int) -> None:
    n = _lane_dict_enter(c)
    prev = [None]
    saw_range = False
    have_ns_list = False
    for _ in range(n):
        k = _lane_dict_key(c, prev)
        if k == b"ns":
            if c.p >= c.end or c.b[c.p] != 0x4C:
                raise _LaneStat(LANE_UNKNOWN)
            c.p += 1
            for _ in range(_lane_u32(c)):
                if _lane_ns(c, st, emit, tx):
                    saw_range = True
            have_ns_list = True
        else:
            _lane_canon(c, 1)
    if not have_ns_list:
        raise _LaneStat(LANE_BAD)
    if saw_range:
        raise _LaneStat(LANE_RANGE)


def _lane_endorsement(c: _LaneCur) -> None:
    n = _lane_dict_enter(c)
    prev = [None]
    have_e = have_s = False
    for _ in range(n):
        k = _lane_dict_key(c, prev)
        if k == b"endorser":
            have_e = True
        elif k == b"signature":
            have_s = True
        _lane_canon(c, 1)
    if not (have_e and have_s):
        raise _LaneStat(LANE_BAD)


def _lane_cc_action(c: _LaneCur, st: _LaneState, emit: bool,
                    tx: int) -> None:
    n = _lane_dict_enter(c)
    prev = [None]
    have_id = have_ver = have_rw = False
    for _ in range(n):
        k = _lane_dict_key(c, prev)
        if k == b"chaincode_id":
            have_id = True
            _lane_canon(c, 1)
        elif k == b"chaincode_version":
            have_ver = True
            _lane_canon(c, 1)
        elif k == b"rwset":
            _lane_rwset(c, st, emit, tx)
            have_rw = True
        else:
            _lane_canon(c, 1)
    if not (have_id and have_ver and have_rw):
        raise _LaneStat(LANE_BAD)


def _lane_action(c: _LaneCur, st: _LaneState, emit: bool, tx: int) -> None:
    n = _lane_dict_enter(c)
    prev = [None]
    have_ph = have_act = have_end = False
    for _ in range(n):
        k = _lane_dict_key(c, prev)
        if k == b"action":
            _lane_cc_action(c, st, emit, tx)
            have_act = True
        elif k == b"endorsements":
            if c.p >= c.end or c.b[c.p] != 0x4C:
                raise _LaneStat(LANE_UNKNOWN)
            c.p += 1
            for _ in range(_lane_u32(c)):
                _lane_endorsement(c)
            have_end = True
        elif k == b"proposal_hash":
            have_ph = True
            _lane_canon(c, 1)
        else:
            _lane_canon(c, 1)
    if not (have_ph and have_act and have_end):
        raise _LaneStat(LANE_BAD)


def _lane_env(base: bytes, off: int, ln: int, tx: int, st: _LaneState):
    """walk_env mirror: (txid_off, txid_len) of an OK endorser tx, or a
    _LaneStat with the terminal status."""
    c = _LaneCur(base, off, off + ln)
    payload_span = None
    have_sig = False
    n = _lane_dict_enter(c)
    prev = [None]
    for _ in range(n):
        k = _lane_dict_key(c, prev)
        vstart = c.p
        _lane_canon(c, 1)
        if k == b"payload":
            payload_span = (vstart, c.p)
        elif k == b"signature":
            have_sig = True
    if c.p != c.end:
        raise _LaneStat(LANE_BAD)              # trailing bytes
    if payload_span is None or not have_sig:
        raise _LaneStat(LANE_BAD)              # KeyError
    if base[payload_span[0]] != 0x42:
        raise _LaneStat(LANE_UNKNOWN)          # decode(non-bytes)
    pc = _LaneCur(base, payload_span[0], payload_span[1])
    poff, pn = _lane_bytes(pc)

    pc = _LaneCur(base, poff, poff + pn)
    header_v = _lane_dict_find(pc, b"header")
    if header_v is None or pc.p != pc.end:
        raise _LaneStat(LANE_BAD)
    ch_v = _lane_dict_find(_LaneCur(base, *header_v), b"channel_header")
    if ch_v is None:
        raise _LaneStat(LANE_BAD)
    type_v = _lane_dict_find(_LaneCur(base, *ch_v), b"type")
    if type_v is None:
        raise _LaneStat(LANE_BAD)
    tv = _LaneCur(base, *type_v)
    if tv.p >= tv.end or base[tv.p] != 0x53:
        raise _LaneStat(LANE_SKIP)             # non-str != TX_ENDORSER
    soff, sn = _lane_str(tv)
    if base[soff:soff + sn] != b"endorser_transaction":
        raise _LaneStat(LANE_SKIP)

    pc = _LaneCur(base, poff, poff + pn)
    data_v = _lane_dict_find(pc, b"data")
    if data_v is None:
        raise _LaneStat(LANE_BAD)
    actions_v = _lane_dict_find(_LaneCur(base, *data_v), b"actions")
    if actions_v is None:
        raise _LaneStat(LANE_BAD)
    av = _LaneCur(base, *actions_v)
    if av.p >= av.end or base[av.p] != 0x4C:
        raise _LaneStat(LANE_UNKNOWN)
    av.p += 1
    an = _lane_u32(av)
    if an == 0:
        raise _LaneStat(LANE_SKIP)             # `not tx.actions` -> None,
                                               # BEFORE ch["txid"] is read
    for i in range(an):
        _lane_action(av, st, i == 0, tx)

    txid_v = _lane_dict_find(_LaneCur(base, *ch_v), b"txid")
    if txid_v is None:
        raise _LaneStat(LANE_BAD)
    xv = _LaneCur(base, *txid_v)
    if xv.p >= xv.end or base[xv.p] != 0x53:
        raise _LaneStat(LANE_UNKNOWN)
    txid = _lane_str(xv)
    # OK also promises the txid readers that Envelope.header() succeeds
    try:
        sh_v = _lane_dict_find(_LaneCur(base, *header_v),
                               b"signature_header")
        strict = (sh_v is not None
                  and None not in (
                      _lane_dict_find(_LaneCur(base, *ch_v), b"channel_id"),
                      _lane_dict_find(_LaneCur(base, *sh_v), b"creator"),
                      _lane_dict_find(_LaneCur(base, *sh_v), b"nonce")))
    except _LaneStat:
        strict = False                         # signature_header no dict
    if not strict:
        raise _LaneStat(LANE_UNKNOWN)
    return txid


def rwset_lanes_py(base: _Raw, spans) -> Optional[tuple]:
    """Mirror of _fastparse.rwset_lanes (same result tuple, same arena
    bytes — see the lane-layout comment above rwset_lanes())."""
    base = bytes(base)
    sp = bytes(spans)
    if len(sp) % 16:
        return None
    blen = len(base)
    n_tx = len(sp) // 16
    st = _LaneState(base)
    txs = []
    for t in range(n_tx):
        off, ln = struct.unpack_from("QQ", sp, 16 * t)
        if off > blen or ln > blen - off:
            return None
        rd_mark, wr_mark = len(st.reads), len(st.writes)
        try:
            txid_off, txid_len = _lane_env(base, off, ln, t, st)
            stat = LANE_OK
        except _LaneStat as e:
            del st.reads[rd_mark:]             # drop partial lanes;
            del st.writes[wr_mark:]            # interned keys stay (C
            stat, txid_off, txid_len = e.st, 0, 0  # parity)
        except _LaneColl:
            return (1, 0, 0, 0, 0, None)
        txs.append((stat, txid_off, txid_len))
    cells: list = []
    for rec in txs:
        cells.extend(rec)
    for rec in st.reads:
        cells.extend(rec)
    for rec in st.writes:
        cells.extend(rec)
    for rec in st.keys:
        cells.extend(rec)
    arena = struct.pack(f"{len(cells)}Q", *cells)
    return (0, n_tx, len(st.keys), len(st.reads), len(st.writes), arena)
