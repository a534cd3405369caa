"""Block validation orchestrator — the verify-then-gate hot path.

Reference flow being restructured (SURVEY.md §3.2, §7):
  core/committer/txvalidator/v20/validator.go:181-266 Validate(block):
    per-tx goroutines (:194-209) each doing
      ValidateTransaction (core/common/validation/msgvalidation.go:248)
        checkSignatureFromCreator (:26-56)          <- 1 ECDSA verify
      Dispatcher.Dispatch (plugindispatcher/dispatcher.go:102)
        builtin v20 Validate (validation_logic.go:185)
          policy EvaluateSignedData                 <- N ECDSA verifies
    then txflags bitmap assembly (:214-260).

TPU-native restructure, in three passes over the whole block:
  PASS 1 (host, no crypto):  structural validation, duplicate-txid marking,
    and *collection* of every SignedData the reference would have verified
    — creator sigs and endorsement sets — deduplicated globally by
    (scheme, pubkey, payload, signature) since Verify is a pure function.
  DISPATCH (device):         ONE batched provider.batch_verify for the
    entire block (p256 + ed25519 sub-batches, mesh-sharded).
  PASS 2 (host, no crypto):  gate on the verdict bitmap — creator-sig
    check consumes its bit; policy evaluation re-runs the exact cauthdsl
    greedy semantics over identities whose bits are set (a bad endorsement
    only weakens the policy, it never fails the block: policy.go:390-393).

MVCC runs afterwards in the ledger (kvledger.commit), consuming the flags
this produces — identical decision order to the reference.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from fabric_tpu.bccsp import SCHEME_ED25519, SCHEME_P256, VerifyItem
from fabric_tpu.bccsp.provider import dispatch_site
from fabric_tpu.msp import Identity
from fabric_tpu.ops_plane import tracing
from fabric_tpu.policy import PolicyEvaluator, SignaturePolicy, SignedData
from fabric_tpu.privdata.collection import chaincode_of
from fabric_tpu.protocol import Block, wire
from fabric_tpu.protocol.txflags import TxFlags, ValidationCode
from fabric_tpu.protocol.types import META_TXFLAGS
from fabric_tpu.protocol.wire import n_txs
from fabric_tpu.verify_plane.cache import (all_miss,
                                           note_device_verifications)

logger = logging.getLogger("fabric_tpu.committer")

# C pass-1 walker (fabric_tpu/native/fastcollect.c): decodes envelopes,
# checks structure/txid, and splices the signed byte spans without
# materializing Python object trees — the single-core answer to the
# reference's per-tx goroutine fan-out (validator.go:194-209).  The
# pure-Python path below stays as the no-compiler fallback and the
# differential oracle (tests/test_committer.py).
#
# Two tails consume the walk and give the same flags: the DEEP tail
# (`_begin_deep` / `_finish_deep`: digest -> assemble -> gate, all C, no
# per-tx Python) and the CLASSIC tail (`_collect_tx_fast` / `_gate_tx`,
# once a transaction), which alone knows key-level endorsement.  Which
# one a block takes is `TxValidator._tail_of`'s rule, read off the state
# and the block — not off how the validator was built.
try:
    from fabric_tpu.native import load as _load_native
    _fastcollect = _load_native("_fastcollect")
except Exception:               # pragma: no cover - broken toolchain
    _fastcollect = None

# fastcollect error-code -> ValidationCode (must match fastcollect.c)
_FC_CODES = {
    1: ValidationCode.NIL_ENVELOPE,
    2: ValidationCode.BAD_PAYLOAD,
    3: ValidationCode.TARGET_CHAIN_NOT_FOUND,
    4: ValidationCode.BAD_PROPOSAL_TXID,
    5: ValidationCode.UNKNOWN_TX_TYPE,
    6: ValidationCode.NIL_TXACTION,
}


class PolicyRegistry:
    """namespace -> endorsement policy (the _lifecycle/plugindispatcher
    lookup surface, dispatcher.go:102).  Falls back to a default policy,
    like a chaincode with no explicit endorsement policy falls back to
    the channel's majority-endorsement default.  A collection's hashed
    namespace `ns$collection` answers with the collection's own policy
    where one was set for it, else with its chaincode's (v2.0: a
    collection-level policy takes the chaincode's place for the
    collection's keys)."""

    def __init__(self, default: Optional[SignaturePolicy] = None):
        self._policies: Dict[str, SignaturePolicy] = {}
        self._default = default

    def set_policy(self, namespace: str, policy: SignaturePolicy) -> None:
        self._policies[namespace] = policy

    def policy_for(self, namespace: str) -> Optional[SignaturePolicy]:
        pol = self._policies.get(namespace)
        if pol is None:
            pol = self._policies.get(chaincode_of(namespace), self._default)
        return pol


@dataclass(slots=True)
class _TxWork:
    """Collected verification workload for one transaction."""
    tx_num: int
    creator_key: Optional[Tuple] = None          # dedup key of creator item
    creator_identity: Optional[Identity] = None
    # per-namespace: (policy, [(dedup_key, identity), ...])
    namespaces: List[Tuple[str, SignaturePolicy, List[Tuple[Tuple, Identity]]]] = \
        field(default_factory=list)
    # SBE: base_ns -> written keys; and this tx's metadata updates
    written_keys: Dict[str, Tuple[str, ...]] = field(default_factory=dict)
    meta_writes: List[Tuple] = field(default_factory=list)
    # (ns, key) of the written keys it deletes: their parameters go too
    deletes: List[Tuple[str, str]] = field(default_factory=list)


def _interval_union(ivals):
    """Merge (start, end) intervals into a sorted disjoint union."""
    out: List[List[float]] = []
    for a, b in sorted(ivals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _intersection_s(u1, u2) -> float:
    i = j = 0
    s = 0.0
    while i < len(u1) and j < len(u2):
        a = max(u1[i][0], u2[j][0])
        b = min(u1[i][1], u2[j][1])
        if b > a:
            s += b - a
        if u1[i][1] < u2[j][1]:
            i += 1
        else:
            j += 1
    return s


class _PipelineEconomics:
    """Live collect-under-verify overlap over a rolling block window.

    Tracked on the node itself so the SLO plane can watch the overlap
    floor from the live process (gauge
    `pipeline_collect_under_verify_frac`; no benchmark cell reads it).
    Collect intervals come from validate_begin, verify
    intervals span device enqueue -> resolve return (the
    bccsp.batch_verify window).  All timestamps share perf_counter."""

    WINDOW = 64            # blocks of history

    def __init__(self):
        self._lock = threading.Lock()
        from collections import deque
        self._collect = deque(maxlen=self.WINDOW)
        self._verify = deque(maxlen=self.WINDOW)

    def note_collect(self, a: float, b: float) -> None:
        if b > a:
            with self._lock:
                self._collect.append((a, b))

    def note_verify(self, a: float, b: float) -> None:
        if b > a:
            with self._lock:
                self._verify.append((a, b))

    def frac(self) -> float:
        with self._lock:
            collect = list(self._collect)
            verify = list(self._verify)
        u_c = _interval_union(collect)
        total = sum(b - a for a, b in u_c)
        if total <= 0.0:
            return 0.0
        return min(1.0, _intersection_s(u_c, _interval_union(verify)) / total)


@dataclass
class ValidationResult:
    flags: TxFlags
    collect_s: float
    dispatch_s: float
    gate_s: float
    n_items: int
    n_unique_items: int

    @property
    def total_s(self) -> float:
        return self.collect_s + self.dispatch_s + self.gate_s


# How many of a block's unique verify items `TxValidator._dispatch` asks
# the verdict cache for before it asks for the rest.  A verdict is in the
# cache only if an earlier verify site of THIS node (gateway ingress,
# speculative verifier, a trusted attestation) saw the item; a replayed
# block passed through none of them, and then a lookup (digest, lock,
# dictionary) and a store (digest, two MACs, an eviction) per item is
# 0.46 s of a 10,000-tx block for nothing (PERF.md §6, PR 31).  256 is
# large enough that a block with 2% of its items cached escapes the
# probe with probability < 0.6% (0.98**256), small enough that the probe
# is ~1 ms, and above every block a served peer cuts at the deployments'
# rates (~92 items), which therefore take the whole path unprobed.  A
# wrong guess costs device time, never a verdict.  A constant: the rule
# reads the block's own items and keeps nothing between blocks.
PROBE = 256
_PROBE_RUN = 4      # consecutive items a run: a transaction's creator
                    # and endorsement signatures sit side by side


def probe_positions(n: int) -> List[int]:
    """The PROBE positions `_dispatch` looks up first in a block of
    `n` > PROBE unique items: PROBE / 4 runs of 4 consecutive items,
    the runs evenly spaced from the first item to the last.  A function
    of `n` only; ascending, distinct, and not aliased with a
    transaction's period in the dispatch order."""
    runs = PROBE // _PROBE_RUN
    last = n - _PROBE_RUN
    return [r * last // (runs - 1) + j
            for r in range(runs) for j in range(_PROBE_RUN)]


class TxValidator:
    """v20 TxValidator equivalent bound to one channel."""

    def __init__(self, channel_id: str, msps: Dict[str, object], provider,
                 policies: PolicyRegistry,
                 ledger_has_txid=None, bundle_source=None,
                 sbe_lookup=None, sbe_state=None,
                 validation_plugin: str = "DefaultValidation",
                 provider_source=None, verify_cache=None):
        self.channel_id = channel_id
        self._static_msps = msps
        self._provider = provider
        # verify-once plane (verify_plane.VerdictCache) — None keeps the
        # classic always-verify behaviour.  When wired, each block's
        # dispatch batch is partitioned against the cache: MAC-verified
        # hits skip the device, misses verify and backfill.  Identity
        # validity and policy evaluation are NEVER cached — the gate
        # always runs live; only the pure signature bit is reused.
        self.verify_cache = verify_cache
        # per-channel device placement hook:
        # provider_source(channel_id, demand) -> Provider | None.  When
        # wired (bccsp_placement), each dispatch re-resolves the provider
        # and reports its batch size so the placement scheduler can
        # resize this channel's device span from observed queue depth.
        self.provider_source = provider_source
        self.policies = policies
        self.bundle_source = bundle_source
        # pluggable commit-time decision (handlers/library/registry.go;
        # the builtin is the v20 policy gate)
        from fabric_tpu.handlers import default_registry
        self.validation_plugin = default_registry.validation(
            validation_plugin)
        # key-level endorsement: committed validation-parameter lookup
        # ((ns, key) -> policy bytes), usually sbe.statedb_lookup(statedb)
        self.sbe_lookup = sbe_lookup
        # ... and the question `_tail_of` asks the same state before
        # every block: () -> (savepoint, live validation parameters),
        # usually `statedb.meta_keys`.  A lookup with no such question
        # beside it may answer for any key: every block stays classic.
        self.sbe_state = sbe_state
        # newest block this validator saw write a `#meta` namespace and
        # that the state's savepoint does not cover yet: until it does,
        # the state's count cannot say "no parameter" for that block
        self._meta_block: Optional[int] = None
        # blkstorage-backed duplicate-txid oracle (validator.go dedup vs
        # ledger).  The module-level sentinel (not a fresh lambda) lets
        # the deep tail detect "unwired" and skip a per-tx Python call.
        self.ledger_has_txid = ledger_has_txid or _false_oracle
        # (block_number, txid-map) of blocks begun whose txids the
        # ledger oracle cannot see yet: a pipelined driver
        # (validate_begin N+1 before block N commits) must still flag a
        # txid duplicated across the in-flight window.  Entries are
        # pruned at the NEXT begin, once the ledger can see them — not
        # at validate_finish, which returns before the commit and would
        # reopen the window.  Maps for block numbers >= the incoming
        # block are also pruned: a replay of the same or an earlier
        # block (catch-up, crash recovery) is not a duplicate of itself.
        self._inflight_txids: List[Tuple[int, Dict[str, int]]] = []
        # live pipeline-economics window (overlap gauge for the SLO plane)
        self._econ = _PipelineEconomics()

    @property
    def provider(self):
        """The channel's current verify provider: placement-resolved
        when a provider_source is wired, else the static one."""
        return self._resolve_provider()

    @provider.setter
    def provider(self, p):
        self._provider = p

    def _resolve_provider(self, demand=None):
        if self.provider_source is not None:
            try:
                p = self.provider_source(self.channel_id, demand)
            except Exception:
                logger.exception("placement provider_source failed; "
                                 "using static provider")
                p = None
            if p is not None:
                return p
        return self._provider

    @property
    def msps(self):
        """MSP set for the block being validated.  Snapshotted once per
        validate() call: all txs of one block must be judged under ONE
        config or peers could produce divergent validity bitmaps when a
        bundle swap races a long validation (the reference pins the bundle
        per block too, core/peer/peer.go:332-371)."""
        snap = getattr(self, "_msps_snapshot", None)
        if snap is not None:
            return snap
        if self.bundle_source is not None:
            return self.bundle_source.current().msps
        return self._static_msps

    @property
    def evaluator(self):
        return PolicyEvaluator(self.msps, self.provider)

    # -- pass 1: structural + collect ---------------------------------------

    def _deserialize(self, ident_bytes: bytes) -> Optional[Identity]:
        from fabric_tpu.msp import deserialize_from_msps
        return deserialize_from_msps(self.msps, ident_bytes)

    def _resolve_creator(self, ident_bytes: bytes, acct: "_Identities"):
        """Creator memo value (`_memo_ent`), or None for identities
        the MSP rejects (deserialize + chain-validate —
        the (0, creator) memo of the Python tail, resolved once per
        unique creator on the deep path).  Booked in the block's
        identity account: one more creator seen first, the seconds, and
        why the MSP refused it where it did."""
        t0 = time.perf_counter()
        creator = self._deserialize(ident_bytes)
        reason = ("undecodable" if creator is None
                  else _msp_rejects(self.msps, creator))
        acct.first += 1
        if reason is not None:
            acct.reject(reason)
        ent = None if reason is not None else _memo_ent(creator)
        acct.add(t0)
        return ent

    def _resolve_creators(self, creators: list, acct: "_Identities"):
        """The deep tail's `_resolve_creator` for a block's unique
        creators at once: each deserialised, then one `validate_many` an
        MSP (an MSP without that verb, or whose `validate` was replaced
        on the instance, is asked one identity at a time) -> (c_ents,
        deferred).

        Where a block brings a CA enough unseen creators for the
        provider's rows lane (`CachedMSP.validate_many`: the count is
        the provider's own `fast_key_threshold`, read off the block's
        cache misses; a provider without one has no such lane and
        nothing is deferred), the CA's signatures over their
        certificates are not checked here: they go to the provider as
        ONE `batch_verify_async` of their own, enqueued now, so that
        the device works under `assemble` and the hand-over.  Not rows
        of the block's table: three CA keys' six rows more would lift a
        500-tx block's twelve past `rows@16` into `rows@64`, four times
        the grid for 12% more signatures and a program nobody warmed; a
        second `rows@16` costs ~10 ms of an idle chip and compiles
        nothing.  Not through the verdict cache either: the MSP's LRU
        *is* the identity cache.  A deferred creator gets its memo
        entry as a sound one does; `_settle_creators` takes the refused
        ones out before the gate.  `deferred`: None, or what that needs."""
        t0 = time.perf_counter()
        msps = self.msps
        ents: list = [None] * len(creators)
        by_msp: dict = {}          # mspid -> (creator slots, identities)
        for slot, raw in enumerate(creators):
            ident = self._deserialize(raw)
            if ident is None:
                acct.reject("undecodable")
                continue
            ents[slot] = _memo_ent(ident)
            slots, idents = by_msp.setdefault(ident.mspid, ([], []))
            slots.append(slot)
            idents.append(ident)
        t_parsed = time.perf_counter()
        provider = self.provider
        min_batch = getattr(provider, "fast_key_threshold", None)
        chains, items = [], []
        for mspid, (slots, idents) in by_msp.items():
            msp = msps[mspid]
            many = _many_verb(msp)
            if many is None:
                errors = [_msp_error(msp, ident) for ident in idents]
                pending = None
            else:
                errors, pending = many(idents, min_batch)
            for slot, err in zip(slots, errors):
                if err is not None:
                    ents[slot] = None
                    acct.reject(getattr(err, "reason", "untrusted"))
            if pending is not None:
                chains.append((pending, slots))
                items += pending.items
        acct.first += len(creators)
        deferred = None
        if items:
            t_checked = time.perf_counter()
            with dispatch_site("validator"):
                resolve = provider.batch_verify_async(items)
            deferred = {"chains": chains,
                        "wait": _resolve_eagerly(resolve, self._econ),
                        "parent": tracing.tracer.current_context()}
            # the span's parts: certificates parsed, chains' host checks
            # and items, the pack and enqueue of the items
            acct.attrs = {
                "deferred": len(items),
                "parse_ms": round((t_parsed - t0) * 1e3, 3),
                "chains_ms": round((t_checked - t_parsed) * 1e3, 3),
                "enqueue_ms": round(
                    (time.perf_counter() - t_checked) * 1e3, 3)}
        acct.add(t0)
        return ents, deferred

    def _settle_creators(self, state: dict) -> dict:
        """The verdicts of a block's deferred certificate signatures,
        before the gate: every creator's into its MSP's cache and
        account, and every transaction of a creator whose certificate
        the CA did not sign stamped BAD_CREATOR_SIGNATURE with its plan
        dropped — the code `assemble` gives a creator the MSP refused
        on the host, so the flags are the host branch's bit for bit.
        Then the block's identity account is booked, which waited for
        these.  -> the wait for the verdicts and the settling, in ms,
        for the caller's span."""
        t0 = time.perf_counter()
        deferred = state["deferred"]
        thread, holder = deferred["wait"]
        thread.join()
        if "err" in holder:
            raise holder["err"]
        t_ready = time.perf_counter()
        verdicts = holder["out"]
        acct = state["identities"]
        refused = set()
        lo = 0
        for pending, slots in deferred["chains"]:
            hi = lo + len(pending.items)
            for k, err in pending.settle(verdicts[lo:hi]):
                refused.add(slots[k])
                acct.reject(err.reason)
            lo = hi
        if refused:
            codes = state["codes"]
            bad = {w[0] for w in state["works"] if w[2] in refused}
            for tx in bad:
                codes[tx] = int(ValidationCode.BAD_CREATOR_SIGNATURE)
            state["plans"] = [p for p in state["plans"] if p[0] not in bad]
        self._note_identities(int(state["block"].header.number), acct,
                              parent=deferred["parent"])
        return {"certs_wait_ms": round((t_ready - t0) * 1e3, 3),
                "settle_ms": round((time.perf_counter() - t_ready) * 1e3, 3)}

    def _resolve_endorser(self, ident_bytes: bytes, acct: "_Identities"):
        """Endorser memo value — deserialize only, NO chain validation
        (the (1, endorser) memo: an unrecognized endorser merely weakens
        the policy, policy.go:390-393)."""
        t0 = time.perf_counter()
        ident = self._deserialize(ident_bytes)
        acct.endorsers += 1
        acct.add(t0)
        return None if ident is None else _memo_ent(ident)

    def _note_identities(self, num: int, acct: "_Identities",
                         parent=None) -> None:
        """One block's identity resolution, from the account's plain
        numbers: the creators into `validator_creators_total` by whether
        the block's memo knew them, the refused ones into
        `validator_creator_rejected_total` by the MSP's reason, and the
        span `validator.identities` — on the deep tail the stretch
        between the C walk and `assemble`; on the classic tail, where a
        resolution happens at the first transaction that needs it, from
        the first one's start for the sum of them all.  A block that
        deferred certificate signatures is booked when their verdicts
        are in (`_settle_creators`, under the `parent` context collect
        ran in) and says how many in `deferred`, with the span's parts
        beside it (`parse_ms`, `chains_ms`, `enqueue_ms`)."""
        try:
            from fabric_tpu.ops_plane import registry
            ch = self.channel_id
            seen = registry.counter(
                "validator_creators_total",
                "transactions whose creator the validator resolved, by "
                "whether the block's memo knew the identity: first "
                "(deserialised and chain-validated through the MSP) or "
                "again (answered from the block's memo)")
            seen.add(acct.first, channel=ch, seen="first")
            seen.add(acct.again, channel=ch, seen="again")
            if acct.rejected:
                refused = registry.counter(
                    "validator_creator_rejected_total",
                    "unique creators of a block the MSP refused, by why: "
                    "undecodable (or of no known MSP), revoked, "
                    "untrusted, expired")
                for reason, n in acct.rejected.items():
                    refused.add(n, channel=ch, reason=reason)
        except Exception:
            pass
        if acct.start is not None:
            attrs = {"block": int(num), "unique_creators": acct.first,
                     "unique_endorsers": acct.endorsers,
                     "rejected": sum(acct.rejected.values()),
                     **acct.attrs}
            tracing.tracer.record_span(
                "validator.identities", acct.start,
                acct.start + acct.seconds, attributes=attrs, parent=parent)

    def _collect_tx_fast(self, tx_num: int, rec, flags: TxFlags,
                         seen_txids: Dict[str, int],
                         items: Dict[VerifyItem, None],
                         memo: dict, acct: "_Identities", n_txs: int = 1,
                         has_txid=None) -> Optional[_TxWork]:
        """Pass-1 tail for one tx whose structural walk ran in either
        front walker — C (native/fastcollect.c) or the Python mirror
        (committer/collect_py.py).  One consumer tail for both walkers
        is the invariant that keeps C-enabled and no-compiler peers on
        identical validity bitmaps; the walkers themselves are tested
        differentially.

        This loop runs ~10k times per block on one core (the slot of
        the reference's per-tx goroutine fan-out), so it is written for
        bytecode economy: VerifyItems are their own dedup keys
        (NamedTuple), per-identity facts are memoized (`_memo_ent`),
        and attribute lookups are hoisted."""
        if isinstance(rec, int):
            # pre-registration structural failure: the txid never
            # entered seen_txids on the Python path either
            flags.set(tx_num, _FC_CODES[rec])
            return None
        if len(rec) == 2:
            # post-registration failure (unknown type / nil action /
            # malformed body AFTER a valid txid): the Python path
            # registers the txid BEFORE flagging, so later duplicates
            # still read DUPLICATE_TXID — bitmaps must not diverge
            # between the C and no-compiler paths
            code, txid = rec
            if txid in seen_txids or (has_txid or self.ledger_has_txid)(txid):
                flags.set(tx_num, ValidationCode.DUPLICATE_TXID)
                return None
            seen_txids[txid] = tx_num
            flags.set(tx_num, _FC_CODES[code])
            return None
        txtype, txid, creator_bytes, payload, pdigest, signature, actions = rec
        if txid in seen_txids or (has_txid or self.ledger_has_txid)(txid):
            flags.set(tx_num, ValidationCode.DUPLICATE_TXID)
            return None
        seen_txids[txid] = tx_num

        if txtype == 0 and n_txs != 1:
            flags.set(tx_num, ValidationCode.INVALID_CONFIG_TRANSACTION)
            return None

        # creator identity: deserialize + chain-validate, memoized per
        # block (the msp/cache role for this hot loop).  memo value:
        # `_memo_ent`'s, or None for invalid.
        ckey = (0, creator_bytes)
        ent = memo.get(ckey, memo)
        if ent is memo:
            ent = memo[ckey] = self._resolve_creator(creator_bytes, acct)
        else:
            acct.again += 1
        if ent is None:
            flags.set(tx_num, ValidationCode.BAD_CREATOR_SIGNATURE)
            return None
        creator, pub_wire, scheme = ent
        if scheme == SCHEME_P256:
            item = VerifyItem(SCHEME_P256, pub_wire, signature, pdigest)
        elif pub_wire is not None:       # ed25519 signs the message
            item = VerifyItem(scheme, pub_wire, signature, payload)
        else:                            # idemix: its own item shape
            item = creator.verify_item(payload, signature)
        if item not in items:
            items[item] = None
        work = _TxWork(tx_num)
        work.creator_key = item
        work.creator_identity = creator

        if txtype == 0:
            return work

        policy_for = self.policies.policy_for
        for cc_id, endorsed, endorsements, ns_writes, meta in actions:
            namespaces = {cc_id}
            for ns, keys, deleted in ns_writes:
                namespaces.add(ns)
                prev = work.written_keys.get(ns, ())
                work.written_keys[ns] = prev + tuple(keys)
                if deleted:
                    work.deletes.extend((ns, k) for k in deleted)
            for base, k, v in meta:
                namespaces.add(base)
                work.meta_writes.append((base, k, v))
            sigset: List[Tuple[VerifyItem, Identity]] = []
            seen_idents = set()
            for endorser, esig, edigest in endorsements:
                if endorser in seen_idents:   # policy.go:385-387 dedup
                    continue
                seen_idents.add(endorser)
                ekey = (1, endorser)
                ent = memo.get(ekey, memo)
                if ent is memo:
                    ent = memo[ekey] = self._resolve_endorser(endorser,
                                                              acct)
                if ent is None:
                    continue
                ident, e_wire, scheme = ent
                if scheme == SCHEME_P256:
                    it = VerifyItem(SCHEME_P256, e_wire, esig, edigest)
                elif e_wire is not None:
                    it = VerifyItem(scheme, e_wire, esig, endorsed + endorser)
                else:
                    it = ident.verify_item(endorsed + endorser, esig)
                if it not in items:
                    items[it] = None
                sigset.append((it, ident))
            for ns in sorted(namespaces):
                pol = policy_for(ns)
                if pol is None:
                    flags.set(tx_num, ValidationCode.INVALID_CHAINCODE)
                    return None
                work.namespaces.append((ns, pol, sigset))
        return work

    # -- pass 2: gate + evaluate --------------------------------------------

    def _memoized_plugin(self, eval_cache: dict):
        """Per-block memoizing wrapper around the validation plugin.

        Policy evaluation is a pure function of (plugin, policy,
        ordered valid-identity list).  Identities are memoized
        per-block objects and every policy the gate sees is interned
        for the block (PolicyRegistry entries live on the validator;
        SbeOverlay interns decoded key-level policies per block —
        id()-keying a FRESH decode would let a freed policy's reused
        address answer for a different policy), so id() keys are stable
        and the common case — every tx of a chaincode under the same
        endorser set — evaluates ONCE per block instead of ~10k times.
        """
        raw_plugin = self.validation_plugin

        def plugin(pol, idents, ev, _c=eval_cache):
            key = (id(pol), tuple(map(id, idents)))
            r = _c.get(key)
            if r is None:
                r = _c[key] = raw_plugin(pol, idents, ev)
            return r

        return plugin

    def _gate_tx(self, work: _TxWork, flags: TxFlags,
                 verdict: Dict[Tuple, bool], sbe_overlay=None,
                 plugin=None) -> None:
        if not verdict.get(work.creator_key, False):
            flags.set(work.tx_num, ValidationCode.BAD_CREATOR_SIGNATURE)
            return
        evaluator = self.evaluator
        if plugin is None:
            plugin = self.validation_plugin

        for ns, pol, sigset in work.namespaces:
            valid_idents = [ident for key, ident in sigset
                            if verdict.get(key, False)]
            # key-level endorsement (validator_keylevel.go:244): a key's
            # validation parameter REPLACES the chaincode policy for that
            # key; keys without one fall back to the namespace policy.
            # Metadata UPDATES to a key are themselves gated by the key's
            # CURRENT policy (or the cc policy when none is set).
            base_written = work.written_keys.get(ns, ())
            meta_keys = [k for (b, k, _) in work.meta_writes if b == ns]
            if sbe_overlay is None or (not base_written and not meta_keys):
                need_ns_policy = True
            else:
                need_ns_policy = False
                for key in base_written:
                    kpol = sbe_overlay.policy_for(ns, key)
                    if kpol is None:
                        need_ns_policy = True
                        continue
                    if not plugin(kpol, valid_idents, evaluator):
                        sbe_overlay.failures += 1
                        flags.set(work.tx_num,
                                  ValidationCode.ENDORSEMENT_POLICY_FAILURE)
                        return
                for key in meta_keys:
                    kpol = sbe_overlay.policy_for(ns, key)
                    if not plugin(kpol or pol, valid_idents, evaluator):
                        sbe_overlay.failures += kpol is not None
                        flags.set(work.tx_num,
                                  ValidationCode.ENDORSEMENT_POLICY_FAILURE)
                        return
            if need_ns_policy and not plugin(pol, valid_idents, evaluator):
                flags.set(work.tx_num, ValidationCode.ENDORSEMENT_POLICY_FAILURE)
                return
        flags.set(work.tx_num, ValidationCode.VALID)
        if sbe_overlay is not None and (work.meta_writes or work.deletes):
            # a VALID tx's metadata updates take effect for later txs in
            # this block (the reference's intra-block dependency
            # ordering), and a key it deletes loses its parameter
            sbe_overlay.apply_valid_tx(work.meta_writes, work.deletes)

    # -- the block entry point (validator.go:181) ---------------------------

    def validate(self, block: Block) -> ValidationResult:
        return self.validate_finish(self.validate_begin(block))

    def validate_begin(self, block: Block) -> dict:
        """Pass 1 + async device enqueue for one block; returns the
        in-flight state for validate_finish.

        The split would let a block-stream driver overlap host
        collection of block N+1 with device verification of block N
        (the reference has no analogue — its validator is synchronous
        per block).  No such driver exists: outside `tests/` the two
        halves have one caller, `validate` above, which runs them back
        to back (ROADMAP S1, D1)."""
        self._msps_snapshot = (self.bundle_source.current().msps
                               if self.bundle_source is not None else None)
        if self.verify_cache is not None and self.bundle_source is not None:
            # pin THIS channel's cache epoch to its config sequence: a
            # config update (new CRL, rotated CA, policy change)
            # invalidates every verdict minted under the previous
            # sequence of this channel — the cache is shared per node,
            # so other channels' entries must not flap with ours
            try:
                self.verify_cache.set_epoch(
                    self.bundle_source.current().sequence,
                    scope=self.channel_id)
            except Exception:
                pass
        try:
            return self._begin_inner(block)
        finally:
            self._msps_snapshot = None

    def validate_finish(self, state: dict) -> ValidationResult:
        self._msps_snapshot = state["msps"]
        try:
            return self._finish_inner(state)
        finally:
            self._msps_snapshot = None

    # -- the one verify step (both tails) -----------------------------------

    def _dispatch(self, items) -> tuple:
        """Partition a block's unique items against the node's verdict
        cache and enqueue the misses on the device, ONE dispatch a
        block.  Never waits for the device; `_await` does.  MAC-verified
        cached verdicts skip the device entirely; anything else — miss,
        MAC failure, stale epoch — is dispatched (the partition's home:
        verify_plane/cache.py).  A block of more than PROBE items is
        probed first, and where the probe finds the cache silent the
        rest is dispatched unasked and nothing is stored.

        `items` is the classic tail's list of VerifyItems or the deep
        tail's signature table (fastcollect.c SigTable: a sequence of
        the same items in which a P-256 item exists only as a row of
        flat buffers until somebody asks for it).  A table goes to the
        provider AS ARRAYS — `batch_verify_packed_async`, no VerifyItem
        but the probe's — exactly where today's rule dispatches the
        whole block unasked: more than PROBE unique items and a silent
        probe (or no cache wired).  Where the probe is answered, the
        block is small, or the provider lacks the verb, the items are
        built and the block goes as items.  -> (partition, thread,
        holder, (n_arrays, reason)): how many of the block's unique
        items were handed over as arrays, and why the rest were not."""
        cache = self.verify_cache
        n = len(items)
        table = None if isinstance(items, list) else items
        reason = "classic_tail"
        if table is not None and n <= PROBE:
            items, table, reason = list(table), None, "small_block"
        if cache is None or not n:
            part = all_miss(items)
        else:
            t0 = time.perf_counter()
            if n <= PROBE:
                part = cache.partition(items)
            else:
                part = cache.partition_probed(items, probe_positions(n),
                                              site="commit")
            tracing.tracer.record_span(
                "validator.cache_filter", t0, time.perf_counter(),
                attributes={"items": n - part.n_bypassed})
        misses = part.misses
        if table is not None and misses is not table:
            table, reason = None, "cache_answered"
        if not misses:
            return part, None, {}, (0, reason)
        provider = self._resolve_provider(len(misses))
        n_arrays = 0
        with dispatch_site("validator"):
            packed = _packed_verb(provider) if table is not None else None
            if packed is not None:
                # the rows as arrays; what is no row rides beside them
                n_arrays, reason = table.n_rows, "scheme"
                resolve = packed(table)
            else:
                if table is not None:
                    misses, reason = list(table), "no_verb"
                # items are their OWN dedup keys (VerifyItem NamedTuple)
                resolve = provider.batch_verify_async(misses)
        th, holder = _resolve_eagerly(resolve, self._econ)
        return part, th, holder, (n_arrays, reason)

    def _prepare_lanes(self, block, wait) -> None:
        """The one step where either tail waits for the device, put to
        use: the block's programs are enqueued and the host has nothing
        to do until their verdicts are in, so what the commit derives
        from the block's bytes alone — its lane table,
        `wire.prepare_lanes` — is opened here, and the commit that
        follows finds it open.  It needs no verdict, no flag and no
        state, whether there is anything to prepare is read off the
        block, and the extraction holds no interpreter lock, so the
        resolver thread wakes when the device is done as it did while
        this thread slept.  A block the extractor fails on is left to the commit,
        which meets the same failure at the same call, as it would have
        without this step.  `wait`: the context reserved for the
        caller's `validator.dispatch_wait`, under which the preparation's
        span, `validator.lanes_prepare`, is recorded where it did the
        work."""
        t0 = time.perf_counter()
        try:
            table = wire.prepare_lanes(block, at="validator_wait")
        except Exception:
            logger.warning("[%s] block %d: lane table not prepared",
                           self.channel_id, block.header.number,
                           exc_info=True)
            table = None
        if table is not None:
            tracing.tracer.record_span(
                "validator.lanes_prepare", t0, time.perf_counter(),
                attributes={"block": int(block.header.number),
                            "txs": table.n_tx},
                parent=wait)

    def _await(self, handle: tuple) -> np.ndarray:
        """Wait for `_dispatch`'s results, store them in the cache and
        return the block's verdicts as one bool array aligned with the
        dispatched items.  The store — a digest and a MAC per item — is
        inside the caller's dispatch-wait clock, under its own span so
        that the wait is told from it.  A bypassed block stores nothing:
        only the device's work is booked."""
        part, th, holder, _handoff = handle
        if th is not None:
            th.join()
            if "err" in holder:
                raise holder["err"]
        t0 = time.perf_counter()
        verdicts = part.settle(holder.get("out"), site="commit",
                               scope=self.channel_id)
        if part.n_bypassed:
            note_device_verifications(part.n_misses, "commit")
        elif th is not None and self.verify_cache is not None:
            tracing.tracer.record_span(
                "validator.cache_store", t0, time.perf_counter(),
                attributes={"items": part.n_misses})
        self._note_coverage(part)
        return verdicts

    def _collected(self, t0: float, num: int, n: int, verify: tuple,
                   tail: str, reason: str, **parts) -> float:
        """Close pass 1: the collect interval into the overlap window,
        the block's transactions into `validator_tail_total`, its unique
        items into `validator_handoff_sigs_total` by the form the
        provider got them in, and the `validator.collect` span (`parts`:
        further attributes of it); returns its seconds."""
        collect_s = time.perf_counter() - t0
        self._econ.note_collect(t0, t0 + collect_s)
        part = verify[0]
        n_arrays, why_items = verify[3]
        n_unique = part.n_hits + part.n_misses
        try:
            from fabric_tpu.ops_plane import registry
            ch = self.channel_id
            registry.counter(
                "validator_tail_total",
                "transactions of the blocks validated, by the tail that "
                "collected and gated them and why: deep (C, for a block "
                "key-level endorsement cannot touch) or classic"
            ).add(n, channel=ch, tail=tail, reason=reason)
            handoff = registry.counter(
                "validator_handoff_sigs_total",
                "unique verify items of the blocks validated, by the "
                "form the provider was handed them in: arrays (rows of "
                "the deep tail's signature table, no VerifyItem built) "
                "or items, and why")
            if n_arrays:
                handoff.add(n_arrays, channel=ch, form="arrays",
                            reason="bypassed")
            if n_unique - n_arrays:
                handoff.add(n_unique - n_arrays, channel=ch, form="items",
                            reason=why_items)
        except Exception:
            pass
        attrs = {"block": int(num), "txs": n, "unique_items": n_unique,
                 "tail": tail, "handoff_arrays": n_arrays,
                 "handoff_items": n_unique - n_arrays, **parts}
        if tail == "classic":
            attrs["reason"] = reason
        if self.verify_cache is not None and n_unique:
            attrs["cache_hits"] = part.n_hits
            attrs["cache_misses"] = part.n_misses - part.n_bypassed
            if part.n_bypassed:
                attrs["cache_bypassed"] = part.n_bypassed
        if part.links:
            # stitch the block trace to the speculative spans whose
            # verdicts it consumed
            attrs["links"] = sorted(part.links)[:8]
        tracing.tracer.record_span(
            "validator.collect", t0, t0 + collect_s, attributes=attrs)
        return collect_s

    # -- which tail a block takes -------------------------------------------

    def _sbe_enabled(self) -> bool:
        """Key-level endorsement is a CHANNEL CAPABILITY
        (common/capabilities/application.go KeyLevelEndorsement): on a
        channel whose config lacks it, validation parameters are inert
        and every key falls back to the namespace policy — peers that
        disagreed on this would produce divergent validity bitmaps."""
        if self.sbe_lookup is None:
            return False
        if self.bundle_source is None:
            return True
        from fabric_tpu.config import CAP_KEY_LEVEL_ENDORSEMENT
        return self.bundle_source.current().has_capability(
            CAP_KEY_LEVEL_ENDORSEMENT)

    def _tail_of(self, use_sbe: bool) -> Tuple[str, str]:
        """(tail, reason) for the block about to be collected.  Key-level
        endorsement can change a flag only where a validation parameter
        exists that a tx of the block could meet: in the committed state,
        in a block validated and not yet committed, or in the block
        itself.  Where none does, `SbeOverlay.policy_for` answers None
        for every key, both tails evaluate the namespace policies alone,
        and the deep one does it without per-tx Python.  This reads the
        first two; the third is the C walk's own count (`_begin_deep`)."""
        if _fastcollect is None or not hasattr(_fastcollect, "digest"):
            return "classic", "no_native"
        if getattr(self, "force_python_collect", False):
            return "classic", "forced"
        if not use_sbe:
            return "deep", "no_sbe"
        if self.sbe_state is None:
            return "classic", "state_meta"      # cannot ask: assume some
        savepoint, live = self.sbe_state()
        if live:
            return "classic", "state_meta"
        pending = self._meta_block
        if pending is not None:
            if savepoint is None or savepoint < pending:
                return "classic", "inflight_meta"
            # committed, and the state's count held it when read
            self._meta_block = None
        return "deep", "no_sbe"

    def _note_meta_block(self, num: int) -> None:
        if self._meta_block is None or num > self._meta_block:
            self._meta_block = num

    def _begin_inner(self, block: Block) -> dict:
        n = n_txs(block)
        # duplicate-txid oracle widened by the in-flight window: a txid
        # in an earlier block the ledger cannot see yet is a duplicate
        # here.  Prune entries the ledger now covers (committed) and
        # entries at/above this block's number (replay of the window).
        num = block.header.number
        self._inflight_txids = [
            (bn, m) for bn, m in self._inflight_txids
            if m and bn < num
            and not self.ledger_has_txid(next(iter(m)))]
        carry = [m for _, m in self._inflight_txids]

        t0 = time.perf_counter()
        use_sbe = self._sbe_enabled()
        tail, reason = self._tail_of(use_sbe)
        if tail == "deep":
            state = self._begin_deep(block, num, carry, t0, use_sbe)
            if state is not None:
                return state
            # the block itself sets or deletes a parameter, and an
            # earlier valid tx's governs the later ones: one wasted C
            # walk, on a block that is rare by nature
            tail, reason = "classic", "block_meta"

        flags = TxFlags(n)
        use_fast = (_fastcollect is not None
                    and not getattr(self, "force_python_collect", False))
        seen_txids: Dict[str, int] = {}
        items: Dict[VerifyItem, None] = {}   # insertion-ordered dedup set
        works: List[_TxWork] = []
        if use_fast:
            recs = _fastcollect.collect(block.data, self.channel_id)
        else:
            from fabric_tpu.committer import collect_py
            recs = collect_py.collect(block.data, self.channel_id)
        has_txid = (self.ledger_has_txid if not carry else (
            lambda t: any(t in s for s in carry)
            or self.ledger_has_txid(t)))
        memo: dict = {}
        acct = _Identities()
        for tx_num, rec in enumerate(recs):
            work = self._collect_tx_fast(tx_num, rec, flags, seen_txids,
                                         items, memo, acct, n_txs=n,
                                         has_txid=has_txid)
            if work is not None:
                works.append(work)
        if use_sbe and any(w.meta_writes for w in works):
            self._note_meta_block(num)
        self._note_identities(num, acct)
        verify = self._dispatch(list(items))
        self._inflight_txids.append((num, seen_txids))
        collect_s = self._collected(t0, num, n, verify, tail, reason)
        return {"block": block, "flags": flags, "items": items,
                "works": works, "verify": verify,
                "msps": self._msps_snapshot, "seen_txids": seen_txids,
                "collect_s": collect_s, "use_sbe": use_sbe}

    def _begin_deep(self, block: Block, num: int, carry: list,
                    t0: float, use_sbe: bool) -> Optional[dict]:
        """Deep native pass 1: the C walker consumes its own tuples
        (fastcollect digest/assemble) — txid dedup, creator/endorser
        memo slot assignment, and the block's unique signatures written
        in dispatch order into one table of flat buffers all run
        without per-tx Python bytecode, and without a Python object a
        signature.  Python's per-block work shrinks to resolving each
        UNIQUE identity once and launching the block's async device
        dispatch (`_dispatch`).  Flag parity with the classic tail and
        the pure-Python mirror is enforced differentially
        (tests/test_committer.py).  None, with nothing of the block
        kept, where the walk found a `#meta` write on a channel with
        key-level endorsement: that block is the classic tail's."""
        n = n_txs(block)
        oracle = self.ledger_has_txid
        if oracle is _false_oracle:
            oracle = None          # unwired: skip the per-tx call in C
        spans = getattr(block, "data_spans", None)
        if spans is not None and hasattr(_fastcollect, "digest_spans"):
            # zero-copy ingest: the envelopes are consumed as spans of
            # the block's raw wire bytes (protocol/wire.py BlockView) —
            # no per-tx bytes objects ever exist on this path
            codes, seen_txids, works, creators, endorsers, n_meta = \
                _fastcollect.digest_spans(spans[0], spans[1],
                                          self.channel_id, carry, oracle)
        else:
            codes, seen_txids, works, creators, endorsers, n_meta = \
                _fastcollect.digest(block.data, self.channel_id, carry,
                                    oracle)
        t_walked = time.perf_counter()
        if n_meta and use_sbe:
            self._note_meta_block(num)
            return None
        # one MSP resolution per unique identity (the whole-block analogue
        # of the classic tail's (0,creator)/(1,endorser) memo dicts)
        acct = _Identities()
        c_ents, deferred = self._resolve_creators(creators, acct)
        e_ents = [self._resolve_endorser(b, acct) for b in endorsers]
        acct.again = len(works) - len(creators)
        if deferred is None:
            self._note_identities(num, acct)

        # the block's unique items in dispatch order: a P-256 item is a
        # row of the table's flat buffers, not an object
        plans: list = []
        pol_cache: dict = {}
        t_assemble = time.perf_counter()
        table, n_refs = _fastcollect.assemble(
            works, c_ents, e_ents, endorsers, codes, plans,
            VerifyItem, SCHEME_P256, self.policies.policy_for, pol_cache)
        t_assembled = time.perf_counter()
        verify = self._dispatch(table)
        self._inflight_txids.append((num, seen_txids))
        # collect's parts, on the span: the C walk, assemble, and the
        # hand-over (the probe + the provider's pack and enqueue)
        collect_s = self._collected(
            t0, num, n, verify, "deep", "no_sbe",
            walk_ms=round((t_walked - t0) * 1e3, 3),
            identities_ms=round(acct.seconds * 1e3, 3),
            assemble_ms=round((t_assembled - t_assemble) * 1e3, 3),
            handover_ms=round((time.perf_counter() - t_assembled) * 1e3, 3))
        state = {"deep": True, "block": block, "codes": codes,
                 "plans": plans, "items": table, "verify": verify,
                 "msps": self._msps_snapshot, "seen_txids": seen_txids,
                 "collect_s": collect_s, "n_refs": n_refs}
        if deferred is not None:
            state.update(deferred=deferred, identities=acct, works=works)
        return state

    # per-block stage SLIs + live overlap gauge (the SLO plane's inputs;
    # the "commit" stage lands next door in committer._observe_metrics)
    _STAGE_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5, 5.0, 10.0, float("inf"))

    def _observe_block(self, collect_s: float, dispatch_s: float,
                       gate_s: float) -> None:
        try:
            from fabric_tpu.ops_plane import registry
            h = registry.histogram(
                "validator_stage_seconds",
                "per-block validation stage latency",
                buckets=self._STAGE_BUCKETS)
            ch = self.channel_id
            h.observe(collect_s, stage="collect", channel=ch)
            h.observe(dispatch_s, stage="dispatch", channel=ch)
            h.observe(gate_s, stage="gate", channel=ch)
            registry.gauge(
                "pipeline_collect_under_verify_frac",
                "live collect-under-verify overlap, rolling block window"
            ).set(self._econ.frac(), channel=ch)
        except Exception:
            pass

    def _note_coverage(self, part) -> None:
        """Verify-once economics for one block: feed the rolling
        coverage window and, on a node whose cache is speculatively
        filled (the gateway host), publish speculative_coverage_frac —
        the fraction of this window's unique verify items whose
        verdicts were already cached when the block arrived."""
        cache = self.verify_cache
        if cache is None:
            return
        cache.coverage.note(part.n_hits, part.n_hits + part.n_misses)
        if not cache.speculative_attached:
            return
        try:
            from fabric_tpu.ops_plane import registry
            registry.gauge(
                "speculative_coverage_frac",
                "fraction of committed unique verify items whose "
                "verdicts were cached before the block arrived "
                "(rolling block window)"
            ).set(cache.coverage.frac(), channel=self.channel_id,
                  # the registry is process-global: multi-node test
                  # topologies share it, so each node's coverage must be
                  # its own series or the last committer wins the sample
                  owner=getattr(cache, "owner", "node"))
        except Exception:
            pass

    def _finish_deep(self, state: dict) -> ValidationResult:
        block = state["block"]
        codes = state["codes"]
        index = state["items"]

        t0 = time.perf_counter()
        # enqueued first, so ready first: settled on the host while the
        # device runs the block's own programs
        settled = (self._settle_creators(state) if "deferred" in state
                   else {})
        wait = tracing.tracer.reserve_span()
        self._prepare_lanes(block, wait)
        # positional over the table, as gate reads it
        verdict = self._await(state["verify"]).view(np.uint8)
        dispatch_s = time.perf_counter() - t0
        tracing.tracer.record_span(
            "validator.dispatch_wait", t0, t0 + dispatch_s,
            attributes={"block": int(block.header.number),
                        "unique_items": len(index), **settled},
            context=wait)

        t0 = time.perf_counter()
        _fastcollect.gate(state["plans"], verdict, codes,
                          self.validation_plugin, self.evaluator, {})
        flags = TxFlags.from_bytes(bytes(codes))
        return self._finished(state, flags, t0, dispatch_s,
                              len(state["plans"]))

    def _finish_inner(self, state: dict) -> ValidationResult:
        if state.get("deep"):
            return self._finish_deep(state)
        block = state["block"]
        flags = state["flags"]
        items = state["items"]
        works = state["works"]

        t0 = time.perf_counter()
        keys = list(items.keys())
        wait = tracing.tracer.reserve_span()
        self._prepare_lanes(block, wait)
        verdict: Dict[Tuple, bool] = dict(
            zip(keys, self._await(state["verify"]).tolist()))
        dispatch_s = time.perf_counter() - t0
        tracing.tracer.record_span(
            "validator.dispatch_wait", t0, t0 + dispatch_s,
            attributes={"block": int(block.header.number),
                        "unique_items": len(keys)},
            context=wait)

        t0 = time.perf_counter()
        from fabric_tpu.committer.sbe import SbeOverlay
        overlay = SbeOverlay(self.sbe_lookup) if state["use_sbe"] else None
        plugin = self._memoized_plugin({})
        for work in works:
            self._gate_tx(work, flags, verdict, overlay, plugin=plugin)
        return self._finished(state, flags, t0, dispatch_s, len(works),
                              self._note_sbe(overlay))

    def _note_sbe(self, overlay) -> dict:
        """What key-level endorsement did in one block's gate, from the
        overlay's plain ints: into the counters once a block, and back
        as the `validator.gate` span's attributes."""
        if overlay is None:
            return {}
        try:
            from fabric_tpu.ops_plane import registry
            ch = self.channel_id
            keys = registry.counter(
                "validator_sbe_keys_total",
                "written keys and `#meta` keys the classic gate looked a "
                "policy up for, by what answered: a committed validation "
                "parameter, none (the namespace policy governs), or the "
                "block's own updates")
            keys.add(overlay.by_parameter, channel=ch, judged="parameter")
            keys.add(overlay.by_namespace, channel=ch, judged="namespace")
            keys.add(overlay.by_overlay, channel=ch, judged="overlay")
            registry.counter(
                "validator_sbe_failures_total",
                "transactions a key's validation parameter failed"
            ).add(overlay.failures, channel=ch)
            registry.counter(
                "validator_sbe_policies_total",
                "distinct validation parameters decoded, a block"
            ).add(overlay.policies, channel=ch)
        except Exception:
            pass
        return {"sbe_keys": (overlay.by_parameter + overlay.by_namespace
                             + overlay.by_overlay),
                "sbe_overlay": overlay.by_overlay,
                "sbe_failures": overlay.failures,
                "sbe_policies": overlay.policies}

    def _finished(self, state: dict, flags: TxFlags, t0: float,
                  dispatch_s: float, n_gated: int,
                  sbe_attrs: Optional[dict] = None) -> ValidationResult:
        """Close pass 2 for either tail: the gate's span, then the
        flags' way into the block's metadata (a BlockView decodes its
        metadata here), the stage metrics and the log line."""
        gate_s = time.perf_counter() - t0
        block = state["block"]
        collect_s = state["collect_s"]
        n_unique = len(state["items"])
        tracing.tracer.record_span(
            "validator.gate", t0, t0 + gate_s,
            attributes=dict(sbe_attrs or (), block=int(block.header.number),
                            txs=n_gated))
        n_refs = state.get("n_refs")
        if n_refs is None:       # the classic tail counts its own here
            n_refs = sum(1 + sum(len(s) for _, _, s in w.namespaces)
                         for w in state["works"])
        t0 += gate_s
        block.metadata.items[META_TXFLAGS] = flags.to_bytes()
        self._observe_block(collect_s, dispatch_s, gate_s)
        logger.info(
            "[%s] validated block %d: %d/%d valid | collect=%.1fms "
            "dispatch=%.1fms (%d uniq sigs) gate=%.1fms",
            self.channel_id, block.header.number, flags.valid_count(),
            n_txs(block), collect_s * 1e3, dispatch_s * 1e3, n_unique,
            gate_s * 1e3)
        tracing.tracer.record_span("validator.finish", t0,
                                   time.perf_counter())
        return ValidationResult(flags, collect_s, dispatch_s, gate_s,
                                n_refs, n_unique)


def _memo_ent(ident: Identity) -> tuple:
    """What both tails memoize per identity: (identity, pub_wire,
    scheme) where the identity's VerifyItem is its four plain fields —
    P-256 over the walker's SHA-256 digest, Ed25519 over the message
    itself — so that a tail interns it without a call per signature;
    (identity, None, None) for one that shapes its own item (idemix)."""
    scheme = getattr(ident, "scheme", None)
    if scheme in (SCHEME_P256, SCHEME_ED25519):
        return ident, ident._pub_wire, scheme
    return ident, None, None


def _packed_verb(provider):
    """The provider's `batch_verify_packed_async`, or None where it
    knows items only: it has no such verb, or its item verb was replaced
    on the instance (a fault injection — the benchmark's controls answer
    yes from a `batch_verify_async` of their own), and the class's
    packed verb would go round the replacement."""
    if "batch_verify_async" in getattr(provider, "__dict__", ()):
        return None
    return getattr(provider, "batch_verify_packed_async", None)


def _many_verb(msp):
    """The MSP's `validate_many`, or None where it validates one
    identity at a time: it has no such verb (an idemix MSP), or its
    `validate` was replaced on the instance and the class's verb would
    go round the replacement."""
    if "validate" in getattr(msp, "__dict__", ()):
        return None
    return getattr(msp, "validate_many", None)


def _resolve_eagerly(resolve, econ: _PipelineEconomics) -> tuple:
    """EAGER background resolution: a thread blocks on the results the
    moment a dispatch is enqueued -> (thread, holder), the results in
    holder["out"] or what `resolve` raised in holder["err"].  The
    provider's dispatch account times the device by when a waiter that
    was ALREADY blocked saw the output (`t_ready`,
    bccsp/dispatch_account.py), and a driver that begins block N+1
    before finishing block N keeps this fetch ahead of the later
    dispatch."""
    holder: dict = {}
    t_disp = time.perf_counter()

    def run():
        try:
            holder["out"] = resolve()
            econ.note_verify(t_disp, time.perf_counter())
        except BaseException as exc:   # re-raised by whoever joins
            holder["err"] = exc

    th = threading.Thread(target=run, daemon=True)
    th.start()
    return th, holder


def _false_oracle(_txid: str) -> bool:
    """Default ledger-txid oracle for an unwired validator."""
    return False


def _msp_error(msp, ident: Identity) -> Optional[Exception]:
    """None where the MSP validates the identity's chain, else what it
    raised."""
    try:
        msp.validate(ident)
    except Exception as exc:
        return exc
    return None


def _msp_rejects(msps: Dict[str, object], ident: Identity) -> Optional[str]:
    """None where the identity's MSP validates its chain, else why not
    (`MSPValidationError.reason`)."""
    msp = msps.get(ident.mspid)
    if msp is None:
        return "undecodable"
    err = _msp_error(msp, ident)
    return None if err is None else getattr(err, "reason", "untrusted")


class _Identities:
    """One block's account of identity resolution (plain numbers, read
    once a block by `_note_identities`)."""
    __slots__ = ("start", "seconds", "first", "again", "endorsers",
                 "rejected", "attrs")

    def __init__(self):
        self.start = None        # the first resolution's start
        self.seconds = 0.0       # the resolutions' sum
        self.first = 0           # creators the block's memo did not know
        self.again = 0           # transactions whose creator it knew
        self.endorsers = 0       # unique endorsers resolved
        self.rejected = {}       # reason -> unique creators refused
        self.attrs = {}          # further attributes of the span, where a
        #                          provider got creators' CA signatures:
        #                          how many, and the stretch's parts

    def reject(self, reason: str) -> None:
        self.rejected[reason] = self.rejected.get(reason, 0) + 1

    def add(self, t0: float) -> None:
        if self.start is None:
            self.start = t0
        self.seconds += time.perf_counter() - t0
