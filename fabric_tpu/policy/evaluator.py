"""Verify-then-gate policy evaluation — the north-star restructure.

Reference flow being restructured (SURVEY.md §3.2):
  policies/policy.go:365-401 SignatureSetToValidIdentities
    - deserialize each SignedData identity, DEDUP by identity
      (policy.go:385-387),
    - Verify() each signature immediately (policy.go:389-393; a bad
      signature only excludes that identity, it is not fatal),
  cauthdsl/cauthdsl.go:24-92 compiled NOutOf/SignedBy evaluation with
      greedy used-once identity consumption.

Here the same decision logic is split into:
  collect()  : produce dedup'd VerifyItems (no crypto),
  [provider.batch_verify over an entire block — ONE TPU dispatch],
  gate()     : keep identities whose verdict bit is set,
  evaluate() : the exact cauthdsl greedy semantics over valid identities.
`evaluate_signed_data` composes all three for single-policy use; a caller
that has already verified some of the set's items (the endorser: the
proposal's creator signature) hands their verdicts in and only the rest
is dispatched.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence

from fabric_tpu.bccsp import VerifyItem
from fabric_tpu.msp import Identity, Principal
from fabric_tpu.utils import serde
from .policy import PolicyError, SignaturePolicy, SignedData


@dataclass
class CollectResult:
    """Dedup'd verification workload for one signature set."""
    items: List[VerifyItem] = field(default_factory=list)
    identities: List[Identity] = field(default_factory=list)  # aligned w/ items

    def __len__(self):
        return len(self.items)


class PolicyEvaluator:
    """Binds an MSP routing table + crypto provider to policy logic.

    msps: mspid -> MSP-like (must expose deserialize_identity and
    satisfies_principal; CachedMSP recommended).
    """

    def __init__(self, msps: Dict[str, object], provider):
        self.msps = msps
        self.provider = provider

    # -- pass 1: collect ----------------------------------------------------

    def collect(self, signed_data: Sequence[SignedData]) -> CollectResult:
        """Deserialize + dedup identities, emit VerifyItems (no crypto)."""
        out = CollectResult()
        seen = set()
        for sd in signed_data:
            if sd.identity in seen:  # policy.go:385-387 dedup rule
                continue
            seen.add(sd.identity)
            try:
                # cheap route on the serialized envelope's mspid, then ONE
                # (cached) full deserialization in the owning MSP
                mspid = serde.decode(sd.identity).get("mspid")
                msp = self.msps.get(mspid)
                if msp is None:
                    continue
                ident = msp.deserialize_identity(sd.identity)
            except Exception:
                continue  # undeserializable identity is skipped, not fatal
            out.items.append(ident.verify_item(sd.data, sd.signature))
            out.identities.append(ident)
        return out

    # -- pass 2 happens in the provider (batched) ---------------------------

    # -- pass 3: gate + evaluate --------------------------------------------

    @staticmethod
    def gate(collected: CollectResult,
             verdicts: Sequence[bool]) -> List[Identity]:
        """Identities whose signatures verified (policy.go:390-393: invalid
        signatures only exclude, never fail the set)."""
        return [ident for ident, ok in zip(collected.identities, verdicts) if ok]

    def evaluate(self, policy: SignaturePolicy,
                 identities: Sequence[Identity]) -> bool:
        """cauthdsl.go:24-92 compiled semantics: greedy, used-once."""
        used = [False] * len(identities)
        return self._eval(policy, identities, used)

    def _eval(self, node: SignaturePolicy, idents, used) -> bool:
        if node.kind == "signed_by":
            p = node.principal
            msp = self.msps.get(p.mspid) if p.mspid else None
            for i, ident in enumerate(idents):
                if used[i]:
                    continue
                target = msp if msp is not None else self.msps.get(ident.mspid)
                if target is None:
                    continue
                if target.satisfies_principal(ident, p):
                    used[i] = True
                    return True
            return False
        if node.kind == "n_out_of":
            # cauthdsl.go:44-58: ALL rules are evaluated (no early exit) and
            # every satisfied rule commits its identity consumption — a
            # satisfied OR branch consumes identities that outer rules then
            # cannot reuse.  Bit-identical verdicts require this exactly.
            satisfied = 0
            for rule in node.rules:
                snapshot = list(used)
                if self._eval(rule, idents, used):
                    satisfied += 1
                else:
                    used[:] = snapshot  # failed branch consumes nothing
            return satisfied >= node.n
        raise PolicyError(f"unknown node kind {node.kind!r}")

    # -- one-shot composition ----------------------------------------------

    def evaluate_signed_data(
            self, policy: SignaturePolicy,
            signed_data: Sequence[SignedData],
            verified: Optional[Mapping[VerifyItem, bool]] = None) -> bool:
        """collect -> verify -> gate -> evaluate for one signature set.

        `verified` holds verdicts the caller already has from this
        node's provider, keyed by the item verified.  A collected item
        is answered from it only when equal in all four fields (scheme,
        public key, signature, payload: Verify is a pure function of
        them); every other item is dispatched here, as without it."""
        collected = self.collect(signed_data)
        if not collected.items:
            return self.evaluate(policy, [])
        verdicts = dict(verified or {})
        todo = [it for it in collected.items if it not in verdicts]
        if todo:
            verdicts.update(zip(todo, self.provider.batch_verify(todo)))
        return self.evaluate(policy, self.gate(
            collected, [verdicts[it] for it in collected.items]))
