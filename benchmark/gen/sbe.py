"""Assets under their owner's endorsement policy for the yardstick:
generator and plain model, from the seed.

The benchmark's own copy of `fabric_tpu/testing/asset_sbe_model.py`, as
`smallbank.py` is of its model: the yardstick must not move when the
program does (`tests/test_sbe_gen.py` holds the two copies to the same
chain, flags and counts on a seed).  Shares no code with the program's
contract, `committer/` or `ledger/`.

Two dicts — the assets and the keys' validation parameters — the
functions of fabric-samples' `asset-transfer-sbe`, the read-write set
each leaves, the traffic as a pure function of a seed, and a serial
block rule.  A key's parameter is held as the one organisation whose
peer must endorse (`OutOf(1, '<org>.peer')`); the chaincode-level policy
asks every organisation of the channel.  A transaction of a block is, in
this order,

  ENDORSEMENT_POLICY_FAILURE  if its valid endorsers (an endorsement
                              tampered with is none) do not satisfy the
                              policy that governs each key it writes and
                              each parameter it writes: the key's
                              parameter — the block's own updates first,
                              then the committed one — else the
                              chaincode policy; a parameter write is
                              judged under the key's CURRENT parameter,
  MVCC_READ_CONFLICT          else if a key it read is no longer at the
                              version it read,
  VALID                       otherwise, and only then its writes, its
                              parameter and its delete count: a deleted
                              key drops its parameter.

"The block's own updates" are those of the earlier transactions that
passed the policy check — the validator gates a whole block before MVCC
runs, so a transfer that later loses MVCC has still handed the key over
for the rest of its block (`committer/sbe.py`'s same-block rule, which
this follows; upstream is recalled to refuse every later transaction on
such a key outright, and `upstream_differs` counts the transactions the
two rules could code differently).  A delete that passed the check
clears the key's parameter the same way.

`iter_chain` draws the chain: a load phase that creates every asset
(creators round-robin, endorsed by every org, none tampered), then
blocks of the mix over the assets live before each block, uniformly,
every transaction simulated against the state committed before its
block, as a block cut under load holds them.  `build_block_data` turns one
block's plan into signed envelopes (what the parallel workers run): each
transaction carries its own endorsers' signatures and no others.
`summary` is what the judge keeps of a block once it is built.
"""

from __future__ import annotations

import collections
import itertools
import json
import random

VALID, POLICY_FAILURE, MVCC_CONFLICT = 0, 10, 11

# the run phase's mix: (kind, share); the sample's script is its shape —
# updates mostly, one wrong-org attempt for every two hand-overs
MIX = (("update", 0.80), ("transfer", 0.10), ("wrong_org", 0.05),
       ("delete", 0.025), ("create", 0.025))
VALUES = (1, 1_000_000)          # uniform, both ends included
FUNCTIONS = ("CreateAsset", "ReadAsset", "UpdateAsset", "TransferAsset",
             "DeleteAsset", "AssetExists")


class Rejected(Exception):
    """The function refuses: the contract raises SimulationError."""


def asset_key(n) -> str:
    return f"asset{n}"


def record(asset_id: str, value: int, owner: str, owner_org: str) -> str:
    """The asset as its one state value holds it."""
    return json.dumps({"ID": asset_id, "Value": value, "Owner": owner,
                       "OwnerOrg": owner_org}, separators=(",", ":"))


def parameter_expression(org: str) -> str:
    """A key's validation parameter in the policy language."""
    return f"OutOf(1, '{org}.peer')"


def enrolment_name(client: int, orgs) -> str:
    """Clients are enrolled org by org in turn (`node/provision.py`'s
    pool): client i is its org's (i // len(orgs))-th."""
    org, nth = orgs[client % len(orgs)], client // len(orgs)
    return f"client{nth or ''}@{org}"


class Tally:
    """What one block's policy check asked, in plain ints: the
    validator's `validator_sbe_*` counters hold the same."""

    def __init__(self):
        self.by_parameter = self.by_namespace = self.by_overlay = 0
        self.failures = 0
        self.orgs = set()        # distinct parameters met

    def as_dict(self) -> dict:
        return {"parameter": self.by_parameter,
                "namespace": self.by_namespace, "overlay": self.by_overlay,
                "failures": self.failures, "policies": len(self.orgs)}


class Registry:
    """Every asset, every key's parameter, and the version of each
    asset's last write."""

    def __init__(self, orgs):
        self.orgs = tuple(orgs)  # the chaincode policy: all of them
        self.assets = {}         # id -> {"ID", "Value", "Owner", "OwnerOrg"}
        self.params = {}         # id -> the org whose peer must endorse
        self.version = {}        # id -> [block, tx number]

    def held(self, asset_id: str) -> dict:
        if asset_id not in self.assets:
            raise Rejected(f"asset {asset_id} does not exist")
        return self.assets[asset_id]

    # -- the functions: (writes {id: record | None}, parameter, payload) ----

    def CreateAsset(self, org, asset_id, value, owner):
        if asset_id in self.assets:
            raise Rejected(f"asset {asset_id} already exists")
        return ({asset_id: record(asset_id, int(value), owner, org)},
                [asset_id, org], "created")

    def ReadAsset(self, org, asset_id):
        a = self.held(asset_id)
        return {}, None, record(a["ID"], a["Value"], a["Owner"],
                                a["OwnerOrg"])

    def UpdateAsset(self, org, asset_id, value):
        a = self.held(asset_id)
        return ({asset_id: record(asset_id, int(value), a["Owner"],
                                  a["OwnerOrg"])}, None, "updated")

    def TransferAsset(self, org, asset_id, new_owner, new_owner_org):
        a = self.held(asset_id)
        return ({asset_id: record(asset_id, a["Value"], new_owner,
                                  new_owner_org)},
                [asset_id, new_owner_org], "transferred")

    def DeleteAsset(self, org, asset_id):
        self.held(asset_id)
        return {asset_id: None}, None, "deleted"

    def AssetExists(self, org, asset_id):
        return {}, None, "true" if asset_id in self.assets else "false"

    # -- simulate, commit --------------------------------------------------

    def simulate(self, fn: str, args, creator_org: str) -> dict:
        """What an endorser's simulation of `fn(*args)`, submitted by a
        client of `creator_org`, records against this state: the one
        read with the version read, the writes (None: a delete), the
        parameter it sets, the response payload.  Raises Rejected."""
        if fn not in FUNCTIONS:
            raise Rejected(f"unknown function {fn!r}")
        args = [str(a) for a in args]
        try:
            written, param, payload = getattr(self, fn)(creator_org, *args)
        except (TypeError, ValueError) as exc:
            raise Rejected(str(exc))
        return {"fn": fn, "args": args,
                "reads": [[args[0], self.version.get(args[0])]],
                "writes": [[k, written[k]] for k in sorted(written)],
                "param": param, "payload": payload}

    def commit_block(self, number: int, txs: list, tally: Tally = None):
        """The serial block rule over `txs` (each a `simulate` result
        plus "endorsers", the orgs whose peers signed it, and "tampered"
        when one endorsement was altered).  -> the validation codes;
        each tx gets its "cause" where the policy check failed it and
        "upstream_differs" where upstream's same-block rule could code
        it differently; the VALID transactions' effects are applied."""
        tally = tally or Tally()
        overlay = {}             # id -> org | None, by txs that passed
        codes = []
        for n, tx in enumerate(txs):
            ok, touched = self._policy_check(tx, overlay, tally)
            if not ok:
                codes.append(POLICY_FAILURE)
                continue
            if touched:
                tx["upstream_differs"] = True
            if tx["param"] is not None:
                overlay[tx["param"][0]] = tx["param"][1]
            for key, value in tx["writes"]:
                if value is None:
                    overlay[key] = None
            if any(self.version.get(k) != v for k, v in tx["reads"]):
                codes.append(MVCC_CONFLICT)
                continue
            codes.append(VALID)
            for key, value in tx["writes"]:
                if value is not None:
                    self.assets[key] = json.loads(value)
                    self.version[key] = [number, n]
            if tx["param"] is not None:
                self.params[tx["param"][0]] = tx["param"][1]
            for key, value in tx["writes"]:
                if value is None:        # the parameter goes with the key
                    for held in (self.assets, self.version, self.params):
                        held.pop(key, None)
        return codes

    def _policy_check(self, tx: dict, overlay: dict, tally: Tally):
        """(passed, a key of it was in the block's overlay)."""
        endorsers = list(tx["endorsers"])
        if tx.get("tampered"):
            # the one altered signature: the second of several, as the
            # other deployments tamper, or the only one
            del endorsers[min(1, len(endorsers) - 1)]
        valid = set(endorsers)
        touched = False

        def governing(key):
            """(the org whose peer must endorse | None, from the overlay)"""
            nonlocal touched
            from_overlay = key in overlay
            if from_overlay:
                tally.by_overlay += 1
                touched = True
                org = overlay[key]
            else:
                org = self.params.get(key)
                if org is None:
                    tally.by_namespace += 1
                else:
                    tally.by_parameter += 1
            if org is not None:
                tally.orgs.add(org)
            return org, from_overlay

        def fail(org, from_overlay):
            if org is not None:
                tally.failures += 1
            tx["cause"] = ("tampered" if tx.get("tampered") else
                           "wrong_org" if tx.get("kind") == "wrong_org" else
                           "overlay" if from_overlay else "policy")
            return False, touched

        chaincode_policy = False
        for key, _ in tx["writes"]:
            org, from_overlay = governing(key)
            if org is None:
                chaincode_policy = True
            elif org not in valid:
                return fail(org, from_overlay)
        if tx["param"] is not None:
            org, from_overlay = governing(tx["param"][0])
            if (org not in valid) if org is not None \
                    else not valid.issuperset(self.orgs):
                return fail(org, from_overlay)
        if (chaincode_policy or not (tx["writes"] or tx["param"])) \
                and not valid.issuperset(self.orgs):
            return fail(None, False)
        return True, touched


# -- the generator ---------------------------------------------------------------

def iter_chain(seed: int, assets: int, blocks: int, block_tx: int,
               n_clients: int, tamper_every: int, orgs=("Org1", "Org2",
                                                        "Org3")):
    """Yields block plans {"number", "txs", "codes", "tally"}: first the
    load phase (`CreateAsset` of asset1..asset<assets>, `block_tx` a
    block, creator round-robin, every org endorsing, none tampered),
    then `blocks` blocks of the mix.  A tx is a `Registry.simulate`
    result plus "kind", "creator" (client index), "endorsers",
    "tampered", "nonce" (hex)."""
    rng = random.Random(seed)
    orgs = tuple(orgs)
    world = Registry(orgs)
    clients_of = {org: [c for c in range(n_clients)
                        if orgs[c % len(orgs)] == org] for org in orgs}
    number = 0
    live, where = [], {}         # ids live before the block, and their place
    # ids to create again, the longest-deleted first: (id, was deleted)
    gone = collections.deque()
    fresh = itertools.count(assets + 1)

    def finish(txs):
        nonlocal number
        for tx in txs:
            tx["nonce"] = rng.randbytes(24).hex()
        tally = Tally()
        codes = world.commit_block(number, txs, tally)
        for tx, code in zip(txs, codes):
            key = tx["args"][0]
            if tx["kind"] == "create":
                if code == VALID:
                    where[key] = len(live)
                    live.append(key)
                else:                    # still to be created
                    gone.append((key, tx["recreate"]))
            elif tx["kind"] == "delete" and code == VALID:
                last = live.pop()
                if last != key:
                    live[where[key]] = last
                    where[last] = where[key]
                del where[key]
                gone.append((key, True))
        block = {"number": number, "txs": txs, "codes": codes,
                 "tally": tally.as_dict()}
        number += 1
        return block

    def create(key, client, recreate=False):
        org = orgs[client % len(orgs)]
        tx = world.simulate("CreateAsset",
                            [key, rng.randint(*VALUES),
                             enrolment_name(client, orgs)], org)
        return dict(tx, kind="create", creator=client, recreate=recreate,
                    endorsers=list(orgs), tampered=False)

    for first in range(1, assets + 1, block_tx):
        yield finish([create(asset_key(i), (i - 1) % n_clients)
                      for i in range(first,
                                     min(first + block_tx, assets + 1))])
    shares = list(itertools.accumulate(s for _, s in MIX))
    for _ in range(blocks):
        txs = []
        for t in range(block_tx):
            u = rng.random()
            kind = MIX[next(i for i, s in enumerate(shares)
                            if u < s or i == len(MIX) - 1)][0]
            if kind == "create":
                key, deleted = (gone.popleft() if gone
                                else (asset_key(next(fresh)), False))
                tx = create(key, rng.randrange(n_clients), deleted)
            else:
                key = live[rng.randrange(len(live))]
                owner_org = world.assets[key]["OwnerOrg"]
                others = [o for o in orgs if o != owner_org]
                org = rng.choice(others) if kind == "wrong_org" \
                    else owner_org
                client = rng.choice(clients_of[org])
                if kind == "transfer":
                    to = rng.choice(others)
                    call = ("TransferAsset",
                            [key, enrolment_name(rng.choice(clients_of[to]),
                                                 orgs), to])
                elif kind == "delete":
                    call = ("DeleteAsset", [key])
                else:
                    call = ("UpdateAsset", [key, rng.randint(*VALUES)])
                tx = dict(world.simulate(*call, org), kind=kind,
                          creator=client, endorsers=[org])
            tx["tampered"] = t % tamper_every == tamper_every - 1
            txs.append(tx)
        yield finish(txs)


def plan_chain(*args, **kwargs) -> list:
    return list(iter_chain(*args, **kwargs))


def replay_plan(plan: list, orgs=("Org1", "Org2", "Org3"),
                upto: int = None) -> Registry:
    """The registry after the plan's blocks numbered <= `upto` (all,
    when None), by the block rule alone: codes are decided again here."""
    world = Registry(orgs)
    for block in plan:
        if upto is not None and block["number"] > upto:
            break
        world.commit_block(block["number"], block["txs"])
    return world


def counts(plan_blocks) -> dict:
    """What the cell wants to see happen in every run, counted over the
    given block plans by the model's own codes."""
    out = dict.fromkeys(("wrong_org_failures", "overlay_failures",
                         "mvcc_conflicts", "deletes", "recreates",
                         "upstream_differs", "signatures"), 0)
    for block in plan_blocks:
        for tx, code in zip(block["txs"], block["codes"]):
            out["signatures"] += 1 + len(tx["endorsers"])
            out["mvcc_conflicts"] += code == MVCC_CONFLICT
            out["upstream_differs"] += bool(tx.get("upstream_differs"))
            if code == POLICY_FAILURE:
                out["wrong_org_failures"] += tx.get("cause") == "wrong_org"
                out["overlay_failures"] += tx.get("cause") == "overlay"
            elif code == VALID:
                out["deletes"] += tx["kind"] == "delete"
                out["recreates"] += (tx["kind"] == "create"
                                     and tx["recreate"])
    return out


# -- envelopes, and what the judge keeps ----------------------------------------

def parameter_bytes(org: str) -> bytes:
    """A parameter as the state holds it: the policy, serialized."""
    from fabric_tpu.policy import parse_policy
    return parse_policy(parameter_expression(org)).serialize()


def build_block_data(block_plan: dict, channel: str, chaincode: str,
                     endorsers: dict, creators: list) -> list:
    """One block's serialized envelopes, in order.  `endorsers` is {org:
    its peer's signing identity}; a transaction is signed by the peers of
    its own "endorsers" alone, and a tampered one has a byte of one of
    those signatures flipped (the second of several, else the only)."""
    from fabric_tpu.protocol import (ChaincodeAction, Endorsement, KVRead,
                                     KVWrite, NsRwSet, Transaction,
                                     TransactionAction, TxRwSet, Version,
                                     build)
    from fabric_tpu.protocol.types import TX_ENDORSER
    from gen.backlog import flip_last_byte

    parameters = {}
    data = []
    for tx in block_plan["txs"]:
        creator = creators[tx["creator"]]
        nonce = bytes.fromhex(tx["nonce"])
        txid = build.compute_txid(nonce, creator.serialize())
        sets = [NsRwSet(
            chaincode,
            reads=tuple(KVRead(k, None if v is None else Version(*v))
                        for k, v in tx["reads"]),
            writes=tuple(KVWrite(k, is_delete=True) if v is None
                         else KVWrite(k, v.encode())
                         for k, v in tx["writes"]))]
        if tx["param"] is not None:
            key, org = tx["param"]
            if org not in parameters:
                parameters[org] = parameter_bytes(org)
            sets.append(NsRwSet(chaincode + "#meta",
                                writes=(KVWrite(key, parameters[org]),)))
        args = [tx["fn"].encode()] + [a.encode() for a in tx["args"]]
        ta = TransactionAction(
            build.proposal_hash(channel, txid, chaincode, args),
            ChaincodeAction(chaincode, "1.0", TxRwSet(tuple(sets)),
                            response_payload=tx["payload"].encode()))
        ends = [build.endorse(ta, endorsers[org]) for org in tx["endorsers"]]
        if tx["tampered"]:
            i = min(1, len(ends) - 1)
            ends[i] = Endorsement(ends[i].endorser,
                                  flip_last_byte(ends[i].signature))
        ta = TransactionAction(ta.proposal_hash, ta.action, tuple(ends))
        data.append(build.signed_envelope(
            TX_ENDORSER, channel, Transaction((ta,)).to_dict(), creator,
            nonce=nonce).serialize())
    return data


_IDENTITIES = {}                 # per worker process: loaded once


def worker_build(deployment_file: str, channel: str, chaincode: str,
                 block_plan: dict) -> list:
    """`build_block_data` as a pool's task: a spawned worker loads the
    deployment's identities on its first block."""
    from gen.backlog import load_identities
    if deployment_file not in _IDENTITIES:
        peers, creators = load_identities(deployment_file)
        _IDENTITIES[deployment_file] = ({p.mspid: p for p in peers},
                                        creators)
    endorsers, creators = _IDENTITIES[deployment_file]
    return build_block_data(block_plan, channel, chaincode, endorsers,
                            creators)


def summary(block_plan: dict) -> dict:
    """What judging a block needs once its envelopes exist: its codes,
    which transactions were tampered, what the cell wants to see happen
    counted by the model's codes, the policy check's tally, the highest
    asset number it creates (0: none), and the VALID
    transactions' effects on the two dicts, in order: (id, record | None,
    parameter's org | None), a None record being a delete."""
    effects = []
    for tx, code in zip(block_plan["txs"], block_plan["codes"]):
        if code != VALID:
            continue
        for key, value in tx["writes"]:
            effects.append((key, value,
                            tx["param"][1] if tx["param"] else None))
    return {"number": block_plan["number"],
            "codes": bytes(block_plan["codes"]),
            "tampered": [n for n, tx in enumerate(block_plan["txs"])
                         if tx["tampered"]],
            "kinds": [tx["kind"] for tx in block_plan["txs"]],
            "causes": {n: tx["cause"]
                       for n, tx in enumerate(block_plan["txs"])
                       if "cause" in tx},
            "recreates": [n for n, tx in enumerate(block_plan["txs"])
                          if tx["kind"] == "create" and tx["recreate"]],
            "counts": counts([block_plan]), "tally": block_plan["tally"],
            "highest_id": max([0] + [int(tx["args"][0][len("asset"):])
                                     for tx in block_plan["txs"]
                                     if tx["kind"] == "create"]),
            "effects": effects}


def state_after(summaries: list, upto: int) -> tuple:
    """(assets {id: record text}, parameters {id: org}) after the blocks
    numbered <= `upto`, from their summaries."""
    assets, params = {}, {}
    for block in summaries:
        if block["number"] > upto:
            break
        for key, value, org in block["effects"]:
            if value is None:
                assets.pop(key, None)
                params.pop(key, None)
            else:
                assets[key] = value
                if org is not None:
                    params[key] = org
    return assets, params


def digests(assets: dict, params: dict, ids) -> list:
    """One SHA-256 (hex) an id over its record and its parameter, None
    where the model holds neither: what a peer reports of its state."""
    import hashlib
    raw = {}
    out = []
    for key in ids:
        record_text, org = assets.get(key), params.get(key)
        if record_text is None and org is None:
            out.append(None)
            continue
        if org is not None and org not in raw:
            raw[org] = parameter_bytes(org)
        out.append(hashlib.sha256(
            (record_text or "").encode() + b"|"
            + (raw[org] if org is not None else b"")).hexdigest())
    return out
