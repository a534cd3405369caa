"""A registry whose assets trade at a private price, in plain Python: the
reference the system is compared with.

Plain dicts — the hashed state every peer holds (collection, SHA-256 of
the key -> SHA-256 of the value and the version of its last write), each
org's cleartext view (what a peer of that org holds in its private
store), the expiry list (which block deletes which hashed keys) — the
functions of `chaincode/asset_private.py` (fabric-samples'
`asset-transfer-private-data`) written again from their definitions with
the hashed read-write set and the private write-sets each leaves, the
traffic as a pure function of a seed, and a serial block rule written
from upstream's description; sharing no code with the contract, the
shim, `committer/`, `ledger/` or `privdata/`.  A transaction of a block
is, in this order,

  ENDORSEMENT_POLICY_FAILURE  if its endorsement was tampered with
                              (cause `tampered`), or it writes a
                              collection that has its own endorsement
                              policy and was endorsed by a peer of
                              another org (`collection_policy`),
  MVCC_READ_CONFLICT          else if a hashed key it read is no longer
                              at the version it read — the block's
                              earlier valid writes first, then the state;
                              cause `expired` where the key left because
                              its block-to-live ended, else `conflict`,
  VALID                       otherwise, and only then do its writes and
                              deletes count, in the hashed state and in
                              the view of every org that is a member of
                              the written collection.

After the block's transactions, its expiries: a key of a collection with
block-to-live BTL written by block N, not written again since and not
written or deleted by this block, leaves the hashed state and every view
with block N + BTL + 1.  The transactions of that block still read it.

`Chain` draws the traffic: a load phase that creates every asset (owners
alternating between the trading orgs, none tampered), then the run
phase's draws — a kind from `MIX`, a pick, one envelope in
`tamper_every` tampered.  A draw becomes a transaction only when its
block is formed: `next_block(limit)` simulates the next draws against
the state committed before the block (an agreement picks an asset
created one block earlier, a transfer one agreed one block earlier, a
delete one at most three blocks old and under no agreement; a draw whose
pool is empty is drawn again as a create), and `commit_block(n)` takes
the first `n` of them — where the orderer's cutter ended the block —
through the block rule.  A `late` draw is a transfer simulated with its
block's others, while the owner's appraisal still lives, and held back
into the front of the next block, after the appraisal's purge.
`build_envelopes` turns transactions into endorsed, signed envelopes;
`private_sets` is what a peer of an org was pushed at endorsement.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import random

VALID, POLICY_FAILURE, MVCC_CONFLICT = 0, 10, 11

# the run phase's mix: (kind, share)
MIX = (("create", 0.40), ("agree", 0.28), ("transfer", 0.25),
       ("delete", 0.05), ("wrong_org", 0.01), ("late", 0.01))
COLORS = ("red", "green", "blue", "yellow", "black", "white", "purple",
          "orange")
SIZES = (1, 100)                 # uniform, both ends included
VALUES = (1, 1_000_000)
ASSET_COLLECTION = "assetCollection"
AGREEMENT = "transferAgreement"
FUNCTIONS = ("CreateAsset", "AgreeToTransfer", "TransferAsset", "DeleteAsset",
             "DeleteTransferAgreement", "ReadAsset",
             "ReadAssetPrivateDetails", "ReadTransferAgreement")
# a delete's asset is at most this many blocks old: its owner's appraisal
# (block-to-live 3) still lives when the delete is validated
DELETE_AGE = 3


class Rejected(Exception):
    """The function refuses: the contract raises SimulationError."""


def asset_key(n) -> str:
    return f"asset{n}"


def org_collection(org: str) -> str:
    return org + "PrivateCollection"


def collections(traders=("Org1", "Org2")) -> dict:
    """The sample's collections_config.json for the trading orgs: name ->
    members, block-to-live, the member-only flags, and the one org whose
    members' endorsement the collection's own policy asks (None: the
    chaincode's policy governs)."""
    out = {ASSET_COLLECTION: {
        "members": tuple(traders), "btl": 1_000_000,
        "member_only_read": True, "member_only_write": True,
        "policy_org": None}}
    for org in traders:
        out[org_collection(org)] = {
            "members": (org,), "btl": 3, "member_only_read": True,
            "member_only_write": False, "policy_org": org}
    return out


def hash_key(key: str) -> str:
    return hashlib.sha256(key.encode()).hexdigest()


def hash_value(value: str) -> str:
    """SHA-256 of a value, hex (the state holds the 32 raw bytes)."""
    return hashlib.sha256(value.encode()).hexdigest()


def agreement_key(asset_id: str) -> str:
    """The composite key (transferAgreement, [assetID])."""
    return "\x00" + AGREEMENT + "\x00" + asset_id + "\x00"


def compact(doc: dict) -> str:
    return json.dumps(doc, separators=(",", ":"))


def details_record(asset_id: str, appraised_value: int) -> str:
    return compact({"assetID": asset_id, "appraisedValue": appraised_value})


def enrolment_name(client: int, orgs) -> str:
    """Clients are enrolled org by org in turn (`node/provision.py`'s
    pool): client i is its org's (i // len(orgs))-th, and its
    certificate's common name — its id to the contract — is this."""
    org, nth = orgs[client % len(orgs)], client // len(orgs)
    return f"client{nth or ''}@{org}"


class _Simulation:
    """One transaction's reads and writes against a `World`, as the shim
    records them at a peer of `peer_org` for a client of `org`."""

    def __init__(self, world, org: str, peer_org: str):
        self.world, self.org, self.peer_org = world, org, peer_org
        self.reads = {}          # (coll, hashed key) -> version read
        self.writes = {}         # (coll, hashed key) -> value hash | None
        self.private = {}        # (coll, key) -> value | None

    def _member_only(self, coll: str, flag: str) -> None:
        cfg = self.world.collections[coll]
        if cfg[flag] and self.org not in cfg["members"]:
            raise Rejected(f"{coll} is member-only: {self.org} is none")

    def _read(self, coll: str, key: str):
        hk = hash_key(key)
        held = self.world.hashed.get((coll, hk))
        self.reads.setdefault((coll, hk), held and held[1])
        return held

    def get(self, coll: str, key: str):
        self._member_only(coll, "member_only_read")
        if (coll, key) in self.private:
            return self.private[coll, key]
        self._read(coll, key)
        return self.world.views[self.peer_org].get((coll, key))

    def get_hash(self, coll: str, key: str):
        held = self._read(coll, key)
        return held and held[0]

    def put(self, coll: str, key: str, value: str) -> None:
        self._member_only(coll, "member_only_write")
        self.writes[coll, hash_key(key)] = hash_value(value)
        self.private[coll, key] = value

    def delete(self, coll: str, key: str) -> None:
        self._member_only(coll, "member_only_write")
        self.writes[coll, hash_key(key)] = None
        self.private[coll, key] = None


def _field(doc: dict, name: str, kind):
    value = doc.get(name)
    if type(value) is not kind or not value or (kind is int and value < 0):
        raise Rejected(f"{name} must be a "
                       + ("positive integer" if kind is int
                          else "non-empty string"))
    return value


class World:
    """The hashed state, each org's view, the expiry list."""

    def __init__(self, orgs=("Org1", "Org2", "Org3"),
                 traders=("Org1", "Org2")):
        self.collections = collections(traders)
        self.traders = tuple(traders)
        self.hashed = {}         # (coll, hashed key) -> (value hash, version)
        self.views = {org: {} for org in orgs}   # (coll, key) -> value
        self.expiry = {}         # block -> [(coll, hashed key, key, written)]
        self.expired = {}        # (coll, hashed key) -> the block it left with

    # -- the functions, each against one simulation ------------------------

    def _transient(self, transient: dict, name: str) -> dict:
        if name not in transient:
            raise Rejected(f"{name} not in the transient map")
        try:
            doc = json.loads(transient[name])
        except ValueError:
            raise Rejected(f"{name}: not JSON")
        if not isinstance(doc, dict):
            raise Rejected(f"{name}: not an object")
        return doc

    def _peer_is_clients(self, sim) -> None:
        if sim.org != sim.peer_org:
            raise Rejected(f"client of {sim.org} at a peer of {sim.peer_org}")

    def _asset(self, sim, asset_id: str) -> dict:
        raw = sim.get(ASSET_COLLECTION, asset_id)
        if raw is None:
            raise Rejected(f"{asset_id} does not exist")
        return json.loads(raw)

    def CreateAsset(self, sim, client, transient):
        doc = self._transient(transient, "asset_properties")
        asset_id = _field(doc, "assetID", str)
        for name, kind in (("objectType", str), ("color", str),
                           ("size", int), ("appraisedValue", int)):
            _field(doc, name, kind)
        if sim.get(ASSET_COLLECTION, asset_id) is not None:
            raise Rejected(f"{asset_id} already exists")
        self._peer_is_clients(sim)
        sim.put(ASSET_COLLECTION, asset_id, compact({
            "objectType": doc["objectType"], "assetID": asset_id,
            "color": doc["color"], "size": doc["size"], "owner": client}))
        sim.put(org_collection(sim.org), asset_id,
                details_record(asset_id, doc["appraisedValue"]))
        return ""

    def AgreeToTransfer(self, sim, client, transient):
        doc = self._transient(transient, "asset_value")
        asset_id = _field(doc, "assetID", str)
        _field(doc, "appraisedValue", int)
        self._asset(sim, asset_id)
        self._peer_is_clients(sim)
        sim.put(org_collection(sim.org), asset_id,
                details_record(asset_id, doc["appraisedValue"]))
        sim.put(ASSET_COLLECTION, agreement_key(asset_id), client)
        return ""

    def TransferAsset(self, sim, client, transient):
        doc = self._transient(transient, "asset_owner")
        asset_id = _field(doc, "assetID", str)
        buyer_org = _field(doc, "buyerMSP", str)
        self._peer_is_clients(sim)
        asset = self._asset(sim, asset_id)
        # verifyAgreement: the submitter owns it, and both parties'
        # appraisals are on the chain, hash for hash the same
        if client != asset["owner"]:
            raise Rejected("the submitter does not own the asset")
        mine = sim.get_hash(org_collection(sim.org), asset_id)
        if mine is None:
            raise Rejected("the owner's appraisal is not on the chain")
        theirs = sim.get_hash(org_collection(buyer_org), asset_id)
        if theirs is None:
            raise Rejected("the buyer's appraisal is not on the chain")
        if mine != theirs:
            raise Rejected("the two appraisals differ")
        buyer = sim.get(ASSET_COLLECTION, agreement_key(asset_id))
        if not buyer:
            raise Rejected("no transfer agreement")
        sim.put(ASSET_COLLECTION, asset_id, compact(dict(asset, owner=buyer)))
        sim.delete(org_collection(sim.org), asset_id)
        sim.delete(ASSET_COLLECTION, agreement_key(asset_id))
        return ""

    def DeleteAsset(self, sim, client, transient):
        doc = self._transient(transient, "asset_delete")
        asset_id = _field(doc, "assetID", str)
        self._peer_is_clients(sim)
        if sim.get(ASSET_COLLECTION, asset_id) is None:
            raise Rejected(f"{asset_id} not found")
        if sim.get(org_collection(sim.org), asset_id) is None:
            raise Rejected("no private details in the client org's "
                           "collection")
        sim.delete(ASSET_COLLECTION, asset_id)
        sim.delete(org_collection(sim.org), asset_id)
        return ""

    def DeleteTransferAgreement(self, sim, client, transient):
        doc = self._transient(transient, "agreement_delete")
        asset_id = _field(doc, "assetID", str)
        self._peer_is_clients(sim)
        if sim.get(ASSET_COLLECTION, agreement_key(asset_id)) is None:
            raise Rejected("no transfer agreement")
        sim.delete(org_collection(sim.org), asset_id)
        sim.delete(ASSET_COLLECTION, agreement_key(asset_id))
        return ""

    def ReadAsset(self, sim, client, transient, asset_id):
        raw = sim.get(ASSET_COLLECTION, asset_id)
        if raw is None:
            raise Rejected(f"{asset_id} does not exist")
        return raw

    def ReadAssetPrivateDetails(self, sim, client, transient, coll,
                                asset_id):
        if coll not in self.collections:
            raise Rejected(f"no collection {coll}")
        raw = sim.get(coll, asset_id)
        if raw is None:
            raise Rejected(f"{asset_id} does not exist in {coll}")
        return raw

    def ReadTransferAgreement(self, sim, client, transient, asset_id):
        buyer = sim.get(ASSET_COLLECTION, agreement_key(asset_id))
        if buyer is None:
            raise Rejected("no transfer agreement")
        return compact({"assetID": asset_id, "buyerID": buyer})

    # -- simulate, commit ----------------------------------------------------

    def simulate(self, fn: str, args, transient: dict, client: str,
                 org: str, peer_org: str = None) -> dict:
        """What the simulation of `fn(*args)` with `transient` ({name:
        JSON text}), submitted by `client` of `org` and run at a peer of
        `peer_org` (the client's own org when None), records against
        this state: the hashed reads with the versions read, the hashed
        writes (None: a delete) and the private write-sets, each in
        (collection, key) order, the response payload.  Raises
        Rejected."""
        if fn not in FUNCTIONS:
            raise Rejected(f"unknown function {fn!r}")
        sim = _Simulation(self, org, peer_org or org)
        args = [str(a) for a in args]
        try:
            payload = getattr(self, fn)(sim, client, transient, *args)
        except (TypeError, ValueError, KeyError) as exc:
            raise Rejected(str(exc))
        return {"fn": fn, "args": args, "transient": dict(transient),
                "org": org, "endorser": peer_org or org,
                "reads": [[c, k, v] for (c, k), v in sorted(sim.reads.items())],
                "writes": [[c, k, v]
                           for (c, k), v in sorted(sim.writes.items())],
                "private": [[c, k, v]
                            for (c, k), v in sorted(sim.private.items())],
                "payload": payload}

    def commit_block(self, number: int, txs: list) -> list:
        """The serial block rule over `txs` (each a `simulate` result,
        "tampered" where its endorsement was altered, "endorser" the org
        whose peer signed it), then the block's expiries.  -> the
        validation codes; a transaction that lost gets its "cause".  The
        VALID transactions' effects are applied, and `self.last` holds
        what the block did: the hashed keys it expired and, by org, the
        private write-sets it resolved."""
        codes = []
        expired_keys = []
        for n, tx in enumerate(txs):
            if tx.get("tampered"):
                tx["cause"] = "tampered"
                codes.append(POLICY_FAILURE)
                continue
            written = {c for c, _, _ in tx["writes"]}
            if tx["endorser"] not in self.traders or any(
                    self.collections[c]["policy_org"] not in (
                        None, tx["endorser"]) for c in written):
                tx["cause"] = "collection_policy"
                codes.append(POLICY_FAILURE)
                continue
            stale = next(((c, k) for c, k, v in tx["reads"]
                          if (self.hashed.get((c, k)) or (None, None))[1]
                          != v), None)
            if stale is not None:
                tx["cause"] = ("expired" if stale in self.expired
                               and stale not in self.hashed else "conflict")
                codes.append(MVCC_CONFLICT)
                continue
            codes.append(VALID)
            self._apply(number, n, tx)
        for coll, hk, key, written in self.expiry.pop(number, ()):
            held = self.hashed.get((coll, hk))
            if held is not None and held[1][0] == written:
                del self.hashed[coll, hk]
                self.expired[coll, hk] = number
                expired_keys.append((coll, hk))
                for view in self.views.values():
                    view.pop((coll, key), None)
        self.last = {"expired": expired_keys}
        return codes

    def _apply(self, number: int, n: int, tx: dict) -> None:
        for (coll, hk, vh), (_, key, value) in zip(tx["writes"],
                                                   self._by_hash(tx)):
            if vh is None:
                self.hashed.pop((coll, hk), None)
            else:
                self.hashed[coll, hk] = (vh, [number, n])
                self.expired.pop((coll, hk), None)
                life = self.collections[coll]["btl"]
                if life:
                    self.expiry.setdefault(number + life + 1, []).append(
                        (coll, hk, key, number))
            for org in self.collections[coll]["members"]:
                if value is None:
                    self.views[org].pop((coll, key), None)
                else:
                    self.views[org][coll, key] = value

    @staticmethod
    def _by_hash(tx: dict) -> list:
        """The private writes in the order of the hashed writes."""
        by_hash = {(c, hash_key(k)): (c, k, v) for c, k, v in tx["private"]}
        return [by_hash[c, hk] for c, hk, _ in tx["writes"]]


# -- the traffic ----------------------------------------------------------------

class Chain:
    """The chain of one seed, formed block by block (module docstring).
    A transaction is a `World.simulate` result plus "kind", "creator"
    (client index), "tampered", "nonce" (hex), "asset"."""

    def __init__(self, seed: int, assets: int, run_tx: int, n_clients: int,
                 tamper_every: int, orgs=("Org1", "Org2", "Org3"),
                 traders=("Org1", "Org2")):
        self.assets, self.orgs, self.traders = assets, tuple(orgs), traders
        self.world = World(orgs, traders)
        self.number = 0          # of the next block
        # the clients that submit, by org, in enrolment order
        self.submitters = {org: [c for c in range(n_clients)
                                 if orgs[c % len(orgs)] == org]
                           for org in traders}
        # what the draws pick from, kept by the committed blocks' codes
        self.born = {}           # block -> [asset ids created VALID in it]
        self.info = {}           # asset id -> its record (see `_note`)
        self._draws = self._draw(random.Random(seed), run_tx, tamper_every)
        self._fresh = itertools.count(assets)    # ids of the run's creates
        self._pending = []       # drawn, in no block yet
        self._taken = 0          # draws that went into committed blocks
        self._held = []          # late transactions, for the next block
        self._formed = []        # the block being formed: (tx, draws used)
        self.kinds = dict.fromkeys([k for k, _ in MIX] + ["redrawn"], 0)

    def _draw(self, rng, run_tx, tamper_every):
        def values():
            return {"color": rng.choice(COLORS), "size": rng.randint(*SIZES),
                    "value": rng.randint(*VALUES)}
        for n in range(self.assets):
            yield dict(values(), kind="create", id=asset_key(n),
                       org=self.traders[n % len(self.traders)],
                       client=n // len(self.traders), tampered=False,
                       nonce=rng.randbytes(24).hex())
        shares = list(itertools.accumulate(s for _, s in MIX))
        for t in range(run_tx):
            u = rng.random()
            kind = MIX[next(i for i, s in enumerate(shares)
                            if u < s or i == len(MIX) - 1)][0]
            # every draw can fall back to a create: it carries one's values
            yield dict(values(), kind=kind, pick=rng.random(),
                       org=self.traders[rng.randrange(len(self.traders))],
                       client=rng.randrange(1 << 30),
                       tampered=t % tamper_every == tamper_every - 1,
                       nonce=rng.randbytes(24).hex())

    def _client(self, org: str, pick: int) -> int:
        pool = self.submitters[org]
        return pool[pick % len(pool)]

    def _other(self, org: str) -> str:
        return self.traders[1 - self.traders.index(org)]

    def _pools(self) -> dict:
        """What this block's draws pick from, off the state the last
        block left; a pick leaves its pool."""
        n = self.number
        live = lambda b: [a for a in self.born.get(b, ())
                          if self.info[a]["live"]]
        return {
            "agree": [a for a in live(n - 1)
                      if self.info[a]["agreed"] is None],
            "transfer": [a for a in live(n - 2)
                         if self.info[a]["agreed"] == n - 1],
            "delete": [a for b in range(n - DELETE_AGE, n) for a in live(b)
                       if self.info[a]["agreed"] is None],
            # the owner's appraisal, written at n - 4, leaves with block n
            "late": [a for a in live(n - 4)
                     if self.info[a]["agreed"] is not None]}

    def _simulate(self, draw: dict, pools: dict) -> dict:
        kind = draw["kind"]
        pool = pools.get("agree" if kind == "wrong_org" else kind)
        if kind != "create" and not pool:
            kind = "create"      # nothing to pick: drawn again as a create
        if kind == "create":
            # a draw keeps the id its first forming gave it
            asset = draw.setdefault("id", asset_key(next(self._fresh)))
            org = draw["org"]
            client = self._client(org, draw["client"])
            call = ("CreateAsset", {"asset_properties": compact({
                "objectType": "asset", "assetID": asset,
                "color": draw["color"], "size": draw["size"],
                "appraisedValue": draw["value"]})})
        else:
            asset = pool.pop(int(draw["pick"] * len(pool)))
            held = self.info[asset]
            if kind in ("agree", "wrong_org"):
                # the buyer, of the other org, at the owner's price
                org = self._other(held["org"])
                client = self._client(org, draw["client"])
                call = ("AgreeToTransfer", {"asset_value": details_record(
                    asset, held["value"])})
            else:
                org, client = held["org"], held["owner"]
                if kind == "delete":
                    call = ("DeleteAsset", {"asset_delete": compact(
                        {"assetID": asset})})
                else:
                    call = ("TransferAsset", {"asset_owner": compact(
                        {"assetID": asset,
                         "buyerMSP": self._other(org)})})
        tx = self.world.simulate(call[0], [], call[1],
                                 enrolment_name(client, self.orgs), org)
        if kind == "wrong_org":
            # endorsed, against the contract's own check, by the peer of
            # the org whose collection it does not write
            tx["endorser"] = self._other(org)
        return dict(tx, kind=kind, drawn=draw["kind"], asset=asset,
                    creator=client, tampered=draw["tampered"],
                    nonce=draw["nonce"])

    def next_block(self, limit: int) -> list:
        """The next block's candidates: the late transactions held back
        for it, then up to `limit` in all of the next draws, never across
        the end of the load phase, simulated against the state committed
        so far.  [] when the chain is drawn out."""
        pools = self._pools()
        self._formed = [(tx, 0) for tx in self._held]
        used = 0
        loading = self._taken < self.assets
        while sum(1 for tx, _ in self._formed
                  if not tx.get("deferred")) < limit:
            if loading and self._taken + used >= self.assets:
                break
            if used == len(self._pending):
                draw = next(self._draws, None)
                if draw is None:
                    break
                self._pending.append(draw)
            tx = self._simulate(self._pending[used], pools)
            used += 1
            if tx["kind"] == "late":
                tx["deferred"] = True
            self._formed.append((tx, used))
        return [tx for tx, _ in self._formed if not tx.get("deferred")]

    def commit_block(self, n: int = None) -> dict:
        """The first `n` candidates (all, when None) are the block:
        -> {"number", "phase", "txs", "codes", "expired"}.  The late
        transactions drawn before the block's last are held for the next
        block; what lies beyond it is drawn again."""
        block, held, waiting, used = [], [], [], 0
        for tx, upto in self._formed:
            if tx.get("deferred"):
                waiting.append((tx, upto))
                continue
            if n is not None and len(block) == n:
                waiting = []             # beyond the cut: drawn again
                break
            block.append(tx)
            held, waiting = held + waiting, []
            used = max(used, upto)
        for tx, upto in held + waiting:
            del tx["deferred"]
            used = max(used, upto)
        self._held = (self._held[len(block):]
                      + [tx for tx, _ in held + waiting])
        phase = "load" if self._taken < self.assets else "run"
        codes = self.world.commit_block(self.number, block)
        for tx, code in zip(block, codes):
            self._note(tx, code)
        del self._pending[:used]
        self._taken += used
        self._formed = []
        out = {"number": self.number, "phase": phase, "txs": block,
               "codes": codes, "expired": self.world.last["expired"]}
        self.number += 1
        return out

    def _note(self, tx: dict, code: int) -> None:
        """Keep what later draws pick from after a transaction's code."""
        self.kinds[tx["kind"]] += 1
        self.kinds["redrawn"] += tx["kind"] != tx["drawn"]
        if code != VALID:
            return
        asset = tx["asset"]
        if tx["kind"] == "create":
            self.born.setdefault(self.number, []).append(asset)
            self.info[asset] = {
                "live": True, "agreed": None, "org": tx["org"],
                "owner": tx["creator"],
                "value": json.loads(tx["transient"]["asset_properties"])[
                    "appraisedValue"]}
        elif tx["kind"] == "agree":
            self.info[asset]["agreed"] = self.number
        else:                    # transferred or deleted: picked no more
            self.info[asset]["live"] = False
        # a pool looks four blocks back: older records are never read
        for old in [b for b in self.born if b < self.number - 4]:
            for a in self.born.pop(old):
                del self.info[a]


def plan_chain(seed: int, assets: int, run_tx: int, block_tx: int,
               n_clients: int, tamper_every: int,
               orgs=("Org1", "Org2", "Org3")) -> list:
    """The whole chain cut by count alone, `block_tx` a block."""
    chain = Chain(seed, assets, run_tx, n_clients, tamper_every, orgs)
    plan = []
    while chain.next_block(block_tx):
        plan.append(chain.commit_block())
    return plan


def replay_plan(plan: list, upto: int = None,
                orgs=("Org1", "Org2", "Org3")) -> World:
    """The world after the plan's blocks numbered <= `upto` (all, when
    None), by the block rule alone: codes are decided again here."""
    world = World(orgs)
    for block in plan:
        if upto is not None and block["number"] > upto:
            break
        world.commit_block(block["number"], block["txs"])
    return world


def counts(plan_blocks, org: str = "Org1", traders=("Org1", "Org2")) -> dict:
    """What the cell wants to see happen in every run, and what the
    always-on counters of a peer of `org` must read, over the given block
    plans by the model's own codes."""
    colls = collections(traders)
    out = dict.fromkeys(
        ("txs", "valid", "tampered", "collection_policy", "conflict",
         "expired", "creates", "agrees", "transfers", "deletes",
         "expired_keys", "sets_resolved", "sets_not_member",
         "private_writers"), 0)
    for block in plan_blocks:
        out["expired_keys"] += len(block["expired"])
        for tx, code in zip(block["txs"], block["codes"]):
            out["txs"] += 1
            if code != VALID:
                out[tx["cause"]] += 1
                continue
            out["valid"] += 1
            out[{"create": "creates", "agree": "agrees",
                 "delete": "deletes"}.get(tx["kind"], "transfers")] += 1
            written = {c for c, _, _ in tx["writes"]}
            out["private_writers"] += bool(written)
            mine = sum(org in colls[c]["members"] for c in written)
            out["sets_resolved"] += mine
            out["sets_not_member"] += len(written) - mine
    return out


# -- envelopes -----------------------------------------------------------------

def rwset_of(tx: dict, chaincode: str):
    """The transaction's hashed read-write set as the protocol's TxRwSet:
    one namespace `chaincode$collection` a collection, in name order."""
    from fabric_tpu.protocol import KVRead, KVWrite, NsRwSet, TxRwSet, Version
    names = sorted({c for c, _, _ in tx["reads"]}
                   | {c for c, _, _ in tx["writes"]})
    return TxRwSet(tuple(NsRwSet(
        f"{chaincode}${coll}",
        reads=tuple(KVRead(k, None if v is None else Version(*v))
                    for c, k, v in tx["reads"] if c == coll),
        writes=tuple(KVWrite(k, is_delete=True) if v is None
                     else KVWrite(k, bytes.fromhex(v))
                     for c, k, v in tx["writes"] if c == coll))
        for coll in names))


def private_sets(tx: dict, chaincode: str, org: str,
                 traders=("Org1", "Org2")) -> dict:
    """{(chaincode, collection): {key: value bytes | None}} of the
    collections `org` is a member of: what its peer was pushed when the
    transaction was endorsed."""
    colls = collections(traders)
    out = {}
    for coll, key, value in tx["private"]:
        if org in colls[coll]["members"]:
            out.setdefault((chaincode, coll), {})[key] = (
                None if value is None else value.encode())
    return out


def flip_last_byte(sig: bytes) -> bytes:
    """Still DER, no longer a signature of anything."""
    return sig[:-1] + bytes([sig[-1] ^ 0x01])


def txid_of(tx: dict, creators: list) -> str:
    from fabric_tpu.protocol import build
    return build.compute_txid(bytes.fromhex(tx["nonce"]),
                              creators[tx["creator"]].serialize())


def build_envelopes(txs: list, channel: str, chaincode: str,
                    endorsers: dict, creators: list) -> list:
    """The transactions as serialized, endorsed, signed envelopes, in
    order.  One endorsement each, by the peer of the transaction's
    "endorser" org (`endorsers`: org -> signing identity); a tampered one
    has a byte of its signature flipped.  Nothing of the transient map or
    the private write-sets is in them."""
    from fabric_tpu.protocol import (ChaincodeAction, Endorsement,
                                     Transaction, TransactionAction, build)
    from fabric_tpu.protocol.types import TX_ENDORSER
    data = []
    for tx in txs:
        creator = creators[tx["creator"]]
        nonce = bytes.fromhex(tx["nonce"])
        txid = build.compute_txid(nonce, creator.serialize())
        args = [tx["fn"].encode()] + [a.encode() for a in tx["args"]]
        ta = TransactionAction(
            build.proposal_hash(channel, txid, chaincode, args),
            ChaincodeAction(chaincode, "1.0", rwset_of(tx, chaincode),
                            response_payload=tx["payload"].encode()))
        end = build.endorse(ta, endorsers[tx["endorser"]])
        if tx["tampered"]:
            end = Endorsement(end.endorser, flip_last_byte(end.signature))
        ta = TransactionAction(ta.proposal_hash, ta.action, (end,))
        data.append(build.signed_envelope(
            TX_ENDORSER, channel, Transaction((ta,)).to_dict(), creator,
            nonce=nonce).serialize())
    return data


def build_block(block_plan: dict, previous_hash: bytes, channel: str,
                chaincode: str, endorsers: dict, creators: list):
    """-> (serialized Block, its header hash)."""
    from fabric_tpu.protocol import block_header_hash
    from fabric_tpu.protocol.types import (Block, BlockHeader, BlockMetadata,
                                           block_data_hash)
    data = build_envelopes(block_plan["txs"], channel, chaincode, endorsers,
                           creators)
    header = BlockHeader(block_plan["number"], previous_hash,
                         block_data_hash(data))
    return (Block(header, data, BlockMetadata()).serialize(),
            block_header_hash(header))
