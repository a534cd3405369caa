"""An asset registry indexed by colour for the yardstick: generator and
plain model, from the seed.

The benchmark's own copy of `fabric_tpu/testing/asset_queries_model.py`,
as `sbe.py` is of its model: the yardstick must not move when the
program does (`tests/test_queries_gen.py` holds the two copies to the
same chain, flags and counts on a seed).  Shares no code with the
program's contract, `committer/` or `ledger/`.

Two dicts — the assets and the entries of the index `color~name` — the
functions of fabric-samples' `asset-transfer-ledger-queries`, the
read-write set each leaves (range query included: start, end, exhausted,
the raw reads in key order), the traffic as a pure function of a seed,
and a serial block rule written from upstream's description.  Keys
compare by code point.  A transaction of a block is, in this order,

  ENDORSEMENT_POLICY_FAILURE  if one of its endorsements was tampered
                              with (the chaincode policy asks every org),
  MVCC_READ_CONFLICT          else if a key it read is no longer at the
                              version it read — the block's earlier
                              valid writes first, then the state,
  PHANTOM_READ_CONFLICT       else if re-running a range it recorded over
                              the state merged with the block's earlier
                              valid writes and deletes gives other keys
                              or other versions than it recorded,
  VALID                       otherwise, and only then do its writes and
                              deletes count.

Reads are judged before ranges, so a by-colour hand-over that meets an
earlier delete in its colour is MVCC_READ_CONFLICT (it read the deleted
asset), never a phantom: with this contract only a create inside the
colour leaves every read standing and the range changed.

`Chain` draws the traffic: a load phase that creates every asset
(creators round-robin, none tampered), then the run phase's draws — each
independent: a kind from `MIX`, a uniform pick, a client, one envelope in
`tamper_every` tampered.  A draw becomes a transaction only when its
block is formed: `next_block(limit)` simulates the next draws against the
state committed before the block, as a block cut under load holds them
(a transfer's or delete's id is the pick over the assets live then), and
`commit_block(n)` takes the first `n` of them — where the orderer's
cutter ended the block — through the block rule; the rest are drawn
into the next block again.  `form_chain` is that loop with the program's
own `BlockCutter` (`gen/ycsb.py`'s `Cutter`) deciding `n` from the built
envelopes; `summary` is what the judge keeps of a block.  Never imports
jax.
"""

from __future__ import annotations

import bisect
import itertools
import json
import random

VALID, POLICY_FAILURE, MVCC_CONFLICT, PHANTOM_CONFLICT = 0, 10, 11, 12

# the run phase's mix: (kind, share)
MIX = (("transfer", 0.75), ("bycolor", 0.10), ("create", 0.075),
       ("delete", 0.075))
SIZES = (1, 100)                 # uniform, both ends included
VALUES = (1, 1_000_000)
FUNCTIONS = ("CreateAsset", "ReadAsset", "AssetExists", "DeleteAsset",
             "TransferAsset", "TransferAssetByColor", "GetAssetsByRange")
INDEX = "color~name"
INDEX_VALUE = "\x00"
MAX_CODE_POINT = "\U0010ffff"


class Rejected(Exception):
    """The function refuses: the contract raises SimulationError."""


def asset_key(n) -> str:
    return f"asset{n}"


def color_name(n) -> str:
    return "color%04d" % n


def composite_key(object_type: str, attributes) -> str:
    """U+0000, then each part followed by U+0000."""
    parts = [object_type, *attributes]
    if not object_type or any("\x00" in p or MAX_CODE_POINT in p
                              for p in parts):
        raise Rejected("not a composite key's part")
    return "\x00" + "".join(p + "\x00" for p in parts)


def index_key(color: str, asset_id: str) -> str:
    return composite_key(INDEX, [color, asset_id])


def record(asset_id: str, color: str, size: int, owner: str,
           appraised_value: int) -> str:
    """The asset as its one state value holds it."""
    return json.dumps({"docType": "asset", "ID": asset_id, "color": color,
                       "size": size, "owner": owner,
                       "appraisedValue": appraised_value},
                      separators=(",", ":"))


def enrolment_name(client: int, orgs) -> str:
    """Clients are enrolled org by org in turn (`node/provision.py`'s
    pool): client i is its org's (i // len(orgs))-th."""
    org, nth = orgs[client % len(orgs)], client // len(orgs)
    return f"client{nth or ''}@{org}"


class Registry:
    """Every asset, every index entry, the version of each key's last
    write, and every key in code-point order."""

    def __init__(self):
        self.assets = {}         # id -> [color, size, owner, appraisedValue]
        self.index = {}          # composite key -> the asset's id
        self.version = {}        # key (of either dict) -> [block, tx number]
        self._keys = []          # both dicts' keys, ascending

    def record_of(self, asset_id: str) -> str:
        return record(asset_id, *self.assets[asset_id])

    def held(self, asset_id: str) -> list:
        if asset_id not in self.assets:
            raise Rejected(f"asset {asset_id} does not exist")
        return self.assets[asset_id]

    def scan(self, start: str, end: str) -> list:
        """[[key, version]] of the keys in [start, end), ascending; an
        empty `end` means no upper bound."""
        lo = bisect.bisect_left(self._keys, start)
        hi = bisect.bisect_left(self._keys, end) if end else len(self._keys)
        return [[k, self.version[k]] for k in self._keys[lo:hi]]

    # -- the functions: (reads, ranges, writes {key: value | None}, payload) --

    def CreateAsset(self, asset_id, color, size, owner, appraised_value):
        if asset_id in self.assets:
            raise Rejected(f"asset {asset_id} already exists")
        return ([asset_id], [],
                {asset_id: record(asset_id, color, int(size), owner,
                                  int(appraised_value)),
                 index_key(color, asset_id): INDEX_VALUE}, "created")

    def ReadAsset(self, asset_id):
        self.held(asset_id)
        return [asset_id], [], {}, self.record_of(asset_id)

    def AssetExists(self, asset_id):
        return ([asset_id], [], {},
                "true" if asset_id in self.assets else "false")

    def DeleteAsset(self, asset_id):
        color = self.held(asset_id)[0]
        return ([asset_id], [],
                {asset_id: None, index_key(color, asset_id): None}, "deleted")

    def TransferAsset(self, asset_id, new_owner):
        color, size, _, value = self.held(asset_id)
        return ([asset_id], [],
                {asset_id: record(asset_id, color, size, new_owner, value)},
                "transferred")

    def TransferAssetByColor(self, color, new_owner):
        prefix = composite_key(INDEX, [color])
        span = (prefix, prefix + MAX_CODE_POINT)
        ids = [self.index[k] for k, _ in self.scan(*span)]
        writes = {}
        for asset_id in ids:
            _, size, _, value = self.assets[asset_id]
            writes[asset_id] = record(asset_id, color, size, new_owner, value)
        return ids, [span], writes, str(len(ids))

    def GetAssetsByRange(self, start_key, end_key):
        if start_key.startswith("\x00") or end_key.startswith("\x00"):
            raise Rejected("a bound in the composite keys' namespace")
        span = (start_key or "\x01", end_key)    # the simple keys only
        return ([], [span], {},
                "[" + ",".join(self.record_of(k)
                               for k, _ in self.scan(*span)) + "]")

    # -- simulate, commit ----------------------------------------------------

    def simulate(self, fn: str, args) -> dict:
        """What an endorser's simulation of `fn(*args)` records against
        this state: the reads with the versions read and the writes
        (None: a delete), both in key order, each range with its raw
        reads, the response payload.  Raises Rejected."""
        if fn not in FUNCTIONS:
            raise Rejected(f"unknown function {fn!r}")
        args = [str(a) for a in args]
        try:
            reads, spans, written, payload = getattr(self, fn)(*args)
        except (TypeError, ValueError) as exc:
            raise Rejected(str(exc))
        return {"fn": fn, "args": args,
                "reads": [[k, self.version.get(k)] for k in sorted(reads)],
                "ranges": [{"start": start, "end": end, "exhausted": True,
                            "reads": self.scan(start, end)}
                           for start, end in spans],
                "writes": [[k, written[k]] for k in sorted(written)],
                "payload": payload}

    def commit_block(self, number: int, txs: list) -> list:
        """The serial block rule over `txs` (each a `simulate` result,
        "tampered" where an endorsement was altered).  -> the validation
        codes; a transaction that lost gets its "cause": the kind of the
        earlier transaction whose write unseated its read, or what a
        replayed range found ("create": a key more, "delete": a key
        fewer, "rewrite": another version); a transaction whose ranges
        were run again gets "replayed", how many results that re-read.
        The VALID transactions' effects are applied."""
        writer = {}              # key -> kind of the block's tx that wrote it
        codes = []
        for n, tx in enumerate(txs):
            if tx.get("tampered"):
                codes.append(POLICY_FAILURE)
                continue
            stale = next((k for k, v in tx["reads"]
                          if self.version.get(k) != v), None)
            if stale is not None:
                tx["cause"] = writer.get(stale, "state")
                codes.append(MVCC_CONFLICT)
                continue
            if tx["ranges"]:
                tx["replayed"] = 0
                for rq in tx["ranges"]:
                    now = self.scan(rq["start"], rq["end"])
                    tx["replayed"] += len(now)
                    cause = _range_changed(rq, now)
                    if cause:
                        tx["cause"] = cause
                        break
                if "cause" in tx:
                    codes.append(PHANTOM_CONFLICT)
                    continue
            codes.append(VALID)
            for key, value in tx["writes"]:
                writer[key] = tx.get("kind", tx["fn"])
                self._apply(key, value, [number, n])
        return codes

    def _apply(self, key: str, value, version: list) -> None:
        held = self.index if key.startswith("\x00") else self.assets
        if value is None:
            if key in held:
                del held[key], self.version[key]
                del self._keys[bisect.bisect_left(self._keys, key)]
            return
        if key not in held:
            bisect.insort(self._keys, key)
        self.version[key] = version
        if held is self.index:
            held[key] = key[1:-1].split("\x00")[2]
        else:
            doc = json.loads(value)
            held[key] = [doc["color"], doc["size"], doc["owner"],
                         doc["appraisedValue"]]


def _range_changed(rq: dict, now: list):
    """None where the range's raw reads still stand against `now`, the
    same range run again; else what changed.  A range that was not
    exhausted may have grown beyond what it read."""
    then = rq["reads"]
    if not rq["exhausted"]:
        now = now[:len(then)]
    if now == then:
        return None
    keys_then, keys_now = [k for k, _ in then], [k for k, _ in now]
    if keys_now == keys_then:
        return "rewrite"
    return "create" if set(keys_now) - set(keys_then) else "delete"


# -- the traffic ----------------------------------------------------------------

class Chain:
    """The chain of one seed, formed block by block (module docstring).
    A transaction is a `Registry.simulate` result plus "kind", "creator"
    (client index), "tampered", "nonce" (hex)."""

    def __init__(self, seed: int, assets: int, colors: int, run_tx: int,
                 n_clients: int, tamper_every: int,
                 orgs=("Org1", "Org2", "Org3")):
        self.assets, self.colors, self.orgs = assets, colors, tuple(orgs)
        self.world = Registry()
        self.number = 0          # of the next block
        self.live, self._where = [], {}      # live ids, and their place
        self._draws = self._draw(random.Random(seed), run_tx, n_clients,
                                 tamper_every)
        self._pending = []       # drawn, in no block yet
        self._taken = 0          # draws that went into committed blocks
        self._formed = []        # the block being formed

    def _draw(self, rng, run_tx, n_clients, tamper_every):
        for n in range(self.assets):
            yield {"kind": "create", "id": asset_key(n),
                   "color": color_name(n % self.colors),
                   "size": rng.randint(*SIZES), "value": rng.randint(*VALUES),
                   "creator": n % n_clients, "owner": n % n_clients,
                   "tampered": False, "nonce": rng.randbytes(24).hex()}
        shares = list(itertools.accumulate(s for _, s in MIX))
        fresh = itertools.count(self.assets)
        for t in range(run_tx):
            u = rng.random()
            kind = MIX[next(i for i, s in enumerate(shares)
                            if u < s or i == len(MIX) - 1)][0]
            draw = {"kind": kind, "pick": rng.random(),
                    "creator": rng.randrange(n_clients),
                    "owner": rng.randrange(n_clients),
                    "tampered": t % tamper_every == tamper_every - 1}
            if kind == "create":
                draw.update(id=asset_key(next(fresh)),
                            color=color_name(int(draw["pick"] * self.colors)),
                            size=rng.randint(*SIZES),
                            value=rng.randint(*VALUES))
            draw["nonce"] = rng.randbytes(24).hex()
            yield draw

    def _call(self, draw: dict) -> tuple:
        owner = enrolment_name(draw["owner"], self.orgs)
        kind = draw["kind"]
        if kind == "create":
            return "CreateAsset", [draw["id"], draw["color"], draw["size"],
                                   owner, draw["value"]]
        if kind == "bycolor":
            return "TransferAssetByColor", [
                color_name(int(draw["pick"] * self.colors)), owner]
        asset_id = self.live[int(draw["pick"] * len(self.live))]
        if kind == "delete":
            return "DeleteAsset", [asset_id]
        return "TransferAsset", [asset_id, owner]

    def next_block(self, limit: int) -> list:
        """The next block's candidates: up to `limit` draws, never across
        the end of the load phase, simulated against the state committed
        so far.  [] when the chain is drawn out."""
        room = limit
        if self._taken < self.assets:
            room = min(limit, self.assets - self._taken)
        while len(self._pending) < room:
            draw = next(self._draws, None)
            if draw is None:
                break
            self._pending.append(draw)
        self._formed = [
            dict(self.world.simulate(*self._call(d)), kind=d["kind"],
                 creator=d["creator"], tampered=d["tampered"],
                 nonce=d["nonce"]) for d in self._pending[:room]]
        return self._formed

    def commit_block(self, n: int = None) -> dict:
        """The first `n` candidates (all, when None) are the block:
        -> {"number", "phase", "txs", "codes"}."""
        txs = self._formed[:n]
        phase = "load" if self._taken < self.assets else "run"
        codes = self.world.commit_block(self.number, txs)
        for tx, code in zip(txs, codes):
            if code != VALID:
                continue
            key = tx["args"][0]
            if tx["kind"] == "create":
                self._where[key] = len(self.live)
                self.live.append(key)
            elif tx["kind"] == "delete":
                last = self.live.pop()
                if last != key:
                    self.live[self._where[key]] = last
                    self._where[last] = self._where[key]
                del self._where[key]
        del self._pending[:len(txs)]
        self._taken += len(txs)
        self._formed = []
        block = {"number": self.number, "phase": phase, "txs": txs,
                 "codes": codes}
        self.number += 1
        return block


def plan_chain(seed: int, assets: int, colors: int, run_tx: int,
               block_tx: int, n_clients: int, tamper_every: int,
               orgs=("Org1", "Org2", "Org3")) -> list:
    """The whole chain cut by count alone, `block_tx` a block."""
    chain = Chain(seed, assets, colors, run_tx, n_clients, tamper_every, orgs)
    plan = []
    while chain.next_block(block_tx):
        plan.append(chain.commit_block())
    return plan


def replay_plan(plan: list, upto: int = None) -> Registry:
    """The registry after the plan's blocks numbered <= `upto` (all,
    when None), by the block rule alone: codes are decided again here."""
    world = Registry()
    for block in plan:
        if upto is not None and block["number"] > upto:
            break
        world.commit_block(block["number"], block["txs"])
    return world


def counts(plan_blocks) -> dict:
    """What the cell wants to see happen in every run, and what the
    ledger's range counters must read, over the given block plans by the
    model's own codes."""
    out = dict.fromkeys(
        ("bycolor", "bycolor_at_gate", "bycolor_valid", "ranges_replayed",
         "ranges_held", "phantoms", "phantoms_by_create",
         "phantoms_by_delete", "bycolor_mvcc", "bycolor_mvcc_by_transfer",
         "bycolor_mvcc_by_delete", "range_results_replayed",
         "largest_range", "creates", "deletes", "envelope_source_txs"), 0)
    for block in plan_blocks:
        ranged = False
        for tx, code in zip(block["txs"], block["codes"]):
            out["creates"] += code == VALID and tx["kind"] == "create"
            out["deletes"] += code == VALID and tx["kind"] == "delete"
            if not tx["ranges"]:
                continue
            out["bycolor"] += 1
            out["largest_range"] = max(
                out["largest_range"], *(len(r["reads"]) for r in tx["ranges"]))
            if code == POLICY_FAILURE:
                continue
            ranged = True
            out["bycolor_at_gate"] += 1
            out["bycolor_valid"] += code == VALID
            if code == MVCC_CONFLICT:
                out["bycolor_mvcc"] += 1
                out["bycolor_mvcc_by_transfer"] += tx["cause"] in (
                    "transfer", "bycolor")
                out["bycolor_mvcc_by_delete"] += tx["cause"] == "delete"
                continue
            out["ranges_replayed"] += len(tx["ranges"])
            out["ranges_held"] += code == VALID
            out["range_results_replayed"] += tx["replayed"]
            if code == PHANTOM_CONFLICT:
                out["phantoms"] += 1
                out["phantoms_by_create"] += tx["cause"] == "create"
                out["phantoms_by_delete"] += tx["cause"] == "delete"
        if ranged:
            out["envelope_source_txs"] += len(block["txs"])
    return out


# -- envelopes -----------------------------------------------------------------

def rwset_of(tx: dict, chaincode: str):
    """The transaction's read-write set as the protocol's TxRwSet."""
    from fabric_tpu.protocol import KVRead, KVWrite, NsRwSet, TxRwSet, Version
    from fabric_tpu.protocol.types import RangeQueryInfo

    def reads(pairs):
        return tuple(KVRead(k, None if v is None else Version(*v))
                     for k, v in pairs)
    return TxRwSet((NsRwSet(
        chaincode, reads=reads(tx["reads"]),
        writes=tuple(KVWrite(k, is_delete=True) if v is None
                     else KVWrite(k, v.encode()) for k, v in tx["writes"]),
        range_queries=tuple(
            RangeQueryInfo(r["start"], r["end"], r["exhausted"],
                           reads(r["reads"])) for r in tx["ranges"])),))


def build_envelopes(txs: list, channel: str, chaincode: str,
                    endorsers: list, creators: list) -> list:
    """The transactions as serialized, endorsed, signed envelopes, in
    order.  Every endorser signs; a tampered one has a byte of its
    second endorsement's signature flipped."""
    from fabric_tpu.protocol import (ChaincodeAction, Endorsement,
                                     Transaction, TransactionAction, build)
    from fabric_tpu.protocol.types import TX_ENDORSER
    from gen.backlog import flip_last_byte
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
        ends = [build.endorse(ta, e) for e in endorsers]
        if tx["tampered"]:
            ends[1] = Endorsement(ends[1].endorser,
                                  flip_last_byte(ends[1].signature))
        ta = TransactionAction(ta.proposal_hash, ta.action, tuple(ends))
        data.append(build.signed_envelope(
            TX_ENDORSER, channel, Transaction((ta,)).to_dict(), creator,
            nonce=nonce).serialize())
    return data


_IDENTITIES = {}                 # per worker process: loaded once


def worker_build(deployment_file: str, channel: str, chaincode: str,
                 txs: list) -> list:
    """`build_envelopes` as a pool's task: a spawned worker loads the
    deployment's identities on its first chunk."""
    from gen.backlog import load_identities
    if deployment_file not in _IDENTITIES:
        _IDENTITIES[deployment_file] = load_identities(deployment_file)
    endorsers, creators = _IDENTITIES[deployment_file]
    return build_envelopes(txs, channel, chaincode, endorsers, creators)


# -- what the program must have -------------------------------------------------

def require_program_support(contract: str) -> None:
    """A program from before the contract, or whose shim has no composite
    keys, cannot run this deployment: said before anything is started."""
    from harness import BenchFailure

    from fabric_tpu.chaincode import stub
    from fabric_tpu.node import peer
    if contract not in peer.DEV_CONTRACTS:
        raise BenchFailure(f"the program has no contract {contract!r}")
    missing = [name for name in ("create_composite_key",
                                 "split_composite_key")
               if not hasattr(stub, name)]
    if not hasattr(stub.ChaincodeStub, "get_state_by_partial_composite_key"):
        missing.append("ChaincodeStub.get_state_by_partial_composite_key")
    if missing:
        raise BenchFailure("the program's shim has no composite keys "
                           f"(chaincode/stub.py lacks {missing})")


# -- the cut ----------------------------------------------------------------------

def form_chain(chain: Chain, build, batch: dict):
    """Yields the chain's blocks as the orderer would cut them: each
    block's candidates — as many as `max_message_count`, simulated
    against the state the last block left — are built into envelopes
    (`build(txs)` -> serialized envelopes, in order) and fed in order to
    a `BlockCutter` under the deployment's `batch`; its first cut is the
    block, and what it did not take is drawn into the next block again.
    A block: `Chain.commit_block`'s plus "reason" ("count" | "bytes" |
    "end": the batch timer's cut at a phase's end) and "data"."""
    from gen.ycsb import Cutter
    limit = int(batch["max_message_count"])
    while True:
        txs = chain.next_block(limit)
        if not txs:
            return
        cutter = Cutter(batch)
        cuts = []
        for raw in build(txs):
            cuts = cutter.ordered(raw)
            if cuts:
                break
        data, reason = (cuts or cutter.flush())[0]
        yield dict(chain.commit_block(len(data)), reason=reason, data=data)


# -- what the judge keeps -----------------------------------------------------------

def asset_digest(asset_id: str, record_text: str) -> bytes:
    """One SHA-256 over an asset's record and its index entry's key and
    value, as a peer that holds both reports it."""
    import hashlib
    color = json.loads(record_text)["color"]
    return hashlib.sha256(
        record_text.encode() + b"|" + index_key(color, asset_id).encode()
        + b"|" + INDEX_VALUE.encode()).digest()


def summary(block: dict) -> dict:
    """What judging a block needs once its envelopes exist: its codes,
    which transactions were tampered, which carry a range, what the cell
    wants to see happen counted by the model's codes, the highest asset
    number it creates or tries to (-1: none), and the VALID transactions'
    effects on the assets, in order: (asset number, its digest | None
    where deleted)."""
    effects = []
    for tx, code in zip(block["txs"], block["codes"]):
        if code != VALID:
            continue
        effects.extend(
            (int(key[len("asset"):]),
             None if value is None else asset_digest(key, value))
            for key, value in tx["writes"] if not key.startswith("\x00"))
    return {"number": block["number"], "phase": block["phase"],
            "reason": block["reason"], "txs": len(block["txs"]),
            "codes": bytes(block["codes"]),
            "tampered": [n for n, tx in enumerate(block["txs"])
                         if tx["tampered"]],
            "ranged": [n for n, tx in enumerate(block["txs"])
                       if tx["ranges"]],
            "counts": counts([block]),
            "highest_id": max([-1] + [int(tx["args"][0][len("asset"):])
                                      for tx in block["txs"]
                                      if tx["kind"] == "create"]),
            "effects": effects}


def digests_after(summaries: list, upto: int, ids: int) -> list:
    """The digest (hex) of asset0..asset<ids - 1> after the blocks
    numbered <= `upto`, None where the model holds no such asset."""
    held = [None] * ids
    for block in summaries:
        if block["number"] > upto:
            break
        for n, digest in block["effects"]:
            held[n] = digest
    return [None if d is None else d.hex() for d in held]
