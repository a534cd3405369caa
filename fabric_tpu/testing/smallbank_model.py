"""SmallBank in plain Python: the reference the system is compared with.

Balances in a dict, the seven procedures of `chaincode/smallbank.py`
written again from their definitions, the read-write set each leaves,
and a serial block rule — sharing no code with `ledger/`, `committer/`
or the contract.  A transaction of a block is

  ENDORSEMENT_POLICY_FAILURE  if an endorsement of it was tampered with,
  MVCC_READ_CONFLICT          else if a key it read is no longer at the
                              version it read — written by an earlier
                              VALID transaction of the block, or by a
                              block committed since it was simulated,
  VALID                       otherwise, and only then its writes count.

`plan_chain` draws a chain of blocks from a seed (a pure function of its
arguments): the opening blocks create every account, the others mix the
six procedures over accounts drawn from a Zipf law, each transaction
simulated against the state committed before its block, as a block cut
under load holds them.  `build_block` turns one block's plan into a
serialized block of endorsed, signed envelopes.

The money account: the sum of all balances equals what was opened, plus
what was deposited or transacted into savings, less the checks written
and the overdraft penalties, over VALID transactions.
"""

from __future__ import annotations

import bisect
import itertools
import random

VALID, POLICY_FAILURE, MVCC_CONFLICT = 0, 10, 11

# the five modifying procedures, then the read-only one
MIX = ("transact_savings", "deposit_checking", "send_payment", "write_check",
       "amalgamate", "query")
OPENING_BALANCE = 10000          # of each of a customer's two accounts
AMOUNTS = (1, 100)               # uniform, both ends included


class Rejected(Exception):
    """The procedure refuses: the contract raises SimulationError."""


def savings(account) -> str:
    return f"savings_{account}"


def checking(account) -> str:
    return f"checking_{account}"


class Bank:
    """Every balance, the version of its last write, and the money
    account."""

    def __init__(self):
        self.balance = {}        # key -> int
        self.version = {}        # key -> [block, tx number]
        self.money = {"opened": 0, "deposited": 0, "checks": 0,
                      "penalties": 0}

    def held(self, key: str) -> int:
        if key not in self.balance:
            raise Rejected(f"no such account: {key}")
        return self.balance[key]

    def money_balances(self) -> bool:
        m = self.money
        return sum(self.balance.values()) == (
            m["opened"] + m["deposited"] - m["checks"] - m["penalties"])

    def accounts(self, ids) -> dict:
        """{id: [savings, checking]}, None where the key is absent."""
        return {i: [self.balance.get(savings(i)),
                    self.balance.get(checking(i))] for i in ids}

    # -- the procedures: (keys read, {key: new balance}, payload, money) ----

    def create_account(self, account, name, opened_checking, opened_savings):
        c, s = int(opened_checking), int(opened_savings)
        keys = [checking(account), savings(account)]
        if any(k in self.balance for k in keys):
            raise Rejected(f"account {account} exists")
        return keys, {keys[0]: c, keys[1]: s}, "created", {"opened": c + s}

    def transact_savings(self, amount, account):
        key = savings(account)
        after = self.held(key) + int(amount)
        if after < 0:
            raise Rejected("insufficient savings")
        return [key], {key: after}, str(after), {"deposited": int(amount)}

    def deposit_checking(self, amount, account):
        if int(amount) < 0:
            raise Rejected("negative deposit")
        key = checking(account)
        after = self.held(key) + int(amount)
        return [key], {key: after}, str(after), {"deposited": int(amount)}

    def send_payment(self, amount, dst, src):
        v = int(amount)
        if v < 0 or str(dst) == str(src):
            raise Rejected("negative payment, or to the same account")
        have, theirs = self.held(checking(src)), self.held(checking(dst))
        if have < v:
            raise Rejected("insufficient funds")
        return ([checking(src), checking(dst)],
                {checking(src): have - v, checking(dst): theirs + v},
                "sent", {})

    def write_check(self, amount, account):
        v = int(amount)
        if v < 0:
            raise Rejected("negative check")
        s, c = self.held(savings(account)), self.held(checking(account))
        penalty = 1 if s + c < v else 0
        return ([savings(account), checking(account)],
                {checking(account): c - v - penalty}, str(c - v - penalty),
                {"checks": v, "penalties": penalty})

    def amalgamate(self, dst, src):
        if str(dst) == str(src):
            raise Rejected("amalgamate into the same account")
        moved = self.held(savings(src)) + self.held(checking(src))
        total = self.held(checking(dst)) + moved
        return ([savings(src), checking(src), checking(dst)],
                {savings(src): 0, checking(src): 0, checking(dst): total},
                str(total), {})

    def query(self, account):
        s, c = self.held(savings(account)), self.held(checking(account))
        return [savings(account), checking(account)], {}, f"{s},{c}", {}

    # -- simulate, commit --------------------------------------------------

    def simulate(self, fn: str, args) -> dict:
        """What an endorser's simulation of `fn(*args)` against this
        state records: reads with the versions read and writes, both in
        key order, and the response payload.  Raises Rejected."""
        if fn not in MIX and fn != "create_account":
            raise Rejected(f"unknown function {fn!r}")
        read, written, payload, money = getattr(self, fn)(*args)
        return {"fn": fn, "args": [str(a) for a in args],
                "reads": [[k, self.version.get(k)] for k in sorted(read)],
                "writes": [[k, str(written[k])] for k in sorted(written)],
                "payload": payload, "money": money}

    def commit_block(self, number: int, txs: list) -> list:
        """The serial block rule over `txs` (each a `simulate` result,
        `tampered` when an endorsement was altered).  -> the validation
        codes; the VALID transactions' writes are applied."""
        codes = []
        for n, tx in enumerate(txs):
            if tx.get("tampered"):
                codes.append(POLICY_FAILURE)
            elif any(self.version.get(k) != v for k, v in tx["reads"]):
                codes.append(MVCC_CONFLICT)
            else:
                codes.append(VALID)
                self._write(number, n, tx)
        return codes

    def _write(self, number: int, n: int, tx: dict) -> None:
        for key, value in tx["writes"]:
            self.balance[key] = int(value)
            self.version[key] = [number, n]
        for what, amount in tx["money"].items():
            self.money[what] += amount

    def apply(self, number: int, n: int, fn: str, args) -> None:
        """A transaction the chain holds as VALID at (number, n), run
        again here: a valid transaction read what is current, so running
        it against the current state writes what it wrote."""
        self._write(number, n, self.simulate(fn, args))


# -- the generator ---------------------------------------------------------------

def zipf_sampler(n: int, skew: float):
    """draw(rng) -> an id in 1..n with probability ∝ 1 / id**skew."""
    cumulative = list(itertools.accumulate(1.0 / k ** skew
                                           for k in range(1, n + 1)))
    total = cumulative[-1]

    def draw(rng) -> int:
        return min(n, 1 + bisect.bisect_left(cumulative,
                                             rng.random() * total))
    return draw


def draw_call(rng, draw, p_write: float):
    """One procedure call of the mix: (fn, args)."""
    fn = MIX[min(5, int(rng.random() / (p_write / 5.0)))]
    a = draw(rng)
    if fn in ("send_payment", "amalgamate"):
        b = draw(rng)
        while b == a:
            b = draw(rng)
        if fn == "amalgamate":
            return fn, [a, b]                # dst, src
        return fn, [rng.randint(*AMOUNTS), a, b]     # amount, dst, src
    if fn == "query":
        return fn, [a]
    return fn, [rng.randint(*AMOUNTS), a]


def iter_chain(seed: int, accounts: int, blocks: int, block_tx: int,
               n_creators: int, tamper_every: int, skew: float = 1.0,
               p_write: float = 0.95):
    """Yields block plans {"number", "txs", "codes", "redrawn"}: first
    the opening blocks (`create_account` for ids 1..accounts, `block_tx`
    a block, none tampered), then `blocks` blocks of the mix.  A tx is a
    `Bank.simulate` result plus "creator", "tampered", "nonce" (hex)."""
    rng = random.Random(seed)
    bank = Bank()
    draw = zipf_sampler(accounts, skew)
    count = itertools.count()
    number = 0

    def finish(txs, redrawn=0):
        nonlocal number
        for tx in txs:
            tx["creator"] = next(count) % n_creators
            tx["nonce"] = rng.randbytes(24).hex()
        block = {"number": number, "txs": txs, "redrawn": redrawn,
                 "codes": bank.commit_block(number, txs)}
        number += 1
        return block

    for first in range(1, accounts + 1, block_tx):
        yield finish([
            dict(bank.simulate("create_account",
                               [i, f"customer{i}", OPENING_BALANCE,
                                OPENING_BALANCE]), tampered=False)
            for i in range(first, min(first + block_tx, accounts + 1))])
    for _ in range(blocks):
        txs, redrawn = [], 0
        while len(txs) < block_tx:
            try:
                tx = bank.simulate(*draw_call(rng, draw, p_write))
            except Rejected:
                redrawn += 1     # the contract would refuse: drawn again
                continue
            tx["tampered"] = len(txs) % tamper_every == tamper_every - 1
            txs.append(tx)
        yield finish(txs, redrawn)


def plan_chain(*args, **kwargs) -> list:
    return list(iter_chain(*args, **kwargs))


def replay_plan(plan: list, upto: int = None) -> Bank:
    """The bank after the plan's blocks numbered <= `upto` (all, when
    None), by the block rule alone: codes are decided again here."""
    bank = Bank()
    for block in plan:
        if upto is not None and block["number"] > upto:
            break
        bank.commit_block(block["number"], block["txs"])
    return bank


# -- envelopes -----------------------------------------------------------------

def flip_last_byte(sig: bytes) -> bytes:
    """Still DER, no longer a signature of anything."""
    return sig[:-1] + bytes([sig[-1] ^ 0x01])


def rwset_of(tx: dict, chaincode: str):
    """The transaction's read-write set as the protocol's TxRwSet."""
    from fabric_tpu.protocol import KVRead, KVWrite, NsRwSet, TxRwSet, Version
    return TxRwSet((NsRwSet(
        chaincode,
        reads=tuple(KVRead(k, None if v is None else Version(*v))
                    for k, v in tx["reads"]),
        writes=tuple(KVWrite(k, v.encode()) for k, v in tx["writes"])),))


def build_block(block_plan: dict, previous_hash: bytes, channel: str,
                chaincode: str, endorsers: list, creators: list):
    """-> (serialized Block, its header hash).  Every endorser signs
    every transaction; a tampered one has a byte of its second
    endorsement's signature flipped."""
    from fabric_tpu.protocol import (ChaincodeAction, Endorsement,
                                     Transaction, TransactionAction,
                                     block_header_hash, build)
    from fabric_tpu.protocol.types import (TX_ENDORSER, Block, BlockHeader,
                                           BlockMetadata, block_data_hash)
    data = []
    for tx in block_plan["txs"]:
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
    header = BlockHeader(block_plan["number"], previous_hash,
                         block_data_hash(data))
    return (Block(header, data, BlockMetadata()).serialize(),
            block_header_hash(header))
