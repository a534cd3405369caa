"""SmallBank as an in-process contract.

The schema and procedures of Alomari, Cahill, Fekete, Röhm (ICDE 2008)
with H-Store's SendPayment, under the chaincode function names of
Hyperledger caliper-benchmarks' `smallbank` scenario.  A customer is two
keys, `savings_<id>` and `checking_<id>`, each a decimal ASCII balance
(the schema's two balance tables; the customer's name is taken and not
kept).  Every function rejects an unknown account.

Read-write sets: transact_savings 1r/1w, deposit_checking 1r/1w,
send_payment 2r/2w, write_check 2r/1w, amalgamate 3r/3w, query 2r/0w,
create_account 2r/2w.
"""

from __future__ import annotations

from fabric_tpu.chaincode.runtime import FuncContract
from fabric_tpu.chaincode.stub import SimulationError


def savings_key(account: str) -> str:
    return "savings_" + account


def checking_key(account: str) -> str:
    return "checking_" + account


def _amount(raw: bytes) -> int:
    try:
        return int(raw)
    except ValueError:
        raise SimulationError(f"not an amount: {raw!r}")


def _balance(stub, key: str) -> int:
    raw = stub.get_state(key)
    if raw is None:
        raise SimulationError(f"no such account: {key}")
    return int(raw)


def _put(stub, key: str, balance: int) -> None:
    stub.put_state(key, str(balance).encode())


def create_account(stub, account, name, checking, savings):
    acct = account.decode()
    opened = [_amount(checking), _amount(savings)]
    held = [stub.get_state(checking_key(acct)),
            stub.get_state(savings_key(acct))]
    if any(v is not None for v in held):
        raise SimulationError(f"account {acct} exists")
    _put(stub, checking_key(acct), opened[0])
    _put(stub, savings_key(acct), opened[1])
    return b"created"


def transact_savings(stub, amount, account):
    key = savings_key(account.decode())
    balance = _balance(stub, key) + _amount(amount)
    if balance < 0:
        raise SimulationError("insufficient savings")
    _put(stub, key, balance)
    return str(balance).encode()


def deposit_checking(stub, amount, account):
    v = _amount(amount)
    if v < 0:
        raise SimulationError("negative deposit")
    key = checking_key(account.decode())
    balance = _balance(stub, key) + v
    _put(stub, key, balance)
    return str(balance).encode()


def send_payment(stub, amount, dst, src):
    v = _amount(amount)
    if v < 0:
        raise SimulationError("negative payment")
    src_key, dst_key = checking_key(src.decode()), checking_key(dst.decode())
    if src_key == dst_key:
        raise SimulationError("payment to the same account")
    have, theirs = _balance(stub, src_key), _balance(stub, dst_key)
    if have < v:
        raise SimulationError("insufficient funds")
    _put(stub, src_key, have - v)
    _put(stub, dst_key, theirs + v)
    return b"sent"


def write_check(stub, amount, account):
    v = _amount(amount)
    if v < 0:
        raise SimulationError("negative check")
    acct = account.decode()
    savings = _balance(stub, savings_key(acct))
    checking = _balance(stub, checking_key(acct))
    # an overdraft costs one more (the schema's penalty)
    checking -= v + 1 if savings + checking < v else v
    _put(stub, checking_key(acct), checking)
    return str(checking).encode()


def amalgamate(stub, dst, src):
    s, d = src.decode(), dst.decode()
    if s == d:
        raise SimulationError("amalgamate into the same account")
    moved = _balance(stub, savings_key(s)) + _balance(stub, checking_key(s))
    total = _balance(stub, checking_key(d)) + moved
    _put(stub, savings_key(s), 0)
    _put(stub, checking_key(s), 0)
    _put(stub, checking_key(d), total)
    return str(total).encode()


def query(stub, account):
    acct = account.decode()
    return b"%d,%d" % (_balance(stub, savings_key(acct)),
                       _balance(stub, checking_key(acct)))


def contract() -> FuncContract:
    return FuncContract(create_account=create_account,
                        transact_savings=transact_savings,
                        deposit_checking=deposit_checking,
                        send_payment=send_payment, write_check=write_check,
                        amalgamate=amalgamate, query=query)
