"""A key-value store as an in-process contract.

Blockbench's `kvstore` (Dinh et al., SIGMOD 2017: the contract its YCSB
macro benchmark drives): `write(key, value)` puts the whole value — a
blind write, no read — `read(key)` returns it, `del(key)` deletes it.
What a value holds is the client's business; YCSB's is one record of
ten fields.

Read-write sets: write 0r/1w, read 1r/0w, del 0r/1w (a delete).
"""

from __future__ import annotations

from fabric_tpu.chaincode.runtime import FuncContract
from fabric_tpu.chaincode.stub import SimulationError


def write(stub, key, value):
    stub.put_state(key.decode(), value)
    return b"ok"


def read(stub, key):
    value = stub.get_state(key.decode())
    if value is None:
        raise SimulationError(f"no such key: {key.decode()}")
    return value


def delete(stub, key):
    stub.del_state(key.decode())
    return b"ok"


def contract() -> FuncContract:
    return FuncContract(**{"write": write, "read": read, "del": delete})
