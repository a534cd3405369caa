"""The process that holds the chip in a YCSB catch-up cell:
`catchup_child.py`'s device peer in library form and its clocked
window, plus what that script has no way to say — a warm-up that names
both P-256 lanes (`generic`, `rows`: a block of ~460 transactions runs
one program of each), the load phase replayed in set-up, every record
read back out of the state database as a SHA-256, the ledger's counters
beside each block, and the second control: one record a block altered
where it is applied.

Speaks JSON lines: events on stdout, commands on stdin.

    python ycsb_child.py <peer.json> <trace 0|1> <trace dir> [fault...]
"""

from __future__ import annotations

import hashlib
import json
import sys
import time

from catchup_child import break_verifier, command, emit, read_file, window

# the ledger's counters a block moves (absent on a program without them:
# they then read 0 and the readers find nothing)
COUNTS = {"writes": "ledger_state_writes_total",
          "write_bytes": "ledger_state_write_bytes_total"}


def flip_applied_records(statedb) -> None:
    """The control: the last write of every block's update batch has
    its first byte altered on its way into the state database.  Flags
    and commit hash do not see it; only the records do.  (A batch holds
    one write a key, so no later write of the block hides it, and the
    last block's stays to the end.)"""
    apply_updates = statedb.apply_updates

    def altered(batch, *args, **kwargs):
        puts = [(k, vv) for k, vv in batch.items() if vv is not None]
        if puts:
            (ns, key), vv = puts[-1]
            value = bytes(vv.value)
            batch.put(ns, key, bytes([value[0] ^ 0x01]) + value[1:],
                      vv.version)
        return apply_updates(batch, *args, **kwargs)

    statedb.apply_updates = altered


def main(argv) -> int:
    cfg_path, trace, trace_dir = argv[0], argv[1] == "1", argv[2]
    faults = set(argv[3:])
    from fabric_tpu.config.localconfig import load_node_config
    from fabric_tpu.node.peer import PeerNode
    from fabric_tpu.ops_plane import registry, tracing
    from fabric_tpu.protocol import wire
    from fabric_tpu.protocol.types import META_TXFLAGS

    t0 = time.perf_counter()
    cfg = load_node_config(cfg_path, "peer")
    node = PeerNode(cfg, data_dir=cfg["data_dir"])
    try:
        if "yes_verifier" in faults:
            break_verifier(node.provider)
        if "record_flip" in faults:
            flip_applied_records(node.ledger.statedb)
        emit("init", seconds=time.perf_counter() - t0,
             provider=node._provider_status())

        def counts() -> dict:
            held = {k: registry.get(name) for k, name in COUNTS.items()}
            return {k: m.total() if m else 0.0 for k, m in held.items()}

        def store(raw: bytes) -> dict:
            c0 = counts()
            t1 = time.perf_counter()
            block = wire.parse_block(raw)
            node.coordinator.store_block(block)
            t2 = time.perf_counter()
            c1 = counts()
            number = int(block.header.number)
            stored = node.ledger.blockstore.get_by_number(number)
            phases = node.ledger.last_stats      # kept with the tracer off
            return {"number": number, "start": t1, "end": t2,
                    "txs": len(block.data),
                    "ledger_s": {"mvcc": phases.state_validation_s,
                                 "block": phases.block_commit_s,
                                 "state": phases.state_commit_s,
                                 "history": phases.history_commit_s},
                    "flags": bytes(stored.metadata.items[META_TXFLAGS]).hex(),
                    "commit_hash": (node.ledger.commit_hash or b"").hex(),
                    "counts": {k: c1[k] - c0[k] for k in c0}}

        def records(cmd: dict) -> list:
            """SHA-256 (hex) of every named key's value as the state
            database holds it, null where the key is absent."""
            with open(cmd["keys"]) as f:
                keys = json.load(f)
            held = (node.ledger.get_state(cmd["namespace"], k) for k in keys)
            return [None if v is None else hashlib.sha256(v).hexdigest()
                    for v in held]

        backlog = []
        while True:
            cmd = command()
            if cmd["cmd"] == "warm":
                t1 = time.perf_counter()
                timings = node.provider.warm(generic=cmd["generic"],
                                             rows=cmd["rows"])
                emit("warm", timings=timings,
                     seconds=time.perf_counter() - t1)
            elif cmd["cmd"] == "open":
                emit("opened",
                     blocks=[store(read_file(p)) for p in cmd["blocks"]])
            elif cmd["cmd"] == "replay":
                # the plain reference's whole job: blocks in, flags and
                # records out
                emit("replayed",
                     blocks=[store(read_file(p)) for p in cmd["blocks"]],
                     height=node.ledger.height, records=records(cmd),
                     jax_imported="jax" in sys.modules)
                return 0
            elif cmd["cmd"] == "load":
                backlog = [read_file(p) for p in cmd["blocks"]]
                emit("loaded", blocks=len(backlog),
                     bytes=sum(len(b) for b in backlog))
            elif cmd["cmd"] == "go":
                emit("done", **window(node, registry, tracing, store, backlog,
                                      cmd, trace, trace_dir))
            elif cmd["cmd"] == "records":
                emit("records", height=node.ledger.height,
                     records=records(cmd))
                return 0
    finally:
        node.stop()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
