"""The process that holds the chip in a SmallBank catch-up cell:
`catchup_child.py`'s device peer in library form and its clocked
window, plus what that script has no command for — replaying the
opening blocks in set-up, reading every account's balances back out of
the state database, and the ledger's counters beside each block.

Speaks JSON lines: events on stdout, commands on stdin.

    python smallbank_child.py <peer.json> <trace 0|1> <trace dir> [fault...]
"""

from __future__ import annotations

import sys
import time

from catchup_child import break_verifier, command, emit, read_file, window

# the ledger's counters a block moves (absent on a program without them:
# they then read 0 and the readers find nothing)
COUNTS = {"reads": "ledger_mvcc_reads_total",
          "writes": "ledger_state_writes_total"}


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

        def balances(cmd: dict) -> dict:
            """Every account's two balances as the state database holds
            them (null where the key is absent), by id from 1."""
            ns, n = cmd["namespace"], int(cmd["accounts"])
            out = {}
            for table in ("savings", "checking"):
                held = [node.ledger.get_state(ns, f"{table}_{i}")
                        for i in range(1, n + 1)]
                out[table] = [None if v is None else int(v) for v in held]
            if "balance_flip" in faults:
                # the control: one balance altered where it is reported
                out["checking"][n // 2] = (out["checking"][n // 2] or 0) + 1
            return out

        backlog = []
        while True:
            cmd = command()
            if cmd["cmd"] == "warm":
                t1 = time.perf_counter()
                timings = node.provider.warm(rows=cmd["rows"])
                emit("warm", timings=timings,
                     seconds=time.perf_counter() - t1)
            elif cmd["cmd"] == "open":
                emit("opened",
                     blocks=[store(read_file(p)) for p in cmd["blocks"]])
            elif cmd["cmd"] == "replay":
                # the plain reference's whole job: blocks in, flags and
                # balances out
                emit("replayed",
                     blocks=[store(read_file(p)) for p in cmd["blocks"]],
                     height=node.ledger.height, balances=balances(cmd),
                     jax_imported="jax" in sys.modules)
                return 0
            elif cmd["cmd"] == "load":
                backlog = [read_file(p) for p in cmd["blocks"]]
                emit("loaded", blocks=len(backlog),
                     bytes=sum(len(b) for b in backlog))
            elif cmd["cmd"] == "go":
                emit("done", **window(node, registry, tracing, store, backlog,
                                      cmd, trace, trace_dir))
            elif cmd["cmd"] == "balances":
                emit("balances", height=node.ledger.height,
                     balances=balances(cmd))
                return 0
    finally:
        node.stop()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
