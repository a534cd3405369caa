"""The process that holds the chip in the range-query catch-up cell:
`catchup_child.py`'s device peer in library form and its clocked window,
plus what that script has no command for — a warm-up that names both
P-256 lanes, the load phase replayed in set-up, every asset's record and
index entry read back out of the state database as one SHA-256 an id,
the index counted by one scan of its prefix, the ledger's counters
beside each block, and the second control: a commit that calls every
range query stable.

Speaks JSON lines: events on stdout, commands on stdin.

    python queries_child.py <peer.json> <trace 0|1> <trace dir> [fault...]

Faults (controls): `yes_verifier` — a verifier that answers yes to
everything; `range_blind` — the commit's replay of a recorded range
answers "unchanged" whatever the state holds, as a committer that knew
nothing of phantom reads would: phantoms commit, flags and state differ,
and no verifier's answer can cause or cover it.
"""

from __future__ import annotations

import hashlib
import json
import sys
import time

from catchup_child import break_verifier, command, emit, read_file, window

# the ledger's counters a block moves (absent on a program without them:
# they then read 0 and the readers find nothing)
COUNTS = {"reads": "ledger_mvcc_reads_total",
          "writes": "ledger_state_writes_total",
          "range_queries": "ledger_mvcc_range_queries_total",
          "range_reads": "ledger_mvcc_range_reads_total"}
INDEX_PREFIX = "\x00color~name\x00"
TOP = "\U0010ffff"


def blind_range_replay() -> None:
    """The control: every replayed range "held"."""
    from fabric_tpu.ledger import mvcc
    mvcc._validate_range_query = lambda *args, **kwargs: True


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
        if "range_blind" in faults:
            blind_range_replay()
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
                    "mvcc": phases.span_attrs.get("ledger.mvcc", {}),
                    "flags": bytes(stored.metadata.items[META_TXFLAGS]).hex(),
                    "commit_hash": (node.ledger.commit_hash or b"").hex(),
                    "counts": {k: c1[k] - c0[k] for k in c0}}

        def state(cmd: dict) -> dict:
            """asset0..asset<ids - 1> as the state database holds them:
            one SHA-256 (hex) over record + "|" + the index entry's key
            + "|" + its value each, the value `<absent>` where the asset
            has no entry under its colour; null where the id is absent.
            And how many keys one scan of the index's prefix returns."""
            ns = cmd["namespace"]
            out = []
            for i in range(int(cmd["ids"])):
                key = f"asset{i}"
                record = node.ledger.get_state(ns, key)
                if record is None:
                    out.append(None)
                    continue
                entry = (INDEX_PREFIX + json.loads(record)["color"] + "\x00"
                         + key + "\x00")
                value = node.ledger.get_state(ns, entry)
                out.append(hashlib.sha256(
                    record + b"|" + entry.encode() + b"|"
                    + (b"<absent>" if value is None else value)).hexdigest())
            entries = sum(1 for _ in node.ledger.range_query(
                ns, INDEX_PREFIX, INDEX_PREFIX + TOP))
            return {"digests": out, "index_entries": entries,
                    "height": node.ledger.height}

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
                # the state out
                emit("replayed",
                     blocks=[store(read_file(p)) for p in cmd["blocks"]],
                     state=state(cmd), height=node.ledger.height,
                     jax_imported="jax" in sys.modules)
                return 0
            elif cmd["cmd"] == "load":
                backlog = [read_file(p) for p in cmd["blocks"]]
                emit("loaded", blocks=len(backlog),
                     bytes=sum(len(b) for b in backlog))
            elif cmd["cmd"] == "go":
                emit("done", **window(node, registry, tracing, store, backlog,
                                      cmd, trace, trace_dir))
            elif cmd["cmd"] == "state":
                emit("state", **state(cmd))
                return 0
    finally:
        node.stop()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
