"""The process that holds the chip in the key-level-endorsement
catch-up cell: `catchup_child.py`'s device peer in library form and its
clocked window, plus what that script has no command for — replaying the
load phase in set-up, reading every asset's record and validation
parameter back out of the state database (one SHA-256 an asset) with the
state's own count of parameters, the counters a block moves beside each
block, and the attributes of the validator's collect and gate spans.

Speaks JSON lines: events on stdout, commands on stdin.

    python sbe_child.py <peer.json> <trace 0|1> <trace dir> [fault...]

Faults (controls): `yes_verifier` — a verifier that answers yes to
everything; `sbe_blind` — the validator built without its look-up of
committed validation parameters, as a peer that knows nothing of
key-level endorsement would be.
"""

from __future__ import annotations

import hashlib
import sys
import time

from catchup_child import break_verifier, command, emit, read_file, window

# the counters a block moves (absent on a program without them: they
# then read 0 and the readers find nothing)
COUNTS = {"reads": "ledger_mvcc_reads_total",
          "writes": "ledger_state_writes_total",
          "sbe_keys": "validator_sbe_keys_total"}
ATTRIBUTED = ("validator.collect", "validator.gate")


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
        if "sbe_blind" in faults:
            node.validator.sbe_lookup = None
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

        def state(cmd: dict) -> dict:
            """asset1..asset<ids> as the state database holds them: one
            SHA-256 over record + "|" + parameter each (null where it
            holds neither), and the state's own count of parameters."""
            ns, n = cmd["namespace"], int(cmd["ids"])
            out = []
            for i in range(1, n + 1):
                key = f"asset{i}"
                record = node.ledger.get_state(ns, key)
                param = node.ledger.get_state(ns + "#meta", key)
                out.append(None if record is None and param is None else
                           hashlib.sha256((record or b"") + b"|"
                                          + (param or b"")).hexdigest())
            return {"digests": out, "height": node.ledger.height,
                    "meta_keys": node.ledger.statedb.meta_keys()[1]}

        def attributed_spans() -> list:
            """The collect and gate spans with their attributes (`tail`,
            `reason`, `sbe_*`), which `window` does not keep."""
            spans = []
            for rec in tracing.tracer.recorder.list()["recent"]:
                full = tracing.tracer.recorder.get(rec["trace_id"])
                for s in (full or {}).get("spans", ()):
                    if s["name"] in ATTRIBUTED:
                        spans.append({"name": s["name"], "start": s["start"],
                                      "duration_s": s["duration_s"],
                                      "attributes": s.get("attributes", {})})
            return spans

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
                report = window(node, registry, tracing, store, backlog,
                                cmd, trace, trace_dir)
                if trace:
                    report["attributed"] = attributed_spans()
                emit("done", **report)
            elif cmd["cmd"] == "state":
                emit("state", **state(cmd))
                return 0
    finally:
        node.stop()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
