"""The process that holds the chip in the private-data catch-up cell:
`catchup_child.py`'s device peer in library form and its clocked window,
plus what that script has no command for — a warm-up that names both
P-256 lanes, the private write-sets a peer of this org was pushed at
endorsement staged in its transient store (`TransientStore.persist`)
before the blocks that carry their hashes, the load phase replayed in
set-up, the hashed state of every collection read back as one SHA-256 a
namespace, the private store as one SHA-256, the coordinator's and the
ledger's counters beside each block, and the second control: a ledger
whose expiry step is skipped.

Speaks JSON lines: events on stdout, commands on stdin.

    python privdata_child.py <peer.json> <trace 0|1> <trace dir> [fault...]

Faults (controls): `yes_verifier` — a verifier that answers yes to
everything; `expiry_blind` — the ledger's commit neither expires a
hashed key nor enters one for expiry, as a ledger that knew nothing of a
collection's block-to-live would: the hashed state keeps every expired
key, a transfer ordered after its appraisal's purge commits, and no
verifier's answer can cause or cover it.
"""

from __future__ import annotations

import hashlib
import json
import sys
import time

from catchup_child import break_verifier, command, emit, read_file, window

# the counters a block moves (absent on a program without them: they then
# read 0 and the readers find nothing)
COUNTS = {"reads": "ledger_mvcc_reads_total",
          "writes": "ledger_state_writes_total",
          "expired_keys": "ledger_pvt_expired_keys_total",
          "decoded": "privdata_decoded_txs_total",
          "purged": "privdata_purged_keys_total",
          "fetches": "privdata_fetch_total"}
SETS = "privdata_txs_total"
EXPIRY_NS = "_pvt_expiry"


def blind_expiry() -> None:
    """The control: no hashed key ever expires."""
    from fabric_tpu.ledger.kvledger import KVLedger
    KVLedger._expire_private = lambda self, batch, block_num: 0


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
    if "expiry_blind" in faults:
        blind_expiry()
    node = PeerNode(cfg, data_dir=cfg["data_dir"])
    try:
        if "yes_verifier" in faults:
            break_verifier(node.provider)
        emit("init", seconds=time.perf_counter() - t0,
             provider=node._provider_status())
        leaked = [0]             # blocks whose bytes hold a private field

        def counts() -> dict:
            held = {k: registry.get(name) for k, name in COUNTS.items()}
            out = {k: m.total() if m else 0.0 for k, m in held.items()}
            sets = registry.get(SETS)
            for result in ("resolved", "missing", "not_member"):
                out["sets_" + result] = (
                    sets.total_by("result").get(result, 0.0) if sets else 0.0)
            return out

        def stage(paths) -> int:
            """The private write-sets this peer was pushed, into its
            transient store: one file a block, [[txid, {collection: {key:
            value | null}}]]; None: the block brought this peer none."""
            staged = 0
            for path in paths:
                if path is None:
                    continue
                for txid, sets in json.loads(read_file(path)):
                    node.transient.persist(txid, node.ledger.height, {
                        (cfg_ns, coll): {k: (None if v is None
                                             else v.encode())
                                         for k, v in kv.items()}
                        for coll, kv in sets.items()})
                    staged += 1
            return staged

        def store(raw: bytes) -> dict:
            leaked[0] += b"appraisedValue" in raw
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
                                 "expiry": getattr(phases, "pvt_expiry_s",
                                                   0.0),
                                 "block": phases.block_commit_s,
                                 "state": phases.state_commit_s,
                                 "history": phases.history_commit_s},
                    "mvcc": phases.span_attrs.get("ledger.mvcc", {}),
                    "flags": bytes(stored.metadata.items[META_TXFLAGS]).hex(),
                    "commit_hash": (node.ledger.commit_hash or b"").hex(),
                    "counts": {k: c1[k] - c0[k] for k in c0}}

        def state(cmd: dict) -> dict:
            """What this peer holds: for every collection its hashed
            namespace as (keys, one SHA-256 over key, value hash and
            version in key order); its private store as ({collection:
            keys}, one SHA-256 over collection, key and value in order);
            the transient store's entries, the private write-sets
            recorded missing, the expiry index's entries."""
            hashed = {}
            for coll in cmd["collections"]:
                h, n = hashlib.sha256(), 0
                for key, vv in node.ledger.range_query(
                        f"{cfg_ns}${coll}", "", ""):
                    h.update(f"{key}:{vv.value.hex()}:{vv.version.block_num}"
                             f":{vv.version.tx_num}\n".encode())
                    n += 1
                hashed[coll] = [n, h.hexdigest()]
            h, held = hashlib.sha256(), {}
            for ns, coll, key in sorted(node.pvt_store.keys()):
                held[coll] = held.get(coll, 0) + 1
                h.update(coll.encode() + b"\x00" + key.encode() + b"\x00"
                         + node.pvt_store.get(ns, coll, key) + b"\n")
            return {"hashed": hashed, "private": [held, h.hexdigest()],
                    "transient": len(node.transient),
                    "missing": len(node.coordinator.missing),
                    "expiry_entries": sum(1 for _ in node.ledger.range_query(
                        EXPIRY_NS, "", "")),
                    "leaked_blocks": leaked[0],
                    "counts": counts(), "height": node.ledger.height}

        cfg_ns = None
        backlog = []
        while True:
            cmd = command()
            cfg_ns = cmd.get("namespace", cfg_ns)
            if cmd["cmd"] == "warm":
                t1 = time.perf_counter()
                timings = node.provider.warm(generic=cmd["generic"],
                                             rows=cmd["rows"])
                emit("warm", timings=timings,
                     seconds=time.perf_counter() - t1)
            elif cmd["cmd"] == "open":
                staged = stage(cmd["private"])
                emit("opened", staged=staged,
                     blocks=[store(read_file(p)) for p in cmd["blocks"]])
            elif cmd["cmd"] == "replay":
                # the plain reference's whole job: blocks in, flags and
                # the state out
                staged = stage(cmd["private"])
                emit("replayed", staged=staged,
                     blocks=[store(read_file(p)) for p in cmd["blocks"]],
                     state=state(cmd), height=node.ledger.height,
                     jax_imported="jax" in sys.modules)
                return 0
            elif cmd["cmd"] == "load":
                staged = stage(cmd["private"])
                backlog = [read_file(p) for p in cmd["blocks"]]
                emit("loaded", blocks=len(backlog), staged=staged,
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
