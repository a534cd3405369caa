"""Hand blocks to a stopped peer's committer, in a process of its own.

    python -m fabric_tpu.testing.replay <peer.json> <block file>...
        [--warm-rows 384]

Builds the peer from its node config in library form — same provider,
same channel wiring, the ledger its data_dir already holds — without
starting any of its servers or loops, then gives each block (one
serialized Block per file, in order) to the channel's commit path the
way the deliver loop does: `wire.parse_block` → `coordinator.
store_block`.  Prints one JSON line: per-block final tx-filter flags and
timings, the resulting height and commit hash, and the provider's own
report (`/state`'s provider section).

What it is for: a block shape the ordering service does not cut under
the channel's batch config (the 10,000-tx BASELINE block), validated by
the real committer of a device peer and, from the same file, by
software-provider peers in host-only processes.  The peer's own process
must be stopped first: a ledger has one writer and a chip one owner.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def replay(cfg: dict, block_paths, warm_rows=(), on_block=None) -> dict:
    """`on_block(node, i, store)`, when given, stands around each block:
    it is called with the node, the block's index and `store()`, which
    parses and commits that block and returns its record — so a caller
    can put a clock, a profiler or a trace around whole blocks, or stop
    early by raising StopIteration, without a copy of this loop."""
    from fabric_tpu.node.peer import PeerNode
    from fabric_tpu.protocol import wire
    from fabric_tpu.protocol.types import META_TXFLAGS

    t0 = time.perf_counter()
    node = PeerNode(cfg, data_dir=cfg["data_dir"])
    try:
        init_s = time.perf_counter() - t0
        warm = node.provider.warm(rows=warm_rows) if warm_rows else {}
        warm_s = time.perf_counter() - t0 - init_s
        blocks = []

        def store(path) -> dict:
            with open(path, "rb") as f:
                block = wire.parse_block(f.read())
            t1 = time.perf_counter()
            node.coordinator.store_block(block)
            seconds = time.perf_counter() - t1
            number = int(block.header.number)
            stored = node.ledger.blockstore.get_by_number(number)
            return {
                "number": number,
                "flags": bytes(stored.metadata.items[META_TXFLAGS]).hex(),
                "seconds": round(seconds, 3)}

        for i, path in enumerate(block_paths):
            if on_block is None:
                blocks.append(store(path))
                continue
            try:
                blocks.append(on_block(node, i, lambda p=path: store(p)))
            except StopIteration:
                break
        return {"mspid": node.mspid,
                "init_s": round(init_s, 3),
                "warm": warm, "warm_s": round(warm_s, 3),
                "blocks": blocks,
                "height": node.ledger.height,
                "commit_hash": (node.ledger.commit_hash or b"").hex(),
                "jax_imported": "jax" in sys.modules,
                "provider": node._provider_status()}
    finally:
        node.stop()


def _buckets(text: str):
    return [int(b) for b in text.split(",") if b]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="fabric-tpu-replay")
    ap.add_argument("config", help="the stopped peer's node JSON")
    ap.add_argument("blocks", nargs="+",
                    help="files holding one serialized Block each")
    ap.add_argument("--warm-rows", default="", type=_buckets,
                    help="rows-lane buckets to dispatch once first")
    args = ap.parse_args(argv)
    from fabric_tpu.config.localconfig import load_node_config
    cfg = load_node_config(args.config, "peer")
    report = replay(cfg, args.blocks, args.warm_rows)
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
