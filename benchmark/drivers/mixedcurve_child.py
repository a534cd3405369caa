"""The process that holds the chip in a mixed-curve catch-up cell:
`catchup_child.py`'s device peer in library form and its clocked
window, plus what that script has no way to say — a warm-up that names
both kernel families (`rows`, `ed25519_rows`), and the second control:
a verifier whose Ed25519 lanes alone answer yes.

Speaks JSON lines: events on stdout, commands on stdin.

    python mixedcurve_child.py <peer.json> <trace 0|1> <trace dir> [fault...]
"""

from __future__ import annotations

import sys
import time

from catchup_child import break_verifier, command, emit, read_file, window


def break_ed25519(provider) -> None:
    """The second control: every Ed25519 item is answered yes, whatever
    the device said; the P-256 lanes are left as they are."""
    import numpy as np
    verify, verify_async = provider.batch_verify, provider.batch_verify_async

    def ed25519_of(items):
        return np.fromiter((it.scheme == "ed25519" for it in items), bool,
                           len(items))

    def yes_async(items):
        items = list(items)
        resolve = verify_async(items)
        return lambda: np.asarray(resolve(), bool) | ed25519_of(items)

    def yes(items):
        items = list(items)
        return np.asarray(verify(items), bool) | ed25519_of(items)

    provider.batch_verify = yes
    provider.batch_verify_async = yes_async


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
        if "yes_ed25519" in faults:
            break_ed25519(node.provider)
        emit("init", seconds=time.perf_counter() - t0,
             provider=node._provider_status())

        def store(raw: bytes) -> dict:
            t1 = time.perf_counter()
            block = wire.parse_block(raw)
            node.coordinator.store_block(block)
            t2 = time.perf_counter()
            number = int(block.header.number)
            stored = node.ledger.blockstore.get_by_number(number)
            return {"number": number, "start": t1, "end": t2,
                    "txs": len(block.data),
                    "flags": bytes(stored.metadata.items[META_TXFLAGS]).hex(),
                    "commit_hash": (node.ledger.commit_hash or b"").hex()}

        while True:
            cmd = command()
            if cmd["cmd"] == "warm":
                t1 = time.perf_counter()
                timings = node.provider.warm(
                    rows=cmd["rows"], ed25519_rows=cmd["ed25519_rows"])
                emit("warm", timings=timings,
                     seconds=time.perf_counter() - t1)
            elif cmd["cmd"] == "pilot":
                emit("pilot", block=store(read_file(cmd["block"])))
            elif cmd["cmd"] == "replay":
                # the plain reference's whole job: blocks in, flags out
                emit("replayed",
                     blocks=[store(read_file(p)) for p in cmd["blocks"]],
                     height=node.ledger.height,
                     jax_imported="jax" in sys.modules)
                return 0
            elif cmd["cmd"] == "load":
                backlog = [read_file(p) for p in cmd["blocks"]]
                emit("loaded", blocks=len(backlog),
                     bytes=sum(len(b) for b in backlog))
            elif cmd["cmd"] == "go":
                emit("done", **window(node, registry, tracing, store, backlog,
                                      cmd, trace, trace_dir))
                return 0
    finally:
        node.stop()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
