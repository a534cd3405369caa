"""The process that holds the chip in the enrolled-clients catch-up
cell: `catchup_child.py`'s device peer in library form and its clocked
window, plus what that script has no way to say — the second control,
and what the process itself held at the end.

Speaks JSON lines: events on stdout, commands on stdin.

    python enrolled_child.py <peer.json> <trace 0|1> <trace dir> [fault...]

Faults (controls): `yes_verifier` — a verifier that answers yes to
everything; `msp_blind` — the channel's MSPs call every certificate
chain valid, as a peer that takes identities on somebody else's word
would: revoked and forged creators commit, whatever the verifier says
of their signatures (every one of them is valid).
"""

from __future__ import annotations

import resource
import sys
import time

from catchup_child import break_verifier, command, emit, read_file, window


def memory() -> dict:
    """The process's resident set, bytes: `rss` now (`/proc`'s VmRSS,
    where the kernel gives it) and `peak` (`getrusage`; Linux counts
    it in KiB)."""
    out = {"peak": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    out["rss"] = int(line.split()[1]) * 1024
    except OSError:
        pass
    return out


def blind_msps(node) -> None:
    """The second control: no chain is built, no CRL looked at."""
    for msp in node.bundle_source.current().msps.values():
        msp.inner.validate = lambda ident, at_time=None: None


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
        if "msp_blind" in faults:
            blind_msps(node)
        emit("init", seconds=time.perf_counter() - t0,
             provider=node._provider_status(), memory=memory())

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
                timings = node.provider.warm(rows=cmd["rows"])
                emit("warm", timings=timings,
                     seconds=time.perf_counter() - t1, memory=memory())
            elif cmd["cmd"] == "pilot":
                emit("pilot", block=store(read_file(cmd["block"])),
                     memory=memory())
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
                     bytes=sum(len(b) for b in backlog), memory=memory())
            elif cmd["cmd"] == "go":
                report = window(node, registry, tracing, store, backlog,
                                cmd, trace, trace_dir)
                emit("done", memory=memory(), **report)
                return 0
    finally:
        node.stop()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
