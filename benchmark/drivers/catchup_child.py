"""The process that holds the chip in a catch-up cell: the device peer
in library form — same provider, same channel wiring, a fresh ledger —
handed a backlog of blocks the way the deliver loop hands them
(`wire.parse_block` -> `coordinator.store_block`), as
`fabric_tpu/testing/replay.py` does, plus what that tool lacks: a
clocked window, a profiler trace over whole blocks, and the spans.

Speaks JSON lines: events on stdout, commands on stdin.

    python catchup_child.py <peer.json> <trace 0|1> <trace dir> [fault...]
"""

from __future__ import annotations

import json
import sys
import time


def emit(event: str, **fields) -> None:
    print(json.dumps(dict(fields, event=event)), flush=True)


def command() -> dict:
    line = sys.stdin.readline()
    if not line:
        raise SystemExit("launcher went away")
    return json.loads(line)


def read_file(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def break_verifier(provider) -> None:
    """The control: a verifier that answers yes to everything."""
    import numpy as np
    verify, verify_async = provider.batch_verify, provider.batch_verify_async

    def yes_async(items):
        resolve = verify_async(items)
        return lambda: np.ones_like(resolve())

    provider.batch_verify = lambda items: np.ones_like(verify(items))
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
                timings = node.provider.warm(rows=cmd["rows"])
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


def window(node, registry, tracing, store, backlog, cmd, trace, trace_dir):
    """Blocks in order until the clock says stop.  A block is started
    while time remains; only blocks that end inside the window count.
    Traced, the profiler watches the blocks `trace_blocks` names, whole."""
    seconds = float(cmd["seconds"])
    trace_from, trace_to = cmd["trace_blocks"] if trace else (-1, -1)
    before = node._provider_status()
    prom_before = registry.expose_text()
    traced = {}
    if trace:
        import jax

    def start_profiler():
        traced["prom_before"] = registry.expose_text()
        # the interpreter's own tracer off: it slows the blocks it
        # watches twofold and the trace's end by tens of seconds;
        # the program's spans say what the host was doing
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        traced["start"] = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.mark"):
            traced["mark"] = time.perf_counter()

    def stop_profiler():
        traced["end"] = time.perf_counter()
        jax.profiler.stop_trace()
        traced["prom_after"] = registry.expose_text()

    blocks = []
    t_go = time.perf_counter()
    for i, raw in enumerate(backlog):
        if time.perf_counter() - t_go >= seconds:
            break
        if i == trace_from:
            start_profiler()
        if trace_from <= i <= trace_to:
            with jax.profiler.TraceAnnotation("bench.store_block"):
                blocks.append(store(raw))
        else:
            blocks.append(store(raw))
        if i == trace_to:
            stop_profiler()
    if "start" in traced and "end" not in traced:
        stop_profiler()          # the clock cut the traced blocks short
    t_end = time.perf_counter()
    spans = []
    if trace:
        for rec in tracing.tracer.recorder.list()["recent"]:
            full = tracing.tracer.recorder.get(rec["trace_id"])
            if full:
                spans.extend(
                    {"name": s["name"], "start": s["start"],
                     "duration_s": s["duration_s"],
                     "trace_id": s["trace_id"]} for s in full["spans"])
    return {"t_go": t_go, "t_end": t_end, "seconds": seconds,
            "blocks": blocks, "exhausted": len(blocks) == len(backlog),
            "before": before, "after": node._provider_status(),
            "prom_before": prom_before,
            "prom_after": registry.expose_text(),
            "traced": traced, "spans": spans}


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
