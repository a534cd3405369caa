"""Consenter chains: the ordering state machines.

Reference parity: orderer/consensus/consensus.go Chain interface
(Order/Configure/WaitReady/Start/Halt) and orderer/consensus/solo —
a single-node chain that cuts batches by count/bytes/timeout and hands
them to the block writer.  The Raft-replicated chain lives in
fabric_tpu/orderer/raft.py + RaftChain below it in registrar wiring.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional

from fabric_tpu.ops_plane import tracing
from fabric_tpu.ops_plane.logging import jlog
from fabric_tpu.orderer import metrics
from fabric_tpu.orderer.blockcutter import (
    CUT_CONFIG,
    Batch,
    BatchConfig,
    BlockCutter,
)
from fabric_tpu.orderer.blockwriter import BlockWriter
from fabric_tpu.protocol import Envelope

logger = logging.getLogger("fabric_tpu.orderer.consensus")

# blocks whose trace context a chain remembers for its deliver streams
BLOCK_TRACES = 64


class ChainHaltedError(Exception):
    pass


class Chain:
    """consensus.Chain — what broadcast dispatches into."""

    def order(self, env: Envelope) -> None:
        raise NotImplementedError

    def configure(self, env: Envelope) -> None:
        raise NotImplementedError

    def start(self) -> None:
        pass

    def halt(self) -> None:
        pass

    # -- the block's trace ---------------------------------------------------
    # Every cut roots a trace, `orderer.block`, under the tracer's
    # sampling: the cut has no caller whose trace it could join (the
    # batch timer has none at all, and a count or bytes cut closes many
    # requests' envelopes, not the one that happened to fill it).

    def _cut_made(self, batch: Batch, is_config: bool):
        """Count a cut and open its block's trace: -> the root span (the
        shared no-op with the tracer off), from the batch's first
        enqueue, holding `orderer.batch_fill` and back-linking the
        request traces the envelopes came in under."""
        channel = self.writer.channel_id
        metrics.cuts.add(1, channel=channel, reason=batch.reason)
        metrics.block_fill.observe(batch.t_cut - batch.t_first,
                                   channel=channel)
        root = tracing.tracer.start_span(
            "orderer.block", parent=None, start=batch.t_first,
            attributes={"channel": channel, "reason": batch.reason,
                        "txs": len(batch), "is_config": is_config})
        if root.recording:
            for link in batch.links:
                root.add_link(link, back=True)
            tracing.tracer.record_span(
                "orderer.batch_fill", batch.t_first, batch.t_cut,
                attributes={"reason": batch.reason, "txs": len(batch),
                            "bytes": batch.nbytes},
                parent=root.context)
        return root

    def _write_block(self, batch, is_config: bool, parent, fields=None):
        """Create, sign and write the next block and tell the channel,
        under `orderer.write`: a child of `parent` (a span context; one
        parsed off a raft entry makes this node's fragment of the
        leader's trace), a root of its own without one.  What a deliver
        stream sends beside the block is remembered before the block can
        be read."""
        channel = self.writer.channel_id
        with tracing.tracer.start_span(
                "orderer.write", parent=parent,
                attributes={"channel": channel, "txs": len(batch),
                            "is_config": is_config}) as span:
            t0 = time.perf_counter()
            block = self.writer.create_next_block(batch)
            number = int(block.header.number)
            if fields:
                block.metadata.items.update(fields)
            if span.recording:
                span.set_attribute("block", number)
                if span.context.sampled:
                    traces = self._block_traces
                    traces[number] = tracing.format_traceparent(span.context)
                    while len(traces) > BLOCK_TRACES:
                        traces.popitem(last=False)
            self.writer.write_block(block, is_config=is_config)
            metrics.block_write.observe(time.perf_counter() - t0,
                                        channel=channel)
            metrics.committed_block.set(number, channel=channel)
            self.on_block(block)
        return block

    def block_traceparent(self, number: int) -> Optional[str]:
        """The context a deliver frame carries beside block `number`:
        of this node's `orderer.write` span in the block's trace.  None
        for a block written untraced, or long ago."""
        return self._block_traces.get(number)


class SoloChain(Chain):
    """Single-consenter dev chain (orderer/consensus/solo/consensus.go).

    Envelopes are cut into blocks synchronously by count/bytes; the batch
    timeout is enforced either by `tick(now)` (deterministic tests) or by
    the optional background timer thread started with `start()`.
    Config envelopes always cut the pending batch first and are written
    as single-tx config blocks, mirroring solo's main loop.
    """

    def __init__(self, cutter: BlockCutter, writer: BlockWriter,
                 on_block: Optional[Callable] = None):
        self.cutter = cutter
        self.writer = writer
        self.on_block = on_block or (lambda block: None)
        self._lock = threading.RLock()
        self._halted = False
        self._timer: Optional[threading.Thread] = None
        self._batch_deadline: Optional[float] = None
        self._block_traces: "OrderedDict[int, str]" = OrderedDict()

    # -- Chain interface ----------------------------------------------------

    def order(self, env: Envelope) -> None:
        with self._lock:
            self._check_running()
            batches, pending = self.cutter.ordered(env)
            for batch in batches:
                self._write(batch)
            self._restart_deadline(bool(batches), pending)

    def configure(self, env: Envelope) -> None:
        with self._lock:
            self._check_running()
            pending = self.cutter.cut(CUT_CONFIG)
            if pending:
                self._write(pending)
            self._write(_config_batch(env), is_config=True)
            self._batch_deadline = None

    def tick(self, now: Optional[float] = None) -> bool:
        """Cut the pending batch if the batch timeout expired; returns
        whether a block was written."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._halted or self._batch_deadline is None \
                    or now < self._batch_deadline:
                return False
            batch = self.cutter.cut()
            self._batch_deadline = None
            if not batch:
                return False
            self._write(batch)
            return True

    def start(self) -> None:
        if self._timer is not None:
            return
        self._halted = False

        def loop():
            while not self._halted:
                time.sleep(self.cutter.config.batch_timeout_s / 4)
                self.tick()

        self._timer = threading.Thread(target=loop, daemon=True)
        self._timer.start()

    def halt(self) -> None:
        with self._lock:
            self._halted = True
        if self._timer is not None:
            self._timer.join(timeout=2)
            self._timer = None

    # -- internals ----------------------------------------------------------

    def _check_running(self) -> None:
        if self._halted:
            raise ChainHaltedError("chain is halted")

    def _restart_deadline(self, cut_happened: bool, pending: bool) -> None:
        """The batch timer restarts on every cut (the reference resets its
        timer whenever a batch is cut); it only keeps running for an
        already-pending batch when nothing was cut."""
        if not pending:
            self._batch_deadline = None
        elif cut_happened or self._batch_deadline is None:
            self._batch_deadline = (time.monotonic()
                                    + self.cutter.config.batch_timeout_s)

    def _write(self, batch: Batch, is_config: bool = False) -> None:
        with self._cut_made(batch, is_config) as root:
            self._write_block(batch, is_config, root.context)


def _config_batch(env: Envelope) -> Batch:
    """A config envelope is always a batch of its own."""
    raw = env.serialize()
    link = tracing.tracer.current_trace_id()
    return Batch([raw], CUT_CONFIG, len(raw), links=[link] if link else ())


# ---------------------------------------------------------------------------
# Raft-replicated chain (orderer/consensus/etcdraft/chain.go equivalent)

META_RAFT_INDEX = "raft_index"


def make_entry_signer(signer):
    """Build a RaftNode entry_signer from a consenter signing identity:
    returns (serialized identity, signature over the canonical entry
    bytes) — what EntryVerifier checks on the receiving side."""
    from fabric_tpu.orderer import raft as raftmod
    raw = signer.serialize()

    def sign(term: int, index: int, data: bytes, kind: str):
        return raw, signer.sign(
            raftmod.entry_signed_bytes(term, index, data, kind))

    return sign


class _Proposal(NamedTuple):
    """A batch the leader proposed and has not seen applied."""
    term: int               # the term it was proposed in
    txs: int
    t_propose: float        # perf_counter
    root: object            # its block trace's open spans: `orderer.block`
    consensus: object       # and `orderer.consensus`


class RaftChain(Chain):
    """Crash-fault-tolerant ordering over fabric_tpu.orderer.raft.

    Design deviation from the reference (etcdraft/chain.go:378,782): the
    leader proposes the *cut batch* (serialized envelopes + config flag),
    not a pre-built block; every node deterministically builds + signs the
    block at apply time.  Same total order => same block numbers and data
    hashes on every node, with no in-flight block-number tracking and no
    leader-change block reconstruction.

    Replay idempotency: each block records the raft entry index that
    produced it; on restart, re-delivered committed entries at or below
    the recovered index are skipped (the ledger *is* the applied-state
    checkpoint, mirroring SURVEY.md §5 checkpoint/resume).
    """

    def __init__(self, node, cutter: BlockCutter, writer: BlockWriter,
                 on_block: Optional[Callable] = None, entry_signer=None,
                 on_conf: Optional[Callable] = None):
        from fabric_tpu.utils import serde as _serde
        self._serde = _serde
        self.node = node
        self.cutter = cutter
        self.writer = writer
        # membership hook: called with the decoded conf payload
        # ({"op","node",...}) each time a membership entry COMMITS.  Conf
        # entries do not advance _last_applied, so they replay on restart
        # — the hook MUST be idempotent.
        self.on_conf = on_conf or (lambda conf: None)
        # consenter entry signing (round 14): install the signer on the
        # raft node so every local append — proposals, conf changes, the
        # new-leader no-op — carries (proposer, sig); the cluster service
        # enforces the chain on channels whose own chain signs
        if entry_signer is not None:
            node.entry_signer = entry_signer
        self.on_block = on_block or (lambda block: None)
        self._lock = threading.RLock()
        self._halted = False
        self._batch_deadline: Optional[float] = None
        self._block_traces: "OrderedDict[int, str]" = OrderedDict()
        # raft index -> what this node proposed there as leader
        self._open: Dict[int, _Proposal] = {}
        self._was_leader = False
        self._seen_leader: Optional[int] = None
        self._last_applied = self._recover_applied_index()
        self.catchup_target: Optional[dict] = None  # set on snapshot install
        self._held_entries: List = []  # entries arriving while catching up
        node.snapshot_data = self._snapshot_state
        # crash window: snapshot installed but catch_up never ran.  The
        # node's persisted snapshot state knows the cluster ledger height;
        # if our ledger is shorter we must re-enter catch-up, else entries
        # after snap_index would land at wrong block numbers.
        if node.snap_data:
            self._maybe_enter_catchup(node.snap_data, fallback_index=0)

    def _recover_applied_index(self) -> int:
        lg = self.writer.ledger
        if lg.height == 0:
            return 0
        tip = lg.get_by_number(lg.height - 1)
        return int(tip.metadata.items.get(META_RAFT_INDEX, 0))

    def _snapshot_state(self, index: int) -> bytes:
        # called from node.maybe_compact() AFTER process_ready applied all
        # entries <= index, so _last_applied/height describe state AT index
        return self._serde.encode({
            "raft_index": self._last_applied,
            "height": self.writer.height,
        })

    # -- Chain interface ----------------------------------------------------

    def order(self, env: Envelope) -> None:
        with self._lock:
            self._check_running()
            self._check_leader()  # followers redirect Submit (chain.go:378)
            metrics.proposals_received.add(1, channel=self.writer.channel_id)
            batches, pending = self.cutter.ordered(env)
            for batch in batches:
                self._propose(batch, is_config=False)
            self._restart_deadline(bool(batches), pending)

    def configure(self, env: Envelope) -> None:
        with self._lock:
            self._check_running()
            self._check_leader()
            pending = self.cutter.cut(CUT_CONFIG)
            if pending:
                self._propose(pending, is_config=False)
            self._propose(_config_batch(env), is_config=True)
            self._batch_deadline = None

    def _check_leader(self) -> None:
        from fabric_tpu.orderer import raft as raftmod
        if self.node.role != raftmod.LEADER:
            raise raftmod.NotLeaderError(self.node.leader_id)

    def propose_membership(self, op: str, node_id: int, **meta) -> int:
        """Propose an add/remove-consenter config entry through the log
        (leader only).  Returns the entry's raft index; the change takes
        effect — on every replica, including this one — when the entry
        commits and on_conf fires."""
        with self._lock:
            self._check_running()
            self._check_leader()
            return self.node.propose_conf(op, node_id, **meta)

    def transfer_leadership(self, to: int) -> bool:
        """Ask raft to hand leadership to `to` (drain path)."""
        with self._lock:
            return self.node.transfer_leadership(to)

    def tick_batch(self, now: Optional[float] = None) -> bool:
        """Cut + propose the pending batch when the batch timeout fires."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._halted or self._batch_deadline is None \
                    or now < self._batch_deadline:
                return False
            batch = self.cutter.cut()
            self._batch_deadline = None
            if not batch:
                return False
            from fabric_tpu.orderer import raft as raftmod
            try:
                self._propose(batch, is_config=False)
            except raftmod.NotLeaderError as exc:
                # deposed between the deadline being set and firing: the
                # batch is discarded (clients retry against the new leader)
                self._batch_lost("not_leader", len(batch),
                                 leader=exc.leader_id or 0)
                return False
            return True

    def _batch_lost(self, why: str, txs: int, **fields) -> None:
        """A cut batch that will never be a block: counted, and logged
        once per event."""
        channel = self.writer.channel_id
        metrics.proposal_failures.add(1, channel=channel)
        jlog(logger, "orderer.batch_lost", level=logging.WARNING,
             channel=channel, why=why, txs=txs, term=self.node.term,
             **fields)

    def halt(self) -> None:
        with self._lock:
            self._halted = True

    def _check_running(self) -> None:
        if self._halted:
            raise ChainHaltedError("chain is halted")

    _restart_deadline = SoloChain._restart_deadline

    # -- raft plumbing -------------------------------------------------------
    # RaftNode has no internal locking; every access — propose (via
    # order/configure), transport-driven step, clock-driven tick, and the
    # ready drain — must hold self._lock.  Transports call chain.step, not
    # node.step.

    def step(self, msg) -> None:
        with self._lock:
            self.node.step(msg)

    def tick(self) -> None:
        """Advance the raft election/heartbeat clock."""
        with self._lock:
            self.node.tick()

    def _propose(self, batch: Batch, is_config: bool) -> None:
        """Hand a cut batch to raft.  The block's trace is rooted here,
        at the cut, and its context rides in the entry (`tp`; a context,
        never a time), so that the followers' writes join it; the root
        and `orderer.consensus` stay open until the entry is applied on
        this node (`_settle`)."""
        root = self._cut_made(batch, is_config)
        payload = {"cfg": is_config, "batch": list(batch)}
        if root.recording and root.context.sampled:
            payload["tp"] = tracing.format_traceparent(root.context)
        try:
            with tracing.tracer.start_span(
                    "orderer.cut_propose", parent=root.context,
                    attributes={"batch_size": len(batch),
                                "is_config": is_config}):
                index = self.node.propose(self._serde.encode(payload))
        except BaseException:
            root.end(status="ERROR")
            raise
        self._open[index] = _Proposal(
            self.node.term, len(batch), time.perf_counter(), root,
            tracing.tracer.start_span("orderer.consensus",
                                      parent=root.context,
                                      attributes={"index": index}))

    def _settle(self, entry) -> Optional[_Proposal]:
        """-> what this node proposed at the index of a committed entry,
        if this is that entry.  One of another term took the slot: the
        proposal was lost to a leader change."""
        p = self._open.pop(entry.index, None)
        if p is None:
            return None
        if p.term == entry.term:
            return p
        p.consensus.end(status="ERROR")
        p.root.set_attribute("lost_to_term", entry.term)
        p.root.end(status="ERROR")
        self._batch_lost("overwritten", p.txs, index=entry.index,
                         proposed_in=p.term, committed_in=entry.term)
        return None

    def _observe_ready(self, r) -> None:
        """The raft node's drain, counted: who leads, what persisting
        cost, what went to each follower."""
        from fabric_tpu.orderer import raft as raftmod
        channel = self.writer.channel_id
        leading = self.node.role == raftmod.LEADER
        if leading != self._was_leader:
            self._was_leader = leading
            metrics.is_leader.set(1.0 if leading else 0.0, channel=channel)
        # as upstream counts: a leader known after another, or after
        # none — so the same node elected again in a later term counts
        leader = self.node.leader_id
        if leader != self._seen_leader:
            self._seen_leader = leader
            if leader is not None:
                metrics.leader_changes.add(1, channel=channel)
        if r.persist_s is not None:
            metrics.persist.observe(r.persist_s, channel=channel)
        for m in r.messages:
            if m.entries and m.type == raftmod.MSG_APP:
                metrics.append_bytes.add(
                    sum(len(e.data) for e in m.entries),
                    channel=channel, to=str(m.to))

    def process_ready(self):
        """Drain the raft node: apply committed entries to the ledger and
        return the outbound messages for the cluster transport to send."""
        from fabric_tpu.orderer import raft as raftmod
        with self._lock:
            r = self.node.take_ready()
            self._observe_ready(r)
            if r.lost_leadership:
                # discard the pending batch and stop the batch timer
                # (reference etcdraft chain.go:604-607 becomeFollower):
                # stale envelopes must not be proposed if leadership is
                # later regained, and the timer path must not fire.
                dropped = self.cutter.cut()
                if dropped:
                    self._batch_lost("lost_leadership", len(dropped))
                self._batch_deadline = None
            for e in r.committed:
                proposal = self._settle(e) if self._open else None
                if e.kind == raftmod.ENTRY_SNAPSHOT:
                    self._on_snapshot_entry(e)
                elif e.kind == raftmod.ENTRY_NORMAL:
                    self._apply(e, proposal)
                elif e.kind == raftmod.ENTRY_CONF:
                    # the raft-internal effect (node set change) already
                    # ran inside take_ready; surface the full payload so
                    # the owning node can follow — consenter identity
                    # maps, transport addresses, persisted channel state
                    try:
                        self.on_conf(self._serde.decode(e.data))
                    except Exception:
                        logger.exception("membership conf hook failed")
            # compact only after the entries above hit the ledger — and
            # never while catching up, when _last_applied/height lag the
            # raft applied index and would bake stale state into the snap
            if self.catchup_target is None:
                self.node.maybe_compact()
        return r

    def _apply(self, entry, proposal: Optional[_Proposal] = None) -> None:
        """Write the block of a committed entry.  `proposal`: this node
        proposed it as leader, and its block trace is still open."""
        if proposal is not None:
            # consensus ends where the entry is handed over to be applied
            now = time.perf_counter()
            metrics.commit.observe(now - proposal.t_propose,
                                   channel=self.writer.channel_id)
            proposal.consensus.end(end_time=now)
        try:
            self._apply_entry(entry, proposal)
        finally:
            if proposal is not None:
                proposal.root.end()

    def _apply_entry(self, entry, proposal: Optional[_Proposal]) -> None:
        if self.catchup_target is not None:
            # ledger is behind the snapshot: hold entries until the missing
            # blocks arrive (replication), else block numbers would skew
            self._held_entries.append(entry)
            return
        if entry.index <= self._last_applied:
            return  # replayed on restart; ledger already has the block
        if not entry.data:
            # leader-change no-op entry (raft _become_leader): no block
            self._last_applied = entry.index
            return
        d = self._serde.decode(entry.data)
        if proposal is not None:
            parent = proposal.root.context
        else:
            # a follower (or a leader that restarted): the context the
            # leader put in the entry, if its tracer did
            parent = tracing.tracer.context_from(d.get("tp"))
        self._write_block(d["batch"], d["cfg"], parent,
                          {META_RAFT_INDEX: entry.index})
        self._last_applied = entry.index

    def _on_snapshot_entry(self, e) -> None:
        """A snapshot was installed: this node is behind the compacted log
        and must catch up its *ledger* from a peer (the reference's
        orderer/common/cluster/replication.go pull path)."""
        self._maybe_enter_catchup(e.data, fallback_index=e.index)

    def _maybe_enter_catchup(self, state_bytes: bytes,
                             fallback_index: int) -> None:
        """Decode chain snapshot state; if the cluster ledger is ahead of
        ours, enter catch-up.  Tolerates opaque/non-dict app state (raw
        RaftNode snapshots) by doing nothing."""
        try:
            state = self._serde.decode(state_bytes) if state_bytes else {}
        except ValueError:
            return
        if not isinstance(state, dict):
            return
        self._last_applied = max(
            self._last_applied, int(state.get("raft_index", fallback_index)))
        if int(state.get("height", 0)) > self.writer.ledger.height:
            self.catchup_target = state

    def catch_up(self, blocks) -> None:
        """Install blocks fetched from a peer (replication.go equivalent)."""
        with self._lock:
            for block in blocks:
                if block.header.number < self.writer.ledger.height:
                    continue
                self.writer.ledger.add_block(block)
            self.writer.resync()
            # the installed tip's raft index supersedes the snapshot's, or
            # re-delivered entries would re-apply as duplicate blocks
            self._last_applied = max(self._last_applied,
                                     self._recover_applied_index())
            if self.catchup_target and \
                    self.writer.ledger.height >= self.catchup_target["height"]:
                self.catchup_target = None
                held, self._held_entries = self._held_entries, []
                for entry in held:
                    self._apply(entry)
