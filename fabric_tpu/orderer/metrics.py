"""The ordering service's series on the process registry (`/metrics`).

Upstream's name (Fabric v2.2 metrics reference) wherever upstream has
the metric, this repo's naming otherwise; every series carries the
label `channel`.  Always on: each is one addition or observation of a
plain float, once per envelope, per cut or per committed entry, made
where the work happens (blockcutter / consensus / broadcast / deliver).
PERF.md §3 names the reader of each.
"""

from __future__ import annotations

from fabric_tpu.ops_plane import registry

# the wait a batch timer adds is seconds, a block write milliseconds
_FILL_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 2.5,
                 5.0, 10.0, float("inf"))

block_fill = registry.histogram(
    "blockcutter_block_fill_duration",
    "seconds from the first envelope of a batch enqueued to its cut",
    buckets=_FILL_BUCKETS)
cuts = registry.counter(
    "blockcutter_cut_total",
    "batches cut, by reason: timer, count, bytes, oversize, config")

validate = registry.histogram(
    "broadcast_validate_duration",
    "seconds in the channel's message processor, per envelope")
enqueue = registry.histogram(
    "broadcast_enqueue_duration",
    "seconds in chain.order / .configure (chain-lock wait included), "
    "per envelope")
processed = registry.counter(
    "broadcast_processed_count", "envelopes broadcast, by status")

is_leader = registry.gauge(
    "consensus_etcdraft_is_leader", "1 while this node leads the channel")
leader_changes = registry.counter(
    "consensus_etcdraft_leader_changes",
    "changes of the leader this node knows of")
proposal_failures = registry.counter(
    "consensus_etcdraft_proposal_failures",
    "cut batches lost: not proposed (deposed leader) or their entry "
    "overwritten by another term's")
proposals_received = registry.counter(
    "consensus_etcdraft_normal_proposals_received",
    "normal envelopes handed to the chain on the leader")
committed_block = registry.gauge(
    "consensus_etcdraft_committed_block_number",
    "number of the last block this node wrote")
persist = registry.histogram(
    "consensus_etcdraft_data_persist_duration",
    "seconds of WAL append + fsync, per drain of the raft node that "
    "wrote anything")
commit = registry.histogram(
    "consensus_etcdraft_commit_duration",
    "seconds from a batch proposed to its entry applied, on the leader",
    buckets=_FILL_BUCKETS)
append_bytes = registry.counter(
    "consensus_etcdraft_append_bytes_total",
    "entry bytes in the MSG_APP sent to a follower, re-sends included")

block_write = registry.histogram(
    "orderer_block_write_seconds",
    "seconds to create, sign and write one block")

deliver_sent = registry.counter(
    "deliver_blocks_sent", "blocks sent on deliver streams")
deliver_received = registry.counter(
    "deliver_requests_received", "deliver requests received")
deliver_completed = registry.counter(
    "deliver_requests_completed", "deliver requests ended, by success")
