"""Batching of ordered envelopes into blocks.

Reference parity: orderer/common/blockcutter/blockcutter.go —
`Ordered` (:69) accumulates envelopes and cuts batches on
MaxMessageCount / PreferredMaxBytes; `Cut` (:127) flushes the pending
batch (driven by the consenter's batch timeout).

TPU-native twist (SURVEY.md §7 step 5): the batch size is a
*performance-coupled* knob — blocks sized to the TPU verify batch sweet
spot keep the commit-side dispatch (committer/txvalidator.py) at full
MXU occupancy, so `BatchConfig.max_message_count` defaults to a
TPU-friendly size rather than the reference's 10.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Tuple

from fabric_tpu.ops_plane import tracing
from fabric_tpu.protocol import Envelope

# why a batch was cut: the pending batch reached max_message_count, the
# next envelope would have passed preferred_max_bytes, an envelope
# larger than preferred_max_bytes is its own batch, the consenter's
# batch timer fired, or a config envelope flushed what was pending
CUT_COUNT = "count"
CUT_BYTES = "bytes"
CUT_OVERSIZE = "oversize"
CUT_TIMER = "timer"
CUT_CONFIG = "config"

# request traces a block's trace links: the first this many
MAX_LINKS = 32


@dataclass(frozen=True)
class BatchConfig:
    """Orderer.BatchSize equivalent (sampleconfig/orderer.yaml)."""
    max_message_count: int = 512
    absolute_max_bytes: int = 10 * 1024 * 1024
    preferred_max_bytes: int = 2 * 1024 * 1024
    # Orderer.BatchTimeout (seconds) — enforced by the chain loop, not here
    batch_timeout_s: float = 2.0


class Batch(list):
    """A cut batch: the serialized envelopes, and what the cutter knew
    when it cut — why, how many bytes, when the first envelope was
    enqueued and when the cut fell (perf_counter), and the ids of the
    request traces whose envelopes it holds (tracer on; at most
    MAX_LINKS)."""

    __slots__ = ("reason", "nbytes", "t_first", "t_cut", "links")

    def __init__(self, envelopes=(), reason: str = CUT_TIMER,
                 nbytes: int = 0, t_first: Optional[float] = None,
                 links=()):
        super().__init__(envelopes)
        self.reason = reason
        self.nbytes = nbytes
        self.t_cut = time.perf_counter()
        self.t_first = self.t_cut if t_first is None else t_first
        self.links = list(links)


class BlockCutter:
    """One channel's receiver (blockcutter.go receiver struct)."""

    def __init__(self, config: BatchConfig, config_source=None):
        self._static_config = config
        # optional callable returning the live BatchConfig (channel bundle);
        # committed config changes to batch limits then take effect on the
        # next ordered envelope, like the reference re-reads SharedConfig
        self._config_source = config_source
        self._pending: List[bytes] = []
        self._pending_bytes = 0
        self._pending_since: Optional[float] = None
        self._pending_links: List[str] = []

    @property
    def config(self) -> BatchConfig:
        if self._config_source is not None:
            cfg = self._config_source()
            if cfg is not None:
                return cfg
        return self._static_config

    def ordered(self, env: Envelope) -> Tuple[List[Batch], bool]:
        """Enqueue one envelope; returns (cut_batches, pending_remaining).

        Semantics mirror blockcutter.go:69-125:
        - an envelope larger than preferred_max_bytes is cut as its own
          batch (isolated message), after first cutting any pending batch;
        - appending past preferred_max_bytes cuts the pending batch first;
        - reaching max_message_count cuts immediately.
        """
        raw = env.serialize()
        size = len(raw)
        batches: List[Batch] = []
        # the request's trace, where the envelope came in under one (the
        # broadcast frame's `tps`): the block's trace will link it
        link = tracing.tracer.current_trace_id()

        if size > self.config.preferred_max_bytes:
            if self._pending:
                batches.append(self.cut(CUT_OVERSIZE))
            batches.append(Batch([raw], CUT_OVERSIZE, size,
                                 links=[link] if link else ()))
            return batches, False

        if self._pending_bytes + size > self.config.preferred_max_bytes \
                and self._pending:
            batches.append(self.cut(CUT_BYTES))

        if not self._pending:
            self._pending_since = time.perf_counter()
        self._pending.append(raw)
        self._pending_bytes += size
        if link and len(self._pending_links) < MAX_LINKS:
            self._pending_links.append(link)

        if len(self._pending) >= self.config.max_message_count:
            batches.append(self.cut(CUT_COUNT))

        return batches, bool(self._pending)

    def cut(self, reason: str = CUT_TIMER) -> Batch:
        """Flush the pending batch (blockcutter.go:127 Cut).  The caller
        says why: the consenter's timer, unless told otherwise."""
        batch = Batch(self._pending, reason, self._pending_bytes,
                      self._pending_since, self._pending_links)
        self._pending, self._pending_bytes = [], 0
        self._pending_since, self._pending_links = None, []
        return batch

    @property
    def pending_count(self) -> int:
        return len(self._pending)
