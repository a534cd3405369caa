"""Block delivery streams (server side).

Reference parity: common/deliver/deliver.go — Handle (:157) parses a
SeekInfo envelope and deliverBlocks (:199) streams blocks from the
channel ledger, blocking at the chain tip when behavior=BLOCK_UNTIL_READY.
The reader ACL (deliver/acl.go re-evaluated on config change) maps to the
readers-policy check in `authorize`.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Iterator, Optional

from fabric_tpu.orderer import metrics
from fabric_tpu.policy import SignedData
from fabric_tpu.protocol import Block

SEEK_OLDEST = "oldest"
SEEK_NEWEST = "newest"

BEHAVIOR_BLOCK_UNTIL_READY = "block_until_ready"
BEHAVIOR_FAIL_IF_NOT_READY = "fail_if_not_ready"


class DeliverError(Exception):
    pass


class NotReadyError(DeliverError):
    """Seek past the tip with FAIL_IF_NOT_READY."""


@dataclass(frozen=True)
class SeekInfo:
    """ab.SeekInfo: start/stop positions. int = specified block number."""
    start: object = SEEK_OLDEST        # int | "oldest" | "newest"
    stop: Optional[object] = None      # int | "newest" | None (= stream forever)
    behavior: str = BEHAVIOR_BLOCK_UNTIL_READY


class DeliverHandler:
    """deliver.Handler bound to a registrar of channels."""

    def __init__(self, registrar):
        self.registrar = registrar

    def deliver(self, channel_id: str, seek: SeekInfo,
                signed: Optional[SignedData] = None,
                timeout_s: Optional[float] = None) -> Iterator[Block]:
        """Generator of blocks per the seek request.

        `signed` is the deliver request's creator triple, checked against
        the channel Readers policy when the channel enforces one.

        A stream is counted, not traced: a peer's deliver loop asks from
        a thread that has no trace, and each block's own trace context
        rides beside it on the frame (node/orderer.py `_rpc_deliver`).
        A request that ends at the tip, by its stop or by its time-out
        is a success; one refused or cut short is not.
        """
        metrics.deliver_received.add(1, channel=channel_id)
        success = "true"
        try:
            support = self.registrar.get(channel_id)
            if support is None:
                raise DeliverError(f"unknown channel {channel_id!r}")
            support.authorize_read(signed)

            height = support.ledger.height
            start = self._resolve(seek.start, height)
            stop = (self._resolve(seek.stop, height)
                    if seek.stop is not None else None)
            if stop is not None and stop < start:
                raise DeliverError(f"seek stop {stop} < start {start}")

            num = start
            while stop is None or num <= stop:
                if num >= support.ledger.height:
                    if seek.behavior == BEHAVIOR_FAIL_IF_NOT_READY:
                        raise NotReadyError(
                            f"block {num} past tip {support.ledger.height}")
                    if not support.wait_for_height(num + 1, timeout_s):
                        return  # timed out waiting at the tip
                yield support.ledger.get_by_number(num)
                metrics.deliver_sent.add(1, channel=channel_id)
                num += 1
        except NotReadyError:
            raise    # at-tip is the normal end of a window pull
        except BaseException:        # incl. GeneratorExit on client cancel
            success = "false"
            raise
        finally:
            metrics.deliver_completed.add(1, channel=channel_id,
                                          success=success)

    @staticmethod
    def _resolve(pos, height: int) -> int:
        if pos == SEEK_OLDEST:
            return 0
        if pos == SEEK_NEWEST:
            return max(0, height - 1)
        return int(pos)
