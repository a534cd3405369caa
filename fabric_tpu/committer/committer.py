"""Committer: validate + commit, the StoreBlock composition.

Reference parity: core/committer/committer_impl.go LedgerCommitter plus
the gossip/state coordinator hand-off (state.go:781 commitBlock ->
coordinator.StoreBlock -> txvalidator.Validate -> CommitLegacy).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Optional

from fabric_tpu.ledger import KVLedger
from fabric_tpu.ops_plane import tracing
from fabric_tpu.ops_plane.logging import jlog
from fabric_tpu.protocol import Block
from fabric_tpu.protocol.wire import n_txs
from fabric_tpu.utils import heap

from .txvalidator import TxValidator, ValidationResult

logger = logging.getLogger("fabric_tpu.committer")


@dataclass
class BlockCommitResult:
    validation: ValidationResult  # flags as of the sig/policy gate
    commit_stats: object          # ledger CommitStats
    final_flags: object           # TxFlags after MVCC (what the block stores)
    # the block trace's root (SpanContext), None when it is not recorded:
    # what runs after store_block returns (the private-data
    # coordinator's tail) hangs its span here
    trace: object = None


class Committer:
    def __init__(self, ledger: KVLedger, validator: TxValidator,
                 bundle_source=None, provider=None, confighistory=None):
        self.ledger = ledger
        self.validator = validator
        self.bundle_source = bundle_source
        self.provider = provider
        # height-indexed config log (core/ledger/confighistory/mgr.go)
        self.confighistory = confighistory
        # wire the duplicate-txid oracle to the block store
        self.validator.ledger_has_txid = ledger.blockstore.has_txid
        # post-commit hooks fed (block, final TxFlags); the gateway's
        # commit-status notifier rides here so clients learn a txid's
        # validation code without polling the ledger
        self._commit_listeners = []

    def add_commit_listener(self, fn) -> None:
        """Register fn(block, final_flags), called after every commit."""
        self._commit_listeners.append(fn)

    def store_block(self, block: Block) -> BlockCommitResult:
        """Validate (verify-then-gate) and commit one block.

        Committed config blocks are applied to the channel bundle AFTER the
        commit (core/peer: channel config takes effect at the block
        boundary), so the config tx itself is validated under the previous
        configuration — matching configtx/validator.go sequencing.
        """
        # the block's two intake stamps, for the commit listeners (the
        # gateway's stage account): when its frame was received — its
        # parse began — where a parser stamped that, and when the
        # committer took it.  They travel on the block, not in a global
        t_store = time.perf_counter()
        parsed = getattr(block, "parsed", None)
        block.intake = (parsed[0] if parsed is not None else t_store,
                        t_store)
        # root of the block-domain trace: everything downstream (VSCC
        # batch verify, MVCC, ledger append, commit notification) hangs
        # off this span, and commit_status links request traces to it
        with tracing.tracer.start_span(
                "committer.store_block",
                attributes={"channel": self.validator.channel_id,
                            "block": int(block.header.number),
                            "txs": n_txs(block)}) as span:
            # when wire.parse_block made this block, before the hand-off
            parsed = getattr(block, "parsed", None)
            if parsed is not None:
                tracing.tracer.record_span("wire.parse_block", *parsed)
            result = self._store_block_inner(block)
            result.trace = span.context
            if span.recording:
                span.set_attribute("valid",
                                   result.final_flags.valid_count())
            return result

    def _store_block_inner(self, block: Block) -> BlockCommitResult:
        pre = self._precommit(block)
        if isinstance(pre, BlockCommitResult):
            return pre
        vr, new_cfg = pre
        stats = self.ledger.commit(block)
        return self._postcommit(block, vr, stats, new_cfg)

    def _precommit(self, block: Block):
        """Everything that must happen BEFORE the ledger commit: the
        idempotent-replay check, signature/policy validation, and
        commit-time config-tx validation (which may flip tx 0's flag).
        -> BlockCommitResult for an acknowledged replay, else
        (ValidationResult, new_cfg|None)."""
        from fabric_tpu.protocol.txflags import TxFlags, ValidationCode
        from fabric_tpu.protocol.types import META_TXFLAGS

        t0 = time.perf_counter()
        replayed = self._check_replay(block)
        if replayed is not None:
            return replayed
        tracing.tracer.record_span("committer.replay_check", t0,
                                   time.perf_counter())
        vr = self.validator.validate(block)
        t_validated = time.perf_counter()
        # Commit-time config validation happens BEFORE the commit: a config
        # tx that fails (wrong sequence, Admins unsatisfied) must be
        # recorded with an INVALID flag, never committed as VALID with the
        # failure merely logged (the reference invalidates the config tx;
        # an unauthorized config tx permanently recorded valid would be a
        # ledger integrity violation).
        new_cfg = None
        cfg_env = None
        if self.bundle_source is not None:
            from fabric_tpu.config import config_envelope_of
            cfg_env = config_envelope_of(block)
        if cfg_env is not None:
            flags = TxFlags.from_bytes(block.metadata.items[META_TXFLAGS])
            if flags.is_valid(0):
                from fabric_tpu.config import (
                    ConfigError,
                    parse_config_envelope,
                    validate_parsed_config_update,
                )
                bundle = self.bundle_source.current()
                try:
                    cfg, sds = parse_config_envelope(cfg_env)
                except Exception as exc:
                    cfg = None
                    err = ConfigError(f"malformed config envelope: {exc}")
                else:
                    err = None
                if cfg is not None and cfg.sequence <= bundle.sequence:
                    # A stale-sequence config tx is only acceptable as
                    # HISTORICAL REPLAY — a peer bootstrapped at a later
                    # config catching up through the old config blocks
                    # that produced it.  Genuine replay is recognizable:
                    # the block number is at or below the height the
                    # current config was taken/applied at (BundleSource
                    # .config_height, advanced on every application, or
                    # covered by confighistory).  A brand-NEW block above
                    # that height carrying a stale-sequence config tx is
                    # a wrong-sequence config (e.g. a byzantine orderer
                    # replaying an old authorized update) and is flagged
                    # INVALID like any other wrong-sequence config — the
                    # reference invalidates it at commit
                    # (configtx/validator.go sequence check).
                    covered = block.header.number <= getattr(
                        self.bundle_source, "config_height", 0)
                    if not covered and self.confighistory is not None:
                        latest = self.confighistory.latest_height()
                        covered = (latest is not None
                                   and block.header.number <= latest)
                    if (not covered and cfg is not None
                            and cfg.sequence == bundle.sequence
                            and cfg.serialize()
                            == bundle.config.serialize()):
                        # byte-identical to the live config: this is the
                        # very config block that produced the bootstrap
                        # bundle (a fresh peer bootstrapped at sequence S
                        # replaying the block that applied S) — a
                        # harmless idempotent replay, and flagging it
                        # INVALID would diverge from tip peers.  Configs
                        # strictly OLDER than the bootstrap one still
                        # need config_height seeded in the node config.
                        covered = True
                        self.bundle_source.config_height = max(
                            getattr(self.bundle_source, "config_height", 0),
                            block.header.number)
                    if covered:
                        logger.debug(
                            "config block %d sequence %d <= bundle "
                            "sequence %d: catch-up replay, skipping",
                            block.header.number, cfg.sequence,
                            bundle.sequence)
                    else:
                        err = ConfigError(
                            f"config sequence {cfg.sequence} <= current "
                            f"{bundle.sequence} in new block "
                            f"{block.header.number}")
                elif err is None:
                    try:
                        new_cfg = validate_parsed_config_update(
                            bundle, cfg, sds,
                            self.provider or self.validator.provider)
                    except ConfigError as exc:
                        err = exc
                if err is not None:
                    logger.warning(
                        "config tx in block %d invalid at commit: %s",
                        block.header.number, err)
                    jlog(logger, "committer.config_tx_invalid",
                         level=logging.WARNING, exc=err,
                         channel=self.validator.channel_id,
                         block=int(block.header.number))
                    flags.set(0, ValidationCode.INVALID_CONFIG_TRANSACTION)
                    block.metadata.items[META_TXFLAGS] = flags.to_bytes()
        tracing.tracer.record_span("committer.config_check", t_validated,
                                   time.perf_counter())
        return vr, new_cfg

    def _postcommit(self, block: Block, vr, stats,
                    new_cfg) -> BlockCommitResult:
        """Everything AFTER the ledger commit: phase spans, metrics,
        commit listeners, and (for a valid config tx) the channel bundle
        application."""
        from fabric_tpu.protocol.txflags import TxFlags
        from fabric_tpu.protocol.types import META_TXFLAGS

        # from the end of the ledger's last phase: its own tail (apply
        # metrics, the log line) belongs here too
        t0 = (stats.phase_spans[-1][2] if stats.phase_spans
              else time.perf_counter())
        self._record_phase_spans(stats)
        final = TxFlags.from_bytes(block.metadata.items[META_TXFLAGS])
        self._observe_metrics(block, vr, stats)
        tracing.tracer.record_span("committer.observe", t0,
                                   time.perf_counter())
        with tracing.tracer.start_span(
                "committer.notify", require_parent=True,
                attributes={"listeners": len(self._commit_listeners)}):
            for fn in self._commit_listeners:
                try:
                    fn(block, final)
                except Exception as exc:
                    logger.exception("commit listener failed for block %d",
                                     block.header.number)
                    jlog(logger, "committer.listener_failed",
                         level=logging.ERROR, exc=exc,
                         channel=self.validator.channel_id,
                         block=int(block.header.number))
        if new_cfg is not None and final.is_valid(0):
            try:
                from fabric_tpu.config import Bundle
                self.bundle_source.update(Bundle(new_cfg),
                                          config_height=block.header.number)
                if self.confighistory is not None:
                    self.confighistory.record(block.header.number,
                                              new_cfg.serialize())
            except Exception:
                # the block is already committed; a config-plane failure
                # must not make the caller believe the commit failed
                logger.exception("config application failed for block %d",
                                 block.header.number)
        # the block boundary: what is alive now is the ledger, which
        # the collector's full passes need not walk again
        with tracing.tracer.start_span("committer.heap_boundary",
                                       require_parent=True):
            heap.block_boundary()
        return BlockCommitResult(vr, stats, final)

    def _check_replay(self, block: Block) -> Optional[BlockCommitResult]:
        """Idempotent re-commit: a block we already hold (deliver retry
        after a severed stream, duplicated gossip push, orderer resend
        after crash recovery) is acknowledged without re-validating,
        re-committing, or re-notifying listeners — IF it is the same
        block.  The same number with a different header hash is a fork
        and stays a hard error."""
        num = int(block.header.number)
        if num >= self.ledger.height:
            return None
        from fabric_tpu.protocol import block_header_hash
        from fabric_tpu.protocol.txflags import TxFlags
        from fabric_tpu.protocol.types import META_TXFLAGS
        stored = self.ledger.blockstore.get_by_number(num)
        if block_header_hash(stored.header) != block_header_hash(
                block.header):
            raise ValueError(
                f"replayed block {num} does not match the committed "
                f"block (divergent header hash)")
        jlog(logger, "committer.replayed_block",
             channel=self.validator.channel_id, block=num,
             height=self.ledger.height)
        try:
            from fabric_tpu.ops_plane import registry
            registry.counter(
                "committer_replayed_blocks_total",
                "duplicate blocks acknowledged idempotently").add(
                    1, channel=self.validator.channel_id)
        except Exception:
            pass
        tracing.event("committer.replay", block=num)
        final = TxFlags.from_bytes(stored.metadata.items[META_TXFLAGS])
        return BlockCommitResult(None, None, final)

    @staticmethod
    def _record_phase_spans(stats) -> None:
        """Retroactive child spans for the ledger commit phases, each
        where it really ran (kvledger stamps the intervals)."""
        for name, start, end in stats.phase_spans:
            tracing.tracer.record_span(name, start, end,
                                       stats.span_attrs.get(name))

    def _observe_metrics(self, block, vr, stats) -> None:
        """Per-phase commit metrics (metric parity: the reference's
        ledger_block_processing_time / gossip state commit duration and
        validation duration, kv_ledger.go:491-499, validator.go:262)."""
        try:
            from fabric_tpu.ops_plane import registry
            ch = self.validator.channel_id
            registry.histogram(
                "validation_duration_seconds",
                "txvalidator.Validate wall time").observe(
                    vr.total_s, channel=ch)
            commit_s = sum(
                getattr(stats, phase, None) or 0.0
                for phase in ("state_validation_s", "block_commit_s",
                              "state_commit_s", "history_commit_s"))
            # the "commit" stage of the validator_stage_seconds family
            # (collect/dispatch/gate land in txvalidator._observe_block)
            registry.histogram(
                "validator_stage_seconds",
                "per-block validation stage latency",
                buckets=self.validator._STAGE_BUCKETS).observe(
                    commit_s, stage="commit", channel=ch)
            registry.counter(
                "committed_blocks_total", "blocks committed").add(1, channel=ch)
            registry.counter(
                "committed_txs_total", "txs committed").add(
                    n_txs(block), channel=ch)
            registry.gauge("ledger_height", "block height").set(
                self.ledger.height, channel=ch)
        except Exception:
            logger.exception("metrics observation failed")

    @property
    def height(self) -> int:
        return self.ledger.height
