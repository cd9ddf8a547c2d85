"""Unified tiered-store manager for published on-disk artifacts
(chunk caches / block caches / device-native snapshots): one directory
layout + crash-safe manifest, atomic publish with orphan GC, pin/drop
refcounts, byte budgets with cost-aware eviction. See
:mod:`dmlc_tpu.store.manager` and docs/store.md. The flock'd append-only
JSONL substrate (:class:`~dmlc_tpu.store.journal.AppendJournal`) is
shared with the data-service dispatcher's assignment journal, and
:func:`signature_hash` doubles as the data service's cross-job
share-by-signature key: the multi-tenant dispatcher digests each job's
dataset identity with it to assign shared block-cache paths, so two
jobs over the same corpus converge on the same published artifacts and
the fleet parses that corpus exactly once (docs/store.md
share-by-signature; docs/service.md multi-tenant service)."""

from dmlc_tpu.store.journal import AppendJournal
from dmlc_tpu.store.manager import (
    ALL_TIERS,
    COMPACT_BYTES,
    COMPACT_LINES,
    MAGIC_TIERS,
    MANIFEST_NAME,
    RETAINED_TIERS,
    STORE_DIRNAME,
    TIER_COST,
    TIERS,
    ArtifactStore,
    current_publish_owner,
    note_missing,
    publish_owner,
    reset_stores,
    signature_hash,
    store_counters,
    store_for,
    tier_for_magic,
)

__all__ = [
    "AppendJournal",
    "ALL_TIERS", "ArtifactStore", "COMPACT_BYTES", "COMPACT_LINES", "MAGIC_TIERS",
    "MANIFEST_NAME", "RETAINED_TIERS", "STORE_DIRNAME", "TIER_COST", "TIERS",
    "current_publish_owner", "note_missing", "publish_owner",
    "reset_stores", "signature_hash", "store_counters",
    "store_for", "tier_for_magic",
]
