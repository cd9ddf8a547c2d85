#!/usr/bin/env python
"""CI gate: artifact publish goes through the tiered store.

The store PR moved every on-disk artifact lifecycle — the ``DMLCCHK1``
chunk cache, ``DMLCBC01`` block cache, and ``DMLCSN01`` snapshot formats
— onto ``dmlc_tpu/store/`` (one manifest, atomic publish, pin/refcount,
byte budgets with cost-aware eviction; docs/store.md). Before that, each
format hand-rolled its own ``<path>.tmp`` + ``os.replace`` publish, which
is exactly how three lifecycles drifted apart and how a fleet filled its
volume: a publish the store never sees is a publish the budget can never
bound, the manifest can never journal, and a pin can never protect.
``make lint-store`` keeps that from creeping back. It FAILS on, anywhere
under ``dmlc_tpu/`` outside ``dmlc_tpu/store/``:

- ``os.replace(`` — the atomic-publish rename; store-managed artifacts
  must publish via ``ArtifactStore.publish_file`` (and non-artifact
  files should not imitate the store's protocol beside it).
- ``+ ".tmp"`` — hand-allocated staging names; staging paths come from
  ``ArtifactStore.stage_path`` (process-unique, so concurrent writers
  of one signature can never clobber each other, and orphan GC can
  find crashed writers' leftovers).

The gate equally covers the data-service dispatcher's assignment
journal (``dmlc_tpu/service/dispatcher.py``, docs/service.md
control-plane recovery): it persists through the shared
``dmlc_tpu.store.journal.AppendJournal`` — the same flock'd
append/torn-tail-skip/atomic-compaction substrate as the store manifest
— so a hand-rolled ``.tmp`` staging name or a direct ``os.replace``
compaction beside it fails here, exactly like a direct artifact
publish would.

Sanctioned exceptions (non-artifact files, listed in ``ALLOWED``):
``utils/telemetry.py`` (Chrome-trace export writes a trace JSON, not a
store-managed artifact) and ``native/__init__.py`` (the on-demand g++
build publishes the ``.so`` and its source hash by rename, so concurrent
first imports never load a torn library; build output, not data).

Exit status: 0 clean, 1 with offenders listed as ``path:line``.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path
from typing import List, Tuple

# the store package is the one sanctioned home of the publish protocol
STORE_PACKAGE = Path("dmlc_tpu") / "store"

# non-artifact modules allowed to atomically publish their own files
ALLOWED = {
    Path("dmlc_tpu") / "utils" / "telemetry.py",  # Chrome-trace export
    Path("dmlc_tpu") / "native" / "__init__.py",  # the built .so + its hash
}

_PATTERNS = (
    (re.compile(r"\bos\.replace\s*\("),
     "direct os.replace publish — store-managed artifacts publish via "
     "dmlc_tpu/store (ArtifactStore.publish_file)"),
    (re.compile(r"\+\s*[\"']\.tmp[\"']"),
     "hand-allocated .tmp staging name — staging paths come from "
     "ArtifactStore.stage_path (process-unique, orphan-GC-able)"),
)


def scan_source(text: str) -> List[Tuple[int, str]]:
    """Return (1-based line, reason) for each direct-publish site."""
    offenders: List[Tuple[int, str]] = []
    for i, line in enumerate(text.splitlines()):
        stripped = line.lstrip()
        if stripped.startswith("#"):
            continue
        for pattern, reason in _PATTERNS:
            if pattern.search(line):
                offenders.append((i + 1, reason))
    return offenders


def main(argv: List[str]) -> int:
    root = Path(argv[1]) if len(argv) > 1 else \
        Path(__file__).resolve().parent.parent
    bad = 0
    for path in sorted((root / "dmlc_tpu").rglob("*.py")):
        rel = path.relative_to(root)
        if rel in ALLOWED or STORE_PACKAGE in rel.parents:
            continue
        for lineno, reason in scan_source(path.read_text(encoding="utf-8")):
            print(f"{rel}:{lineno}: {reason}", file=sys.stderr)
            bad += 1
    if bad:
        print(f"lint-store: {bad} direct artifact-publish site(s) found",
              file=sys.stderr)
        return 1
    print("lint-store: OK (artifact publish goes through dmlc_tpu/store)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
