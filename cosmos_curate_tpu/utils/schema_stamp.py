"""Shared schema-version stamping for every durable JSON format.

Long-lived deployments replay journals, list DLQ entries and open index
manifests written by OLDER builds (rolling upgrades, crash-resume across a
deploy). Before this module each durable writer invented its own version
story — ``run_report.json`` carried a lone ``"version": 1``, the job
journal, DLQ metadata and index manifests carried nothing — so a reader
could not even *tell* it was looking at an old record, let alone migrate
it. This module is the one place that knows:

- the **published version** of every durable surface
  (:data:`SCHEMA_VERSIONS` — bumping a number here is what the
  ``lint --schema`` drift gate means by "a version bump");
- how to **stamp** a document at write time (:func:`stamp` — every
  report/snapshot/journal/manifest writer routes through it);
- how to **upgrade** an old document at read time (:func:`upgrade` — the
  registered :data:`MIGRATIONS` shims carry version-N−1 records forward,
  one step at a time, so replay/recover paths accept what the previous
  build wrote).

The static half of the contract lives in ``analysis/schema_check.py``
(``lint --schema``): it extracts each surface's field schema from the
code, diffs it against the checked-in golden under ``analysis/schemas/``
and fails the gate when the shape drifted without a bump here — or when a
breaking drift bumped the version but forgot to register a shim.

The wire-protocol counterpart (``PROTOCOL_VERSION``) lives in
``engine/remote_plane.py``: control-plane frames are never persisted, so
skew there is rejected at the Hello/HelloAck handshake instead of being
migrated.
"""

from __future__ import annotations

from typing import Any, Callable

STAMP_KEY = "schema_version"

# surface -> published version. A version is "published" once records with
# it exist outside one process: bumping requires regenerating the goldens
# (`lint --schema --update`) and, for breaking changes, a MIGRATIONS shim
# from the previous version. Version 1 is the historical, unstamped format
# of each surface (no STAMP_KEY on disk).
SCHEMA_VERSIONS: dict[str, int] = {
    # service/job_queue.py journal envelope + JobRecord snapshot
    "job-journal": 2,
    # engine/dead_letter.py meta.json
    "dlq-meta": 2,
    # dedup/index_store.py manifests/gen-N.json + MANIFEST.json pointer
    "index-manifest": 2,
    # observability/flight_recorder.py report/run_report.json
    "run-report": 1,
    # observability/flight_recorder.py report/node-stats-<rank>.json
    "node-stats": 1,
    # observability/live_status.py report/live/status.json
    "live-status": 1,
}


class SchemaVersionError(ValueError):
    """A document's version cannot be reconciled with this build: newer
    than published, or older with no registered migration shim."""


def stamp(doc: dict, surface: str) -> dict:
    """Stamp ``doc`` (in place) with the surface's published version and
    return it. Unknown surfaces raise — a writer inventing a surface name
    must register it here (and in the schema_check registry) first."""
    if surface not in SCHEMA_VERSIONS:
        raise KeyError(
            f"unknown durable surface {surface!r}; register it in "
            "utils/schema_stamp.SCHEMA_VERSIONS and analysis/schema_check.py"
        )
    doc[STAMP_KEY] = SCHEMA_VERSIONS[surface]
    return doc


# -- migration shims --------------------------------------------------------
#
# (surface, from_version) -> shim taking a from_version document and
# returning the (from_version + 1) document. Shims run at READ time
# (replay, list, open); they must be total — never raise on any document
# the old writer could have produced — and must not mutate their input.


def _journal_v1_to_v2(doc: dict) -> dict:
    """v1 journal lines predate stamping: the envelope was
    ``{ts, event, record}`` with no schema_version and no field renames
    since — carrying it forward is filling in the stamp."""
    out = dict(doc)
    out[STAMP_KEY] = 2
    return out


def _dlq_meta_v1_to_v2(doc: dict) -> dict:
    """v1 DLQ meta.json predates stamping; field set is unchanged."""
    out = dict(doc)
    out[STAMP_KEY] = 2
    return out


def _manifest_v1_to_v2(doc: dict) -> dict:
    """v1 manifests (and MANIFEST.json pointers) predate stamping; field
    set is unchanged."""
    out = dict(doc)
    out[STAMP_KEY] = 2
    return out


MIGRATIONS: dict[tuple[str, int], Callable[[dict], dict]] = {
    ("job-journal", 1): _journal_v1_to_v2,
    ("dlq-meta", 1): _dlq_meta_v1_to_v2,
    ("index-manifest", 1): _manifest_v1_to_v2,
}


def doc_version(doc: dict) -> int:
    """The version a document claims; unstamped documents are the
    historical version 1 by definition."""
    v = doc.get(STAMP_KEY, 1)
    try:
        return int(v)
    except (TypeError, ValueError):
        return 1


def has_migration(surface: str, from_version: int) -> bool:
    return (surface, from_version) in MIGRATIONS


def upgrade(doc: dict, surface: str, *, strict: bool = True) -> dict:
    """Carry ``doc`` forward to the surface's published version through the
    shim chain; same-version documents return unchanged (not copied).

    A document NEWER than this build (rolling upgrade read the new build's
    output) raises :class:`SchemaVersionError` when ``strict``; with
    ``strict=False`` it is returned as-is — callers whose parsers already
    ignore unknown fields (e.g. ``JobRecord.from_dict``) can read
    best-effort rather than wedge. A missing shim always raises: silently
    misreading an old record is the failure mode this module exists to
    kill."""
    current = SCHEMA_VERSIONS[surface]
    v = doc_version(doc)
    if v == current:
        return doc
    if v > current:
        if strict:
            raise SchemaVersionError(
                f"{surface} document is schema v{v} but this build publishes "
                f"v{current}; upgrade this process before reading it"
            )
        return doc
    while v < current:
        shim = MIGRATIONS.get((surface, v))
        if shim is None:
            raise SchemaVersionError(
                f"{surface} document is schema v{v} and no migration shim "
                f"({surface}, {v})->v{v + 1} is registered in "
                "utils/schema_stamp.MIGRATIONS"
            )
        doc = shim(doc)
        v = doc_version(doc)
    return doc


def describe() -> dict[str, Any]:
    """Machine-readable summary (``lint --schema --json`` and tests)."""
    return {
        "versions": dict(SCHEMA_VERSIONS),
        "migrations": sorted(f"{s}:v{v}->v{v + 1}" for s, v in MIGRATIONS),
    }
