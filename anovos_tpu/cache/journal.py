"""Write-ahead run journal: ``obs/run_journal.jsonl``.

One JSON line per lifecycle event — ``run_begin``, ``node_begin``
(cache miss, about to execute), ``node_commit`` (artifacts committed to
the store), ``node_restored`` (cache hit), ``node_failed``, ``run_end``;
plus the resilience records (``anovos_tpu.resilience``): ``node_retry``
(a failed attempt re-executes — ``kind`` distinguishes policy retries
from the one escalated-timeout and the one post-failover re-execution),
``node_timeout_escalated`` (watchdog raised a node's bound instead of
aborting), ``node_degraded`` (retries exhausted; the section is marked,
the run continues), and ``backend_failover`` (mid-run flip to CPU — the
committed frontier above this line is exactly what the failover run
kept).
The hardened data plane (round 10) adds the streaming/ingest events:
``chunk_begin`` / ``chunk_commit`` (one resumable-streaming chunk's
partial statistics about to compute / durably committed — written by
``ops.streaming.StreamCheckpoint`` into its own ``stream_journal.jsonl``
through this class, with ``stream``/``phase``/``chunk`` fields),
``chunks_invalidated`` (a part's readability changed between runs —
same bytes, transient fault — so the committed chunks from
``from_chunk`` on covered shifted rows and were dropped to recompute;
with ``phase: 2`` the histogram bucket bounds drifted and every pass-2
partial was dropped),
and ``part_quarantined`` (the ingest guard set a part aside — ``file``,
``error_class``, ``stage``, ``rows_lost``; the crash-safe
``obs/quarantine_manifest.json`` is the durable record, this line the
WAL trail next to node_retry/node_degraded).
The async prefetch pipeline (round 12) adds ``chunk_spilled`` (a
decoded frame outran the in-flight window and was staged to the
``ANOVOS_STREAM_SPILL_DIR`` disk tier — ``file_index``; purely an
overlap/telemetry record, the frame round-trips exactly).  Round 12
also widened ``chunk_begin``/``chunk_commit``/``chunks_invalidated``
to multi-pass streams: quality streams use phase 1, drift streams
phases 1/2/3 (source stats / source histograms / target histograms),
and a ``chunks_invalidated`` whose ``phase`` names the first histogram
pass means the binning EDGES drifted (a quarantined source part came
back, or the persisted model changed) and every histogram partial was
dropped — not just the chunks downstream of the shifted file.
The continuum service (round 13, ``anovos_tpu/continuum`` — its own
``continuum_journal.jsonl`` in the state dir, written through this
class) adds the partition-arrival events: ``step_begin``/``step_end``
(one arrival-loop iteration, ``step_end`` with folded/quarantined/
alert/fold-wall tallies), ``partition_seen`` (a part file classified by
stat signature — ``status`` ∈ new | changed | retracted | quarantined |
adopted, the last meaning an orphan partial from a crash window was
recovered without decode), ``fold_commit`` (one partition's
sufficient-stat partials durably committed — the npz tmp+rename is the
durability point, this line the WAL record; a mid-fold kill resumes
from exactly this frontier with zero re-decoded committed parts),
``snapshot_commit`` (the fold frontier committed content-addressed into
the PR 5 cache store — ``fp``), ``model_fitted`` (the drift source
model fitted from the baseline partitions, with the one-time
``redecoded_parts`` count), ``family_invalidated`` (a family's basis —
the drift cutoff matrix, the outlier bounds — changed under the feed,
so its partials were stripped from every partition to re-fold under the
new basis: the continuum analogue of ``chunks_invalidated``),
``state_restored`` (a lost state dir rebuilt from the newest snapshot)
and ``alert_emitted`` (a threshold-crossing drift/quality/quarantine
alert appended to ``obs/continuum_alerts.jsonl`` with flight-recorder
context).
Sibling machine-readable contract (round 15): the perf-doctor
**diagnosis** document — the ranked run-diff ``tools/perf_doctor``
prints and the HTML "Run Diff" tab renders.  Its full JSON schema
(``diagnosis_version`` / ``kind`` / ``baseline`` / ``candidate`` /
``nodes`` / ``programs`` / ``cache`` / ``env`` / ranked
``attributions``) lives with its validator in
``anovos_tpu/obs/diffing.py``.
The journal is append-only ACROSS runs in the same output directory, so
a killed run's committed frontier is still on disk when ``--resume``
re-runs the config: resumed nodes hit the cache store (the store commit,
tmp+rename, is the durability point — the journal is the human/tooling
record of WHAT was committed and when, and what a resume started from).

Lines are written through the run's :class:`AsyncArtifactWriter` (same
queue as every other artifact, drained at the run barrier) when one is
supplied; appends themselves serialize on an internal lock so concurrent
scheduler workers never interleave partial lines.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import List, Optional

__all__ = ["RunJournal", "read_journal", "committed_fingerprints"]

JOURNAL_KEY = "obs:run_journal"


class RunJournal:
    def __init__(self, path: str, writer=None):
        self.path = os.path.abspath(path)
        self._writer = writer
        self._lock = threading.Lock()
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)

    def _append_line(self, line: str) -> None:
        with self._lock, open(self.path, "a") as f:
            f.write(line + "\n")
            f.flush()

    def append(self, event: str, **fields) -> None:
        rec = {"event": event, "t": round(time.time(), 3), **fields}
        line = json.dumps(rec, sort_keys=True, separators=(",", ":"))
        try:  # every WAL event also feeds the flight recorder's ring
            from anovos_tpu.obs import flight

            flight.record("journal", event=event, **fields)
        except Exception:
            pass
        if self._writer is not None:
            self._writer.submit(JOURNAL_KEY, self._append_line, line)
        else:
            self._append_line(line)


def read_journal(path: str) -> List[dict]:
    """All parseable records (a torn final line from a kill is skipped)."""
    out: List[dict] = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
    return out


def committed_fingerprints(records: List[dict],
                           since_run: Optional[str] = None) -> List[str]:
    """Fingerprints with a commit/restore record (the resumable frontier).
    ``since_run`` restricts to records at or after that run id's last
    ``run_begin``."""
    if since_run is not None:
        start = 0
        for i, r in enumerate(records):
            if r.get("event") == "run_begin" and r.get("run_id") == since_run:
                start = i
        records = records[start:]
    out, seen = [], set()
    for r in records:
        if r.get("event") in ("node_commit", "node_restored"):
            fp = r.get("fp", "")
            if fp and fp not in seen:
                seen.add(fp)
                out.append(fp)
    return out
