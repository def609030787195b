"""``anovos_tpu.cache`` — content-addressed incremental recompute.

The workflow is a config-driven DAG re-run constantly with small config
deltas; every run used to recompute every block from scratch and a crash
lost the whole run.  The scheduler's verified ``reads=``/``writes=``
contracts (PR 1, audited exact by graftcheck GC006) make each node's
artifacts a pure function of (input fingerprint, config slice, code
version, upstream fingerprints) — i.e. a safe cache key.  Four
stdlib-only pieces:

* **fingerprint** — canonical config-slice hashing, the audited
  ``KNOWN_ENV_KNOBS`` list (GC008 enforces completeness), dataset and
  per-node fingerprints folded over RAW edges;
* **capture** — per-node artifact recording (thread-local recorder +
  write-mode ``open()`` hook) so a miss knows exactly which files it
  created;
* **store** — the content-addressed on-disk store (atomic tmp+rename
  commits, LRU eviction, ``tools/cache_gc.py``);
* **journal** — the append-only ``obs/run_journal.jsonl`` write-ahead
  record that lets ``--resume`` pick up a killed run's committed
  frontier.

Opt-in via ``ANOVOS_TPU_CACHE=<dir>``.  JAX's persistent XLA compilation
cache is separate and on by default (``init_runtime``:
``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from anovos_tpu.cache import capture
from anovos_tpu.cache.fingerprint import (
    KNOWN_ENV_KNOBS,
    base_material,
    canonical,
    dataset_fingerprint,
    digest,
    env_fingerprint,
    node_fingerprint,
)
from anovos_tpu.cache.journal import RunJournal, committed_fingerprints, read_journal
from anovos_tpu.cache.store import CacheStore, cache_root, enabled

__all__ = [
    "KNOWN_ENV_KNOBS",
    "NodeCachePolicy",
    "CacheStore",
    "RunJournal",
    "base_material",
    "cache_root",
    "canonical",
    "capture",
    "committed_fingerprints",
    "dataset_fingerprint",
    "digest",
    "enabled",
    "env_fingerprint",
    "node_fingerprint",
    "read_journal",
]


@dataclass
class NodeCachePolicy:
    """What the scheduler needs to cache one node.

    ``key_material`` is the node-local fingerprint part (run base + name
    + config slice + writes); the scheduler folds RAW-dep fingerprints on
    top at registration.  ``flush(keys)`` blocks until the node's queued
    async writes have landed (commit barrier).  ``payload_write(dir)``
    serializes non-file state (a spine node's output df version) into the
    store's payload dir at commit; ``on_hit(payload_dir)`` re-creates that
    state on restore (and releases whatever the skipped body would have
    released)."""

    key_material: str
    flush: Optional[Callable] = None
    payload_write: Optional[Callable[[str], None]] = None
    on_hit: Optional[Callable[[Optional[str]], None]] = None
