"""``anovos_tpu.resilience`` — fault injection, retry policy, failover.

The policy layer that turns the scheduler's all-or-nothing failure
semantics into production behavior: a flaky node retries with backoff, a
stuck node gets one escalated timeout before its error policy applies, a
wedged accelerator fails over to CPU mid-run, and a non-spine analytics
node that exhausts its retries costs its report section (``degraded``)
instead of the run.  Every path is exercised deterministically by the
seeded chaos harness (``ANOVOS_TPU_CHAOS``) in tier-1 tests.

Four cooperating, stdlib-only pieces:

* **chaos** — named injection sites + a seeded spec parser; injections
  are metered (``chaos_injections_total``) and traced.
* **policy** — :class:`ErrorPolicy` / ``on_error="retry:N[:degrade]"``
  parsing, deterministic-jitter backoff, and the degradation registry
  the manifest + report placeholder banner read.
* **failover** — bounded in-run health probe and the one-shot CPU flip.
* the scheduler integration lives in ``parallel/scheduler.py`` (retry
  loop, partial-artifact discard via the PR 5 capture recorder, watchdog
  escalation) and ``workflow.py`` (per-class policy defaults, manifest
  ``resilience`` section).
"""

from anovos_tpu.resilience import chaos, failover, policy
from anovos_tpu.resilience.chaos import (
    BackendWedge,
    ChaosCorrupt,
    ChaosError,
    ChaosHang,
    ChaosPlan,
    ChaosTruncate,
    chaos_point,
)
from anovos_tpu.resilience.failover import (
    backend_healthy,
    failover_to_cpu,
    maybe_failover,
)
from anovos_tpu.resilience.policy import (
    ErrorPolicy,
    backoff_delay,
    degraded_sections,
    parse_policy,
    record_degraded,
    reset_degraded,
)

__all__ = [
    "chaos",
    "failover",
    "policy",
    "BackendWedge",
    "ChaosCorrupt",
    "ChaosError",
    "ChaosHang",
    "ChaosPlan",
    "ChaosTruncate",
    "chaos_point",
    "backend_healthy",
    "failover_to_cpu",
    "maybe_failover",
    "ErrorPolicy",
    "backoff_delay",
    "degraded_sections",
    "parse_policy",
    "record_degraded",
    "reset_degraded",
]
