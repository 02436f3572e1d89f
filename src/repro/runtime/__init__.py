# The production runtime subsystem (DESIGN.md §12): named backend
# profiles resolved once at process start and stamped into every
# artifact (profile), hot-path result/LUT caching (cache), token-bucket
# admission control with a degrade/shed ladder and deadline propagation
# (admission), background compaction + drift recalibration off the
# request path (maintenance), and the structured telemetry: counters,
# per-request phases and host spans on the profiler's clock, which the
# serve report and ``serve.py --telemetry-out`` write (telemetry).
from repro.runtime import profile
from repro.runtime.admission import (
    ADMIT,
    DEGRADE,
    SHED,
    AdmissionController,
    Decision,
    DegradePolicy,
    TokenBucket,
)
from repro.runtime.cache import (
    MISS,
    CachedSearcher,
    LUTCache,
    TTLLRUCache,
    fingerprint,
)
from repro.runtime.maintenance import MaintenanceScheduler
from repro.runtime.profile import PROFILES, RuntimeProfile
from repro.runtime.telemetry import RequestTrace, Telemetry

__all__ = [
    "profile",
    "RuntimeProfile",
    "PROFILES",
    "TTLLRUCache",
    "LUTCache",
    "CachedSearcher",
    "MISS",
    "fingerprint",
    "AdmissionController",
    "DegradePolicy",
    "TokenBucket",
    "Decision",
    "ADMIT",
    "DEGRADE",
    "SHED",
    "MaintenanceScheduler",
    "Telemetry",
    "RequestTrace",
]
