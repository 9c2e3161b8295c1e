"""Continuous-batching serving engine (the serve data plane's core).

``engine.py`` is the model-agnostic half: ``PagedEngine``, an
admission loop that admits waiting requests at EVERY tick and retires
finished rows immediately (per-row EOS / max-token), so a batch never
pads out to its longest row and a new request's time-to-first-token
is one decode tick + its own prefill instead of a whole preceding
generation.  KV memory is a paged arena: block-granular KV with
per-request page tables (``paging.py``: free-list allocator,
admission-time page budgeting, refcounted prefix cache, the row
layout an attention class asks for), chunked prefill interleaved with
decode ticks, and read-only shared prompt pages.

``pool.py`` is the device half: the jitted prefill-chunk/decode pair
over the persistent arena (models/decode.py), shared by the
single-chip server and the multi-host gang driver.

``migration.py`` (ISSUE 16) makes the KV page the unit of MOBILITY:
live sessions move pod-to-pod mid-generation under a fenced cutover
protocol — the primitive behind drain-with-migration, prefix-hotspot
rebalancing, and prefill/decode disaggregation.
"""

from dcos_commons_tpu.serve.engine import (
    SERVESTATS_NAME,
    PagedEngine,
    QueueTimeoutError,
    read_servestats,
)
from dcos_commons_tpu.serve.migration import (
    HttpEngineClient,
    InProcessTransport,
    MigrationError,
    MigrationRecord,
    PrefillHandoff,
    ReleasePendingError,
    SessionMigratedError,
    SessionSnapshot,
    SimulatedDcnTransport,
    drain_sessions,
    migrate_session,
)
from dcos_commons_tpu.serve.paging import (
    PageAllocator,
    PagedServeConfig,
    paged_config_from_env,
)

__all__ = [
    "SERVESTATS_NAME",
    "HttpEngineClient",
    "InProcessTransport",
    "MigrationError",
    "MigrationRecord",
    "PageAllocator",
    "PagedEngine",
    "PagedServeConfig",
    "PrefillHandoff",
    "QueueTimeoutError",
    "ReleasePendingError",
    "SessionMigratedError",
    "SessionSnapshot",
    "SimulatedDcnTransport",
    "drain_sessions",
    "migrate_session",
    "paged_config_from_env",
    "read_servestats",
]
