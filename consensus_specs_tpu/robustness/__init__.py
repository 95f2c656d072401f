"""Fault tolerance around the device boundary.

Four pieces (see each module's docstring):

  faults.py      seeded `FaultPlan` — deterministic injection at the real
                 seams (dispatch, aux readout, write-back staging, gossip
                 frames, deferred-BLS flush).
  retry.py       backoff-with-jitter policies + the retryable-vs-fatal
                 classification every seam shares.
  breaker.py     circuit breaker: device path → pure-Python degradation
                 after N consecutive failures, half-open probe to re-arm.
  checkpoint.py  epoch-boundary engine snapshots with an integrity digest;
                 `restore()` rebuilds the engine, two-phase write-back in
                 bridge._write_back keeps a crash from tearing the registry.

The whole package is jax-free at module level (tpulint import-layering:
`robustness/` is in the jax_free set) so the pure-host consumers —
crypto/bls.py, the gossip driver — can import it
without dragging in a device runtime.
"""
from . import breaker, checkpoint, faults, retry  # noqa: F401
from .breaker import CircuitBreaker  # noqa: F401
from .checkpoint import CheckpointIntegrityError, EngineCheckpoint  # noqa: F401
from .faults import (  # noqa: F401
    CorruptAuxError,
    FatalFault,
    FaultInjected,
    FaultPlan,
    FaultSpec,
    IntegrityError,
    TornWriteBackError,
    TransientFault,
)
from .retry import (  # noqa: F401
    DEVICE_POLICY,
    RetryPolicy,
    call_with_retry,
    is_device_failure,
    is_retryable,
)
