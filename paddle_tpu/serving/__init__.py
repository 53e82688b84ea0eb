"""paddle_tpu.serving — serving-side subsystems.

The engine itself lives in :mod:`paddle_tpu.models.serving`
(re-exported here); :mod:`paddle_tpu.serving.resilience` wraps it
with journal/replay, drain, and warm-start;
:mod:`paddle_tpu.serving.fleet` routes traffic over N resilient
replicas with exactly-once failover and SLO-aware shedding.
"""

from ..models.serving import (ContinuousBatchingEngine,  # noqa: F401
                              PrefixCache, QueueFull, Request)
from . import fleet  # noqa: F401
from . import resilience  # noqa: F401

__all__ = [
    "ContinuousBatchingEngine", "PrefixCache", "QueueFull", "Request",
    "resilience", "fleet",
]
