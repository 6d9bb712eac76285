"""Single-host what-if serving: coalesced, executable-cached, cost-priced
(see serve/README.md for the query lifecycle and design rationale).

:class:`WhatIfService` answers heterogeneous tenant queries — score a
placement batch, rank candidates (weighted or ε-constraint), extract a
Pareto front, co-optimize placement × dq — through shared dispatches:
queries normalize to a :class:`CoalesceKey` (fleet content digest +
objective set; dq/β deliberately excluded because they
are per-row operands of the dispatch), merge across tenants into power-of-two-padded
super-batches, resolve compiled executables through the process-wide
:mod:`repro.sim.execache`, and stream results back per tenant.  Every
query is priced BEFORE dispatch (FLOPs/roofline prior calibrated by
observed per-bucket latency quantiles) and admitted, degraded, or
rejected with a typed verdict.
"""

from repro.serve.admission import (AdmissionConfig, Admitted, Degraded,
                                   DispatchPricer, Rejected, decide)
from repro.serve.bucketing import (CoalesceKey, fleet_digest, next_pow2,
                                   pad_rows)
from repro.serve.cache import BucketStats, ServeStats
from repro.serve.service import (QueryResult, QueryTicket, ResultChunk,
                                 WhatIfQuery, WhatIfService)

__all__ = [
    # service surface
    "WhatIfService", "WhatIfQuery", "QueryTicket", "ResultChunk",
    "QueryResult",
    # admission
    "AdmissionConfig", "Admitted", "Degraded", "Rejected",
    "DispatchPricer", "decide",
    # bucketing / coalescing
    "CoalesceKey", "fleet_digest", "next_pow2", "pad_rows",
    # accounting
    "BucketStats", "ServeStats",
]
