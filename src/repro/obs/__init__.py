"""Fleet-wide observability: spans/counters (``telemetry``), merged
Chrome traces (``trace``), and rank-tagged logging (``log``). See
docs/OBSERVABILITY.md."""
from repro.obs.telemetry import (COORDINATOR_RANK, count, disable, enable,
                                 gauge, is_enabled, observe, record,
                                 snapshot, span)

__all__ = ["COORDINATOR_RANK", "count", "disable", "enable", "gauge",
           "is_enabled", "observe", "record", "snapshot", "span"]
