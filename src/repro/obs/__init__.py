"""Flight-recorder observability for the serving stack.

Four pieces behind one handle (`Recorder`):

    events.py   structured event bus — typed `Event`s, timestamped on
                the INSTALLED serving clock (non-advancing reads under
                a `FakeClock`), bounded ring with drop counters;
    trace.py    profiler annotations (`annotate`: the serving step's
                spans in the device trace), nested span tracing +
                Chrome ``trace_event`` exporter (open any replay in
                Perfetto);
    metrics.py  counters / gauges / log-bucketed quantile sketches with
                label sets, plus the `RequestAggregate` that gives
                `ServingCluster.metrics_by_label` O(1) accounting;
    slo.py      SLO/downtime ledger — Φ_L targets + the event stream →
                windowed per-label attainment and an exact "who paid
                this pause" breakdown.

Plus the Watchtower layer on top (PR 10):

    lineage.py  per-request critical-path attribution — `RequestLineage`
                decomposes measured TTFT/TPOT into named components with
                a conservation check and Chrome flow events;
    alerts.py   `AlertEvaluator` — multi-window SLO burn-rate rules,
                estimator-drift alarms, liveness watchdogs, and
                deterministic debug bundles on every fired alert.

Recording is opt-in and costs one attribute read when off: the serving
stack guards every hook with ``RECORDER is None``. The serving step's
profiler spans (`annotate`) are always on, about a microsecond each
with no profiler session. Enable recording with::

    from repro.obs import Recorder, recording
    with recording(Recorder()) as rec:
        ...
    rec.export_chrome("run.trace.json")

See docs/observability.md for the event taxonomy and span hierarchy.
"""
from repro.obs.alerts import (
    Alert,
    AlertEvaluator,
    BurnRateRule,
    bundle_events,
    load_bundle,
    replay_ledger,
)
from repro.obs.events import (
    Event,
    EventBus,
    Recorder,
    get_recorder,
    install_recorder,
    now,
    recording,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RequestAggregate,
)
from repro.obs.lineage import (
    TPOT_COMPONENTS,
    TTFT_COMPONENTS,
    RequestLineage,
    RequestTimeline,
)
from repro.obs.slo import PauseAccount, SLOLedger, WindowAttainment, meets_slo
from repro.obs.trace import (
    Span,
    TraceBuffer,
    annotate,
    export_chrome,
    overlaps,
    validate_chrome,
)

__all__ = [
    "Alert", "AlertEvaluator", "BurnRateRule", "bundle_events",
    "load_bundle", "replay_ledger",
    "Event", "EventBus", "Recorder", "get_recorder", "install_recorder",
    "now", "recording",
    "RequestLineage", "RequestTimeline",
    "TTFT_COMPONENTS", "TPOT_COMPONENTS",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "RequestAggregate",
    "PauseAccount", "SLOLedger", "WindowAttainment", "meets_slo",
    "Span", "TraceBuffer", "annotate", "export_chrome", "overlaps",
    "validate_chrome",
]
