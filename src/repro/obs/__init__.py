"""Flight recorder: typed schemas, trace artifacts, decision events,
forensics reports, and phase profiling (DESIGN.md §15).

Layers:

* :mod:`repro.obs.schema`  — typed metric/info registry + trace-time
  validation (:func:`validate_metrics`, :func:`validate_info`);
* :mod:`repro.obs.trace`   — compressed ``.npz`` trace sidecars keyed by
  scenario hash, with back-compat reads of JSONL-inlined traces;
* :mod:`repro.obs.events`  — pure-numpy dense-trace -> event-log
  extraction (evictions, restorations, threshold crossings, escape
  firings, attack phase changes) plus replay/summary primitives;
* :mod:`repro.obs.report`  — ``python -m repro.obs.report`` forensics
  CLI ("why was worker k evicted at step t") + markdown campaign
  reports;
* :mod:`repro.obs.profile` — wall-clock phase attribution (compile vs
  execute vs defense) with ``launch.hlo_analysis`` cost attribution;
* :mod:`repro.obs.live`    — layer-4 live telemetry: the host-side
  :class:`LiveCollector` behind ``scan_trial(tap_every=K)``'s
  ``io_callback`` taps, heartbeat JSONL persistence, and the
  ``python -m repro.obs.live`` tail/alerts CLI (DESIGN.md §17);
* :mod:`repro.obs.alerts`  — pure rule engine over heartbeat streams
  (NaN guard, eviction storms, threshold runaway, stalled saddle
  escape, step-rate collapse).

Device time per phase comes from the JAX profiler's trace of the chip,
read by the step's named scopes (``train.trainer.make_train_step``).
"""

from repro.obs.schema import (MetricSpec, SchemaError, INFO, METRICS,
                              TAP, register_metric, spec_of,
                              validate_info, validate_metrics,
                              validate_tap)
from repro.obs.trace import (load_cell_traces, load_trace_file,
                             save_traces, trace_path, trace_relpath)
from repro.obs.events import (Event, caught_curve, eviction_record,
                              events_from_json, events_to_json,
                              extract_events, replay_good, summarize)
# live/alerts resolve lazily (PEP 562): `python -m repro.obs.live`
# executes the module AND imports this package — an eager import here
# would double-load it (runpy's sys.modules warning)
_LAZY = {name: "repro.obs.live"
         for name in ("LiveCollector", "format_beat", "latest_beats",
                      "live_dir", "load_heartbeats")}
_LAZY.update({name: "repro.obs.alerts"
              for name in ("Alert", "AlertConfig", "alerts_for_campaign",
                           "extract_alerts")})


def __getattr__(name):
    if name in _LAZY:
        import importlib
        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "MetricSpec", "SchemaError", "INFO", "METRICS", "TAP",
    "register_metric", "spec_of", "validate_info", "validate_metrics",
    "validate_tap",
    "load_cell_traces", "load_trace_file", "save_traces", "trace_path",
    "trace_relpath",
    "Event", "caught_curve", "eviction_record", "events_from_json",
    "events_to_json", "extract_events", "replay_good", "summarize",
    "LiveCollector", "format_beat", "latest_beats", "live_dir",
    "load_heartbeats",
    "Alert", "AlertConfig", "alerts_for_campaign", "extract_alerts",
]
