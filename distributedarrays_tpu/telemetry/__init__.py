"""Framework-wide telemetry: counters, communication byte accounting, and
a structured event journal.

The observability layer the reference never had (SURVEY.md §5) and the
`jax.profiler` wrappers in ``utils/profiling.py`` cannot provide: every
reshard, eager transfer, traced collective, SPMD mailbox send, fallback
hit, retrace, autotune lookup, and checkpoint phase in this framework
reports here, so one process can answer "how many bytes did this workload
move and how many reshards/retraces/fallbacks did it take?" without a
profiler run.

Quick use::

    import distributedarrays_tpu as dat
    from distributedarrays_tpu import telemetry

    telemetry.configure("run.jsonl")      # optional JSONL journal
    ...workload...
    print(telemetry.report())             # nested dict
    telemetry.dump("telemetry.json")      # JSON export

    # attribute time/bytes to phases with hierarchical spans
    with telemetry.span("train.step", step=i):
        ...                            # comm/events inside carry span_id

    # offline: summarize / export a journal
    #   python -m distributedarrays_tpu.telemetry summarize run.jsonl
    #   python -m distributedarrays_tpu.telemetry trace run.jsonl -o t.json
    #   python -m distributedarrays_tpu.telemetry prom report.json

Disable with ``DA_TPU_TELEMETRY=0`` (or :func:`disable`): every recording
call becomes a boolean check and an immediate return, no journal file is
ever created, and :func:`report` stays empty.

Metric catalog and the journal schema: ``docs/telemetry.md``.
"""

from .core import (enabled, enable, disable, configure, reset, count,
                   set_gauge, observe, event, record_comm, counter_value,
                   gauge_value, comm_bytes, events, journal_path, nbytes_of,
                   report, dump, begin_incident, current_incident,
                   end_incident)
from .summarize import read_journal, summarize, format_summary
from .tracing import (Span, span, traced, current_span, current_span_id,
                      spans, span_stats, open_spans, annotate, trace_ctx,
                      current_trace_ids, bind_trace_ids,
                      record_external_span)
from .export import to_perfetto, to_prometheus
from . import memory
from . import flight
from . import tracing
from . import cluster
from . import alerts
from . import stream
from . import agg
from . import programs
from .memory import leak_census
from .flight import postmortem, record_crash
from .cluster import merge_journals, reconstruct_incidents
from .alerts import AlertRule, AlertManager, default_rules, start_sampler, \
    stop_sampler

__all__ = [
    "enabled", "enable", "disable", "configure", "reset",
    "count", "set_gauge", "observe", "event", "record_comm",
    "counter_value", "gauge_value", "comm_bytes", "events",
    "journal_path", "nbytes_of", "report", "dump",
    "begin_incident", "current_incident", "end_incident",
    "read_journal", "summarize", "format_summary",
    "Span", "span", "traced", "current_span", "current_span_id",
    "spans", "span_stats", "open_spans", "annotate", "trace_ctx",
    "current_trace_ids", "bind_trace_ids", "record_external_span",
    "to_perfetto", "to_prometheus",
    "memory", "flight", "tracing", "cluster", "alerts", "stream", "agg",
    "programs",
    "leak_census", "postmortem", "record_crash",
    "merge_journals", "reconstruct_incidents",
    "AlertRule", "AlertManager", "default_rules",
    "start_sampler", "stop_sampler",
]

# arm the always-on health sampler when the env interval is set — same
# import-time auto-install pattern as flight's SIGUSR1 handler; with
# DA_TPU_TELEMETRY=0 or no interval this is a no-op
alerts._maybe_autostart()
# arm the live-plane streaming exporter when DA_TPU_STREAM_AGG is set
# (same pattern); no-op when unset or telemetry is disabled
stream._maybe_autostart()
