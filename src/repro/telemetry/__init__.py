"""Unified telemetry: span tracing, metric timelines, exporters, and
trace-derived workload profiles (see ``docs/telemetry.md``).

The core (``Tracer``/``Span``/``Event``/``NULL_TRACER``) is stdlib-only so
every serving layer can import it without cost or cycles; the profile
functions lazily import the tuner stack on first use.
"""
from repro.telemetry.export import (coerce_tracer, load_jsonl,
                                    to_chrome_trace, write_chrome_trace,
                                    write_jsonl)
from repro.telemetry.profile import (MIN_ACTIVITY, TraceSummary,
                                     phases_from_trace, profile_from_trace,
                                     summarize_trace)
from repro.telemetry.tracer import (NULL_SPAN, NULL_TRACER, Event,
                                    NullTracer, Span, StepSpan, Tracer)

__all__ = [
    "Event", "NullTracer", "NULL_SPAN", "NULL_TRACER", "Span", "StepSpan",
    "Tracer",
    "coerce_tracer", "load_jsonl", "to_chrome_trace", "write_chrome_trace",
    "write_jsonl",
    "MIN_ACTIVITY", "TraceSummary", "phases_from_trace",
    "profile_from_trace", "summarize_trace",
]
