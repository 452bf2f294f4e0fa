"""Tracing: OpenTelemetry with a graceful no-op default.

Reference behavior: metaflow/tracing/ (__init__.py:14-50 no-op shims unless
deps + an endpoint are configured; context
propagates into subprocesses via env). Enable by setting
TPUFLOW_OTEL_ENDPOINT (requires opentelemetry-sdk to be installed).
"""

import os
from contextlib import contextmanager

from . import knobs

_ENDPOINT_VAR = "TPUFLOW_OTEL_ENDPOINT"
_TRACEPARENT_VAR = "TRACEPARENT"

_tracer = None
_initialized = False


def _init():
    global _tracer, _initialized
    if _initialized:
        return _tracer
    _initialized = True
    endpoint = knobs.get_str(_ENDPOINT_VAR)
    if not endpoint:
        return None
    try:
        from opentelemetry import trace
        from opentelemetry.exporter.otlp.proto.http.trace_exporter import (
            OTLPSpanExporter,
        )
        from opentelemetry.sdk.resources import Resource
        from opentelemetry.sdk.trace import TracerProvider
        from opentelemetry.sdk.trace.export import BatchSpanProcessor

        provider = TracerProvider(
            resource=Resource.create({"service.name": "metaflow_tpu"})
        )
        provider.add_span_processor(
            BatchSpanProcessor(OTLPSpanExporter(endpoint=endpoint))
        )
        trace.set_tracer_provider(provider)
        _tracer = trace.get_tracer("metaflow_tpu")
    except ImportError:
        _tracer = None
    return _tracer


@contextmanager
def span(name, attributes=None):
    """Span context manager; no-op when tracing is disabled.

    Spans also tee into the run's flight recorder (telemetry.py) as timer
    records when one is active — the `persist.*` spans around datastore
    ops thereby land in `tpuflow metrics` without double instrumentation —
    and, through the same timer, onto the profiler's clock while a
    profiler session is open.
    Exceptions are recorded on the span (ERROR status) and re-raised,
    never swallowed into a clean span.
    """
    from . import telemetry

    tracer = _init()
    if tracer is None:
        with telemetry.timer(name, data=_span_data(attributes)):
            yield None
        return
    # attributes at creation: samplers and processors see them at
    # span-start, not after the fact
    with telemetry.timer(name, data=_span_data(attributes)):
        with tracer.start_as_current_span(
            name, attributes=attributes or {}, record_exception=True,
            set_status_on_exception=True,
        ) as s:
            yield s


def _span_data(attributes):
    if not attributes:
        return None
    # telemetry records are JSON: keep attribute values primitive
    return {
        k: (v if isinstance(v, (str, int, float, bool)) else str(v))
        for k, v in attributes.items()
    }


def inject_tracing_vars(env):
    """Propagate trace context into a subprocess env.

    With an active OTel tracer the current span context is injected; with
    tracing off, an ambient TRACEPARENT (set by a CI driver, a parent
    scheduler, or ensure_traceparent) is still forwarded so all ranks of
    a gang — and every task of a run — share one trace id in their
    telemetry records."""
    tracer = _init()
    if tracer is None:
        if _TRACEPARENT_VAR in os.environ:
            env.setdefault(_TRACEPARENT_VAR,
                           os.environ[_TRACEPARENT_VAR])
        return env
    try:
        from opentelemetry.propagate import inject

        carrier = {}
        inject(carrier)
        env.update({k.upper().replace("-", "_"): v
                    for k, v in carrier.items()})
    except ImportError:
        pass
    return env


def ensure_traceparent(seed):
    """Make sure this process carries a W3C TRACEPARENT, synthesizing a
    deterministic one from `seed` (the run id) when absent — so OTel
    spans and telemetry records from every task/rank of a run join one
    trace even without an OTel SDK in the tasks. Returns the value."""
    existing = os.environ.get(_TRACEPARENT_VAR)
    if existing:
        return existing
    import hashlib

    digest = hashlib.sha256(("tpuflow-run:%s" % seed).encode()).hexdigest()
    value = "00-%s-%s-01" % (digest[:32], digest[32:48])
    os.environ[_TRACEPARENT_VAR] = value
    return value


# ---------------------------------------------------------------------------
# Per-request trace context (serving path)
#
# The fleet router mints one traceparent per request and forwards it as an
# HTTP header on every dispatch (including failover re-dispatch), deriving a
# fresh child span id per attempt. Replicas stamp the received trace/span
# into every serve.request.* telemetry record, so `tpuflow trace` can
# reassemble queued -> dispatch -> prefill -> first_token -> failover ->
# finished as ONE tree from the records alone. All ids are deterministic
# sha256 derivations: a re-run with the same request ids produces the same
# tree, and no coordination between router and replicas is needed.
# ---------------------------------------------------------------------------

_TRACE_REQUESTS_VAR = "TPUFLOW_TRACE_REQUESTS"


def trace_requests_enabled(env=None):
    """Per-request tracing is on unless TPUFLOW_TRACE_REQUESTS=0."""
    return knobs.get_bool(_TRACE_REQUESTS_VAR, env=env)


def _hexdigest(seed, n):
    import hashlib

    return hashlib.sha256(seed.encode()).hexdigest()[:n]


def request_traceparent(request_id):
    """Mint the root traceparent for one serving request.

    The trace id joins the ambient run trace (TRACEPARENT set by
    ensure_traceparent / the launching driver) when one exists, so request
    subtrees nest under the run; otherwise it is derived from the request
    id alone. The span id is always derived from the request id — it is
    the root of the request's subtree."""
    ambient = os.environ.get(_TRACEPARENT_VAR, "")
    parts = ambient.split("-")
    if len(parts) >= 3 and len(parts[1]) == 32:
        trace_id = parts[1]
    else:
        trace_id = _hexdigest("tpuflow-request-trace:%s" % request_id, 32)
    span_id = _hexdigest("tpuflow-request:%s" % request_id, 16)
    return "00-%s-%s-01" % (trace_id, span_id)


def child_traceparent(traceparent, key):
    """Derive a child traceparent: same trace id, span id keyed off the
    parent span + `key` (e.g. "dispatch-2" for the second dispatch
    attempt). Deterministic so the assembler can re-derive parentage."""
    trace_id, span_id = traceparent_ids(traceparent)
    child = _hexdigest("tpuflow-span:%s:%s" % (span_id, key), 16)
    return "00-%s-%s-01" % (trace_id, child)


def traceparent_ids(traceparent):
    """Split a W3C traceparent into (trace_id, span_id); ("", "") when
    malformed or absent."""
    parts = (traceparent or "").split("-")
    if len(parts) >= 3 and len(parts[1]) == 32 and len(parts[2]) == 16:
        return parts[1], parts[2]
    return "", ""
