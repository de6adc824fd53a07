"""fedtrace — the sync-free round-telemetry plane (ISSUE 4).

Three layers, one overhead contract (ZERO extra host syncs, ZERO extra
steady-state compiles on the round hot path — pinned by the
``JaxRuntimeAudit``-based tests in ``tests/test_fedtrace.py``):

1. **Device-carry metrics** (:mod:`.carry`): a fixed-shape
   :class:`ObsCarry` pytree (per-phase FLOP weights, cohort counters,
   update norm) computed INSIDE the compiled round and returned through
   the existing metrics pytree, so it rides the same ``jit``/``lax.scan``
   outputs the loss does and materializes only on the driver's existing
   eval/log-round syncs.
2. **Host spans + counters** (:mod:`.tracer`): a thread-safe
   :class:`Tracer` recording staging spans + queue depth, XLA compile
   events with durations (through the shared :mod:`.jaxhooks` monitoring
   hub the runtime auditor also uses), the ``device_put_bytes`` counter
   of what staging put on the device, and comm-manager RTT spans —
   exported as Chrome trace-event JSON (loadable in Perfetto /
   ``chrome://tracing``) plus a Prometheus-style aggregate text dump.
3. **Analysis** (``tools/fedtrace.py``): ``summarize`` turns a trace
   into a per-phase (staging / gather / client steps / merge / server
   update) time breakdown; ``diff`` compares two traces.

fedmon (ISSUE 14) extends the plane with federation-health observability:
:mod:`.health` (robust per-client anomaly / drift detection + declarative
SLO rules over the per-client stat rows the engines compute in-trace) and
:mod:`.metricsd` (the threaded ``/metrics`` · ``/healthz`` ·
``/debug/health`` endpoint behind ``args.metrics_port``).

See ``docs/OBSERVABILITY.md`` for the attribution model and the Perfetto
how-to.
"""

from __future__ import annotations

from . import context  # noqa: F401  (fedscope trace-context propagation)
from .health import (  # noqa: F401  (stdlib-only, like the tracer)
    DEFAULT_SLO_RULES,
    HealthConfig,
    HealthMonitor,
    evaluate_slos,
    load_slo_rules,
)
from .tracer import (  # noqa: F401
    DEVICE_PHASES,
    PHASES,
    Tracer,
    configure,
    escape_label_value,
    get_tracer,
    sanitize_metric_name,
    trace_enabled,
)

#: symbols resolved lazily so importing :mod:`fedml_tpu.obs` (e.g. from a
#: comm manager that never touches jax) stays stdlib-light; :mod:`.carry`
#: pulls in jax + flax.
_CARRY_EXPORTS = ("ObsCarry", "OPT_FLOPS", "obs_host", "obs_host_rows",
                  "param_count", "round_obs")
#: :mod:`.metricsd` exports, lazy for the same reason (http.server)
_METRICSD_EXPORTS = ("MetricsServer", "parse_prometheus_text",
                     "prom_value", "start_from_args")
#: fedslo exports (:mod:`.histogram` / :mod:`.slo` / :mod:`.canary`) —
#: stdlib-only, lazy so disabled-telemetry imports stay featherweight
_FEDSLO_EXPORTS = {
    "BoundedLabels": "histogram", "Histogram": "histogram",
    "ServeHistograms": "histogram",
    "buckets_from_samples": "histogram",
    "merge_bucket_entries": "histogram",
    "quantile_from_buckets": "histogram",
    "BURN_WINDOWS": "slo", "ObjectiveWindow": "slo",
    "evaluate_objective_rules": "slo", "windows_for_rules": "slo",
    "CanaryJudge": "canary", "validate_audit_log": "canary",
}

__all__ = ["DEVICE_PHASES", "PHASES", "DEFAULT_SLO_RULES", "HealthConfig",
           "HealthMonitor", "Tracer", "configure", "context",
           "escape_label_value", "evaluate_slos", "get_tracer",
           "load_slo_rules", "sanitize_metric_name", "trace_enabled",
           *_CARRY_EXPORTS, *_METRICSD_EXPORTS, *_FEDSLO_EXPORTS]


def __getattr__(name):
    if name in _CARRY_EXPORTS:
        from . import carry
        return getattr(carry, name)
    if name in _METRICSD_EXPORTS:
        from . import metricsd
        return getattr(metricsd, name)
    if name in _FEDSLO_EXPORTS:
        import importlib
        mod = importlib.import_module(
            f".{_FEDSLO_EXPORTS[name]}", __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
