"""flexflow_tpu.obs: tracing + telemetry subsystem.

The runtime's observability layer (the Legion Prof / per-op ``--profiling``
analog, SURVEY §1 L0):

* ``trace``: thread-safe span/event tracer with Chrome trace-event JSON
  export (Perfetto-loadable) and a JSONL event sink. Disabled by default via
  a no-op singleton — ``enable()`` swaps in a live tracer. ``span`` /
  ``step_span`` / ``SPANS``: the hot loops' spans (``fit`` with its input
  pipeline, the serve tick) as ``jax.profiler`` annotations on the
  profiler's clock; the profiler session is their switch. ``setup_span`` /
  ``setup_walls``: the same for the phases of the set-up, with their walls
  kept in a process-wide table.
* ``builds``: one record per build of a jitted program — its name, the
  phase it fell in, the seconds tracing, lowering, loading and compiling,
  hit or miss in the persistent cache (``builds()``, ``build_mark()``,
  ``build_totals()``); always on, fed by ``jax.monitoring`` listeners.
* ``telemetry``: per-step training telemetry (wall times, loss history,
  compile-vs-steady split, samples/sec, estimated MFU, XLA peak memory) and
  the Unity/MCMC per-iteration search log.
* ``reqtrace``: request-level distributed tracing for the serving stack
  (ISSUE 16) — per-request lifecycle timelines finalized into a versioned
  ``RequestRecord`` JSONL stream + Perfetto spans, plus the fleet's
  per-tick ``FleetTimeSeries`` ring buffers. Disabled by default via the
  same no-op-singleton idiom — ``enable_reqtrace()`` swaps in a live
  recorder.
* xprof passthroughs: ``start_server`` / ``start_trace`` / ``stop_trace`` /
  ``trace`` wrap ``jax.profiler`` so per-op ``jax.named_scope`` annotations
  (Executor.forward_outputs) show up in XLA/xprof traces.

Nothing in this package allocates in the jitted path; all instrumentation is
host-side and gated on ``get_tracer().enabled``.
"""
from .builds import build_mark, build_totals, builds  # noqa: F401
from .trace import (SPANS, NoopTracer, Tracer,  # noqa: F401
                    atomic_write_json, disable, enable, get_tracer,
                    set_tracer, setup_span, setup_walls, span, step_span)
from .reqtrace import (FleetTimeSeries, NoopRequestTrace,  # noqa: F401
                       RequestTrace, disable_reqtrace, enable_reqtrace,
                       get_reqtrace, set_reqtrace)
from .telemetry import (SearchLog, StepTelemetry,  # noqa: F401
                        capture_memory_analysis, detect_peak_flops,
                        model_flops_per_step)


def start_server(port: int = 9012):
    """Start the xprof/TensorBoard profiler server (jax.profiler
    passthrough); connect with TensorBoard's profile tab or xprof."""
    import jax

    return jax.profiler.start_server(port)


def start_trace(log_dir: str, **kwargs) -> None:
    """Begin an XLA profiler trace into ``log_dir`` (jax.profiler
    passthrough). Per-op names from Executor's ``jax.named_scope`` wrapping
    appear in the resulting xprof timeline."""
    import jax

    jax.profiler.start_trace(log_dir, **kwargs)


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def trace(log_dir: str, **kwargs):
    """Context manager variant: ``with obs.trace(dir): ...`` (jax.profiler
    passthrough)."""
    import jax

    return jax.profiler.trace(log_dir, **kwargs)


trace_dir = trace  # surface alias: obs.trace_dir(dir) reads naturally too
