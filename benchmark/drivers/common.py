"""What both drivers share: the seed of the comparison with the reference,
the compile counter, the profiler's start and stop, the compile cache's
thresholds, the kernel names in a compiled program's text, and the one door
to members of the program that no public entry point gives."""
from __future__ import annotations

import contextlib
import glob
import json
import os
import re
import shutil
import sys
import time

# The comparison with the plain reference is about the program, not about the
# run: the weights and the inputs it is made on come from this constant, so
# its statistics are one value per program and cell. A run's ``--seed`` draws
# what is timed (the training set, the window's arrivals, lengths and
# prompts), which no check's numbers depend on. Until PR 29 every run drew
# new weights and a new check sequence, and the statistics' seed-to-seed tail
# (PERF.md, correct) refused PRs on their parent's runs. Never another value
# "because it passes": a tolerance is set from many seeds' samples (PERF.md).
CHECK_SEED = 0


class Walls:
    """Where a run's seconds went. Every phase prints one line to standard
    error as it ends (``[bench] wall: <phase> <s> (since start <s>)``,
    flushed), so that a run stopped at a time limit shows, in what is kept
    of its standard error, the last phase it finished; ``line()`` is the
    whole table, for the run's last lines. A phase that recurs is summed.
    Nothing here is read by a metric: the walls say what a run cost, not
    what the program did."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.seconds = {}

    def add(self, phase: str, seconds: float) -> None:
        self.seconds[phase] = self.seconds.get(phase, 0.0) + seconds
        print(f"[bench] wall: {phase} {seconds:.2f} (since start "
              f"{time.perf_counter() - self.t_start:.2f})", file=sys.stderr,
              flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t)

    def table(self) -> dict:
        """Seconds by phase in the order they first ended, and the whole
        process so far (``run_wall_s``; the phases leave out what lies
        between them)."""
        out = {k: round(v, 2) for k, v in self.seconds.items()}
        out["run_wall_s"] = round(time.perf_counter() - self.t_start, 2)
        return out

    def line(self) -> str:
        return "walls: " + json.dumps(self.table())


def mosaic_calls(compiled_text: str) -> set:
    """Names of the Mosaic (``tpu_custom_call``) kernels in a compiled
    program's text (a copy of ``chip_smoke.mosaic_calls``)."""
    names = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"(\w+)\)*/pallas_call", line)
            names.add(m.group(1) if m else "<unnamed>")
    return names


def program_member(obj, name: str, what_for: str):
    """A member of the program that the benchmark reads although no public
    entry point hands it out (PERF.md, Open questions, lists every one for
    the ``tracing`` issue to replace by a hook). Where a later PR has
    renamed or removed it, the run stops with a line that says what is
    missing and what for, and not with an AttributeError."""
    if not hasattr(obj, name):
        raise SystemExit(
            f"benchmark: the program's {type(obj).__name__} has no "
            f"{name!r} any more; the benchmark needs it for {what_for}. "
            f"Give the program a public hook for that and point the "
            f"benchmark's driver at it in a benchmark PR (PERF.md, Open "
            f"questions).")
    return getattr(obj, name)


class CompileCounter:
    """Counts what JAX lowers and compiles, so that a window can show it
    compiled nothing."""

    KEYS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
            "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.n = 0
        self.names = []  # what was lowered, in order (JAX's ``fun_name``)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event in self.KEYS:
            self.n += 1
            if event == self.KEYS[0]:
                self.names.append(str(kwargs.get("fun_name", "?")))


def start_trace(ctx) -> None:
    import jax

    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    os.makedirs(ctx.trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer is most of the overhead
    opts.host_tracer_level = 2
    jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)


def stop_trace(ctx) -> str:
    import jax

    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(ctx.trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return found[0] if found else ""


def place_cache() -> None:
    """Every program goes to the persistent cache, also the small ones that
    compile in under a second (JAX's default leaves those out), so that only
    a checkout's first run compiles."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
