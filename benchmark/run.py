"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run, one cell per process. The cell's file
(``workloads/<cell>.json``) names its configuration (``configs/``), its
traffic mix or job (``traffic/``) and its kind; the kind names the driver
(``drivers/<kind>.py``); with ``--trace 1`` every reader in
``layer_metrics/`` whose ``CELLS`` match the cell is applied to the traced
run. A later PR adds a cell, a configuration, a mix, a generator or a
per-layer metric as new files and edits nothing here.

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. Everything else goes to earlier lines (``[bench] ...``; the
checks' verdicts and every compared number beside its limit also to the last
lines of standard error) and, where ``chiprun_out/`` exists, to
``chiprun_out/bench/<cell>.seed<n>.trace<t>.json``. Where the run's seconds
went is on standard error: one ``[bench] wall: <phase> <s>`` line as each
phase ends, and the table ``[bench] walls: {...}`` among the last lines.

It measures on the platform the cell's file names (``tpu`` unless the file
says otherwise — only the ``*-tiny`` rehearsal cells under ``tests/cells``
say ``cpu``) and fails, naming the platform, on any other.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import fnmatch  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (file names carry ``-``, so not by package)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What a driver gets: the cell's three files, the arguments, the clock
    that set-up counts from, and the directories."""

    def __init__(self, args, cells_root: str):
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.cells_root = cells_root
        self.cell = load_json(os.path.join(cells_root, "workloads",
                                           f"{args.workload}.json"))
        self.config = load_json(os.path.join(cells_root, "configs",
                                             f"{self.cell['config']}.json"))
        self.traffic = load_json(self._find("traffic",
                                            f"{self.cell['traffic']}.json"))
        self.t_start = T_START
        from benchmark.drivers.common import Walls

        self.walls = Walls(T_START)  # where the run's seconds go
        self.trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        self.peaks = None
        self.devices = []

    def _find(self, sub: str, name: str) -> str:
        """A cell under ``tests/cells`` may use the benchmark's own mixes,
        generators and references."""
        for base in (self.cells_root, HERE):
            p = os.path.join(base, sub, name)
            if os.path.exists(p):
                return p
        raise FileNotFoundError(f"{sub}/{name} under {self.cells_root} or "
                                f"{HERE}")

    def side_file(self, suffix: str) -> str:
        """A fresh file beside the trace directory for what the program
        writes when asked to (its search log, its telemetry)."""
        os.makedirs(os.path.dirname(self.trace_dir), exist_ok=True)
        path = f"{self.trace_dir}.{suffix}"
        if os.path.exists(path):
            os.remove(path)
        return path

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start

    def generator(self):
        return load_module(self._find("traffic",
                                      f"{self.traffic['generator']}.py"),
                           f"bench_traffic_{self.traffic['generator']}")

    def reference(self):
        return load_module(self._find("reference", self.config["reference"]),
                           "bench_reference")

    def model_config(self, **overrides):
        """The program's own config dataclass, filled from the
        configuration's file through its ``builder.fields`` mapping."""
        b = self.config["builder"]
        mod = importlib.import_module(b["module"])
        kwargs = {field: self.config[key]
                  for field, key in b["fields"].items()}
        kwargs.update(overrides)
        return getattr(mod, b["config_class"])(**kwargs), getattr(
            mod, b["build"])


def check_devices(ctx: Context):
    import jax

    devices = jax.devices()
    want = ctx.cell.get("platform", "tpu")
    platform = devices[0].platform
    if platform != want:
        sys.exit(f"benchmark/run.py: cell {ctx.args.workload!r} measures on "
                 f"platform {want!r}; JAX found platform {platform!r} "
                 f"({len(devices)} device(s)). No fallback: run it on the "
                 f"chip.")
    chips = int(ctx.cell["chips"])
    if len(devices) != chips:
        sys.exit(f"benchmark/run.py: cell {ctx.args.workload!r} asks for "
                 f"{chips} chip(s); JAX found {len(devices)}")
    ctx.devices = devices
    if platform == "tpu":
        from benchmark import flops

        ctx.peaks = flops.peaks(devices[0].device_kind)  # unknown raises
    log(f"platform: {platform} kind: {devices[0].device_kind!r} "
        f"count: {len(devices)}")


def device_block(ctx: Context, facts: dict) -> dict:
    peaks = []
    for d in ctx.devices:
        try:
            peaks.append(int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:  # a backend without memory_stats (the CPU)
            peaks.append(0)
    out = {"platform": ctx.devices[0].platform,
           "kind": ctx.devices[0].device_kind,
           "count": len(ctx.devices),
           "memory_peak_bytes": max(peaks) if peaks else 0}
    tr = facts.get("trace")
    if tr and tr.get("n_devices"):
        out["busy_s"] = tr["busy_mean_s"]
        out["window_s"] = tr["window_s"]
    return out


def layer_metrics(ctx: Context, facts: dict) -> dict:
    out = {}
    folder = os.path.join(HERE, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        mod = load_module(os.path.join(folder, fname),
                          f"bench_metric_{fname[:-3]}")
        if not any(fnmatch.fnmatch(ctx.args.workload, g) for g in mod.CELLS):
            continue
        try:
            value = mod.read(facts)
        except (KeyError, TypeError, ZeroDivisionError) as e:
            log(f"layer metric {mod.NAME}: nothing to read ({e!r})")
            value = None
        if value is not None:
            out[mod.NAME] = {"value": float(value), "unit": mod.UNIT}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cells", default=HERE,
                    help="directory that holds workloads/ configs/ traffic/ "
                         "(the rehearsal cells: benchmark/tests/cells)")
    args = ap.parse_args()
    sys.argv = sys.argv[:1]  # FFConfig() reads sys.argv
    if not os.path.isdir(os.path.join(ROOT, "flexflow_tpu")):
        sys.exit("benchmark/run.py: the system under test (flexflow_tpu/) is "
                 "not beside benchmark/ — nothing to measure")
    sys.path.insert(0, ROOT)
    ctx = Context(args, os.path.abspath(args.cells))
    check_devices(ctx)
    walls = ctx.walls
    walls.add("imports_devices", ctx.since_start())

    driver = load_module(os.path.join(HERE, "drivers",
                                      f"{ctx.cell['kind']}.py"),
                         f"bench_driver_{ctx.cell['kind']}")
    facts = driver.run(ctx)  # the driver measures; facts hold all it saw

    if ctx.trace and facts.get("trace_file"):
        from benchmark import spans
        from benchmark.reduce import program_spans, xplane

        with walls.phase("load_trace"):  # once: both reductions share it
            xplane.load(facts["trace_file"])
        with walls.phase("xplane_reduce"):
            facts["trace"] = xplane.reduce_trace(
                facts["trace_file"], span_names=spans.NAMES,
                window_span=spans.WINDOW, scopes=facts.get("scopes"))
        with walls.phase("program_spans"):
            program_spans.read(facts)  # kept per path: the readers find it
    device = device_block(ctx, facts)
    facts["memory_peak_bytes"] = device["memory_peak_bytes"]
    if ctx.trace:
        with walls.phase("layer_readers"):
            metrics = layer_metrics(ctx, facts)
    else:
        metrics = {k: {"value": float(v[0]), "unit": v[1]}
                   for k, v in facts["end_to_end"].items()}
    result = {"correct": bool(facts["correct"]),
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"]),
              "metrics": metrics, "device": device}
    tr = facts.get("trace")
    if ctx.trace and tr and tr.get("n_devices"):
        result["breakdown"] = {"device_ops": tr["device_ops"][:10],
                               "idle_gaps": tr["idle_gaps"][:10]}
    verdict_lines = [f"check {name}: {ok}"
                     for name, ok in facts.get("checks", {}).items()]
    # every number the comparison with the reference holds to a limit
    verdict_lines += [f"compared {name}: {value:.6g} (limit {limit:g})"
                      for name, value, limit in facts.get("compared", [])]
    for line in verdict_lines:
        log(line)
    facts.setdefault("info", {})["walls_s"] = walls.table()
    facts["info"]["run_wall_s"] = facts["info"]["walls_s"]["run_wall_s"]
    for k, v in facts["info"].items():
        log(f"{k}: {v}")
    side = os.path.join(ROOT, "chiprun_out")
    if os.path.isdir(side):
        os.makedirs(os.path.join(side, "bench"), exist_ok=True)
        keep = {k: facts.get(k) for k in ("checks", "info", "end_to_end",
                                          "trace") if k in facts}
        keep["result"] = result
        with open(os.path.join(side, "bench",
                               f"{args.workload}.seed{args.seed}."
                               f"trace{args.trace}.json"), "w") as f:
            json.dump(keep, f, indent=1, default=str)
    print(json.dumps(result), flush=True)
    # the driver's record of a run that is not correct keeps the end of
    # standard error, and of standard output the last line's keys alone
    # (the benchmark's contract; PERF.md, correct): so the verdicts and each
    # compared number beside its limit are standard error's last lines
    print("\n".join(f"[bench] {line}"
                    for line in [walls.line()] + verdict_lines),
          file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
