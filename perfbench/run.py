"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process, one cell of ``BENCHMARK.json``.  It turns on the persistent
compilation cache, fails without a TPU (or with fewer chips than the cell
asks for), sets up (inputs from the seed, ``plan()``, warm-up of exactly
this cell's shapes), measures for ``--seconds``, compares what the timed
path produced with the plain reference, and prints one JSON line last:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones read from a profiler
trace of the window), ``device``, with ``--trace 1`` a ``breakdown``, and
``checks``: each compared number beside its limit, also the last lines on
standard error.

Two options serve the bounds and limits, not the driver:
``--control bfloat16`` puts the reference, computed in that precision, in
the timed path's place (the check must refuse it), and ``--rate`` offers a
serving cell another load (the sweep that found its rate).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import spec  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class Cell:
    """What a driver needs of one run: the cell's data, its options, the
    host clock's marks, the compile log and the benchmark's host spans."""

    def __init__(self, workload: str, seed: int, seconds: float,
                 trace: bool, *, config_overrides=None,
                 traffic_overrides=None, control=None, rate=None,
                 t_start=None):
        bench = spec.benchmark()
        self.workload = spec.workload(workload, bench)
        self.name = workload
        self.config = {**spec.config(self.workload["config"], bench),
                       **(config_overrides or {})}
        self.traffic = {**spec.traffic(self.workload["traffic"]),
                        **(traffic_overrides or {})}
        self.chips = int(self.workload["chips"])
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.control, self.rate = control, rate
        self.t_start = T_START if t_start is None else t_start
        self.compiles = []          # (perf_counter at the event, seconds)
        self.host = {}              # host-clock readings for the readers
        self.counters = {}          # the program's own counters
        self.trace_data = None
        self._trace_dir = None
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/") or name in COMPILE_EVENTS:
            self.compiles.append((time.perf_counter(), name, float(secs)))

    def compile_s(self, lo=0.0, hi=float("inf")) -> float:
        return sum(s for t, _, s in self.compiles if lo <= t <= hi)

    def compiles_between(self, lo, hi) -> int:
        return sum(1 for t, n, _ in self.compiles
                   if lo <= t <= hi and n in COMPILE_EVENTS)

    def peak_bytes(self) -> int:
        """Peak device memory of the fullest chip this cell used."""
        import jax
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in jax.devices()[:self.chips]))

    @contextmanager
    def span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield

    def start_trace(self):
        if not self.trace:
            return
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)

    def stop_trace(self):
        if not self.trace:
            return
        import jax
        from perfbench import trace
        jax.profiler.stop_trace()
        try:
            self.trace_data = trace.read(self._trace_dir)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)


def run_cell(cell: Cell) -> dict:
    """Set up, measure, check and reduce one cell; the result line."""
    import jax
    from perfbench import trace as tr
    out = spec.driver(cell.traffic["kind"]).run(cell)
    devices = jax.devices()
    kind = devices[0].device_kind
    cell.peaks = spec.peaks(kind) if devices[0].platform == "tpu" else None
    metrics = {}
    which = "per_layer" if cell.trace else "end_to_end"
    for m in spec.metrics_for(cell.name, which):
        if cell.trace:
            value = spec.metric_reader(m["name"])(cell)
        else:
            value = out["end_to_end"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    breakdown = {}
    if cell.trace and cell.trace_data is not None and cell.trace_data.ops:
        lo, hi = cell.trace_data.window()
        devs = sorted(cell.trace_data.ops)
        device["busy_s"] = sum(tr.busy_ns(cell.trace_data, d, lo, hi)
                               for d in devs) / len(devs) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown["breakdown"] = tr.breakdown(cell.trace_data)
    limits = spec.limits(cell.name)
    # a non-finite reading (a result that is not a number) stays above any
    # limit and stays valid JSON
    checks = {name: {"value": value if math.isfinite(value) else 1e300,
                     "limit": limits[name]["limit"]}
              for name, value in out["checks"].items()}
    correct = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            **breakdown, "compiles_in_window": out["compiles_in_window"],
            "window": out.get("window"), "checks": checks}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bfloat16",), default=None,
                    help="time the reference in this precision instead of "
                         "the program (the check must refuse it)")
    ap.add_argument("--rate", type=float, default=None,
                    help="offered requests/s of a serving cell (the sweep)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"perfbench: the system under test is not in this checkout "
              f"({e})", file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    cell = Cell(args.workload, args.seed, args.seconds, bool(args.trace),
                control=args.control, rate=args.rate)
    if devices[0].platform != "tpu":
        print(f"perfbench: no TPU (JAX found {devices[0].platform}); the "
              "benchmark runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"perfbench: {args.workload} needs {cell.chips} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    result = run_cell(cell)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
