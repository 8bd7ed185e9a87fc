"""From a profiler trace to device intervals and host spans.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU its planes ``/device:TPU:<i>`` carry a line ``XLA Ops`` whose
events are the HLO instructions the chip ran, named by their HLO text
(``%superstep_chain.6 = f32[...] custom-call(...)``), with start and
duration in nanoseconds on the host's clock.  The benchmark's own host
spans are the ``TraceAnnotation``s it opens, named ``bench.<what>``, on the
host plane.

A :class:`Trace` keeps only those, in a form that JSON holds (the tests
check the reducers on small traces kept so).
"""
from __future__ import annotations

import dataclasses
import glob
import re

#: instructions that only contain others (a ``while`` spans its whole loop)
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "bench."
_NAME = re.compile(r"^%?([^\s=]+)")


def short_name(op: str) -> str:
    """``%superstep_chain.6 = f32[...] ...`` -> ``superstep_chain.6``."""
    m = _NAME.match(op)
    return m.group(1) if m else op


def base_name(op: str) -> str:
    """The instruction name without its ``.N`` suffix: ``superstep_chain``."""
    return re.sub(r"\.\d+$", "", short_name(op))


@dataclasses.dataclass
class Trace:
    #: device index -> [(op text, start_ns, end_ns)], sorted by start
    ops: dict
    #: [(span name, start_ns, end_ns)] of the benchmark's host spans
    spans: list

    def window(self, name: str = "bench.window") -> tuple:
        ws = [(s, e) for n, s, e in self.spans if n == name]
        if not ws:
            raise ValueError(f"no host span {name!r} in the trace")
        return ws[0]

    def leaf_ops(self, device) -> list:
        return [o for o in self.ops[device]
                if base_name(o[0]) not in CONTAINERS]

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops={int(k): [tuple(o) for o in v]
                        for k, v in d["ops"].items()},
                   spans=[tuple(s) for s in d["spans"]])


def read(log_dir: str) -> Trace:
    """The :class:`Trace` of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise ValueError(f"expected one xplane.pb under {log_dir}, found "
                         f"{paths}")
    data = ProfileData.from_file(paths[0])
    ops, spans = {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                ops[int(m.group(1))] = sorted(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans += [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s[1]))


# --- interval arithmetic -----------------------------------------------------

def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals) -> list:
    """Disjoint, sorted cover of ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals, lo: int, hi: int) -> list:
    """The parts of ``[lo, hi)`` that no interval covers."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_ns(trace: Trace, device, lo: int, hi: int) -> int:
    """Time in ``[lo, hi)`` in which some operation ran on ``device``."""
    return length(clip([(s, e) for _, s, e in trace.leaf_ops(device)],
                       lo, hi))


def span_at(trace: Trace, t: int, skip=("bench.window",)) -> str:
    """The innermost benchmark span open at ``t`` (the latest to start)."""
    best = None
    for n, s, e in trace.spans:
        if s <= t < e and n not in skip and (best is None or s >= best[1]):
            best = (n, s)
    return best[0] if best else "outside any benchmark span"


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (seconds per chip, by
    instruction name) and the longest idle gaps, each named by the host
    span open at its middle, over the traced window."""
    lo, hi = trace.window()
    devs = sorted(trace.ops)
    per_op = {}
    idle = []
    for d in devs:
        leaf = trace.leaf_ops(d)
        for op, s, e in leaf:
            cut = clip([(s, e)], lo, hi)
            if cut:
                k = base_name(op)
                per_op[k] = per_op.get(k, 0) + (cut[0][1] - cut[0][0])
        idle += [(d, s, e) for s, e in gaps([(s, e) for _, s, e in leaf],
                                            lo, hi)]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle.sort(key=lambda g: g[1] - g[2])
    return {"device_ops": [[k, v / 1e9 / len(devs)] for k, v in ops],
            "idle_gaps": [[span_at(trace, (s + e) // 2)
                           + ("" if len(devs) == 1 else f"@tpu{d}"),
                           (e - s) / 1e9] for d, s, e in idle[:top]]}


def idle_share(trace: Trace):
    """Percent of the traced window in which no operation ran, averaged
    over the traced devices (None when the trace holds no device)."""
    lo, hi = trace.window()
    devs = sorted(trace.ops)
    if not devs:
        return None
    busy = sum(busy_ns(trace, d, lo, hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
