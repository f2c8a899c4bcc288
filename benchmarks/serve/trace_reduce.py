"""Reduce a profiler trace (`.xplane.pb`) to the numbers the metrics read.

Reads the file with `jax.profiler.ProfileData` alone.  Device planes are
`/device:TPU:<n>`; on each, the `XLA Ops` line holds one event per executed
operation and the `XLA Modules` line one per executed program.  Host spans
are the benchmark's own `TraceAnnotation`s on the `/host:CPU` plane, on the
same clock.  An operation is named by its HLO instruction without the
numeric suffix (`%fused_gemv.65 = ...` -> `fused_gemv`), a program by its
module name without the fingerprint (`jit_decode(8639...)` -> `jit_decode`).
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict

OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s*=|$)")
MODULE_NAME = re.compile(r"^([^(]+)")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

STEP, PREFILL, DECODE = "bench.step", "bench.prefill", "bench.decode"
HOST_SPANS = (STEP, PREFILL, DECODE)


def op_name(raw: str) -> str:
    m = OP_NAME.match(raw.strip())
    return m.group(1) if m else raw.split(" ", 1)[0]


def module_name(raw: str) -> str:
    m = MODULE_NAME.match(raw)
    return m.group(1) if m else raw


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class TraceSummary:
    """Seconds, over the traced window, averaged over the chips traced."""

    window_s: float
    busy_s: float
    devices: int
    op_s: dict[str, float]                      # op name -> seconds
    module_op_s: dict[tuple[str, str], float]   # (module, op) -> seconds
    module_runs: dict[str, int]                 # module -> executions
    idle_by_host: dict[str, float]              # host activity -> idle seconds
    planes: dict[str, list[str]]                # plane -> its line names

    def kernel_s(self, kernels, module_prefix: str = "") -> float:
        """Device seconds of ops named in `kernels` inside modules whose name
        starts with `module_prefix`."""
        return sum(s for (mod, op), s in self.module_op_s.items()
                   if op in kernels and mod.startswith(module_prefix))

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _host_spans(planes):
    spans = {name: [] for name in HOST_SPANS}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    spans[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return {k: sorted(v) for k, v in spans.items()}


def _inside(spans, t) -> bool:
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= t <= spans[i][1]


def _activity(host, t) -> str:
    if _inside(host[PREFILL], t):
        return "prefill dispatch"
    if _inside(host[DECODE], t):
        return "decode dispatch"
    if _inside(host[STEP], t):
        return "step outside both"
    return "between steps"


def reduce_trace(path: str) -> TraceSummary:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    planes = list(pd.planes)
    host = _host_spans(planes)
    if not host[STEP]:
        raise ValueError(f"{path}: no {STEP!r} span; nothing was traced")
    w0, w1 = host[STEP][0][0], max(e for _, e in host[STEP])
    window = (w1 - w0) * 1e-9
    op_s: dict[str, float] = defaultdict(float)
    module_op_s: dict[tuple[str, str], float] = defaultdict(float)
    module_runs: dict[str, int] = defaultdict(int)
    idle_by_host: dict[str, float] = defaultdict(float)
    busy_total, n_dev = 0.0, 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        n_dev += 1
        mods = []
        for ev in lines["XLA Modules"].events if "XLA Modules" in lines else ():
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e > w0 and s < w1:
                name = module_name(ev.name)
                mods.append((s, e, name))
                module_runs[name] += 1
        mods.sort()
        starts = [m[0] for m in mods]
        events = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in lines["XLA Ops"].events)
        intervals = []
        for j, (start, end, raw) in enumerate(events):
            s, e = max(start, w0), min(end, w1)
            if e <= s:
                continue
            intervals.append((s, e))
            if j + 1 < len(events) and events[j + 1][0] < end:
                continue  # a loop or call whose body's ops are events of their own
            sec = (e - s) * 1e-9
            name = op_name(raw)
            op_s[name] += sec
            i = bisect.bisect_right(starts, start) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= start else "?"
            module_op_s[(mod, name)] += sec
        busy = _union(intervals)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle_by_host[_activity(host, (a + b) / 2)] += (b - a) * 1e-9
    scale = 1.0 / max(n_dev, 1)  # no TPU plane (a CPU rehearsal): nothing to read
    return TraceSummary(
        window_s=window,
        busy_s=busy_total * scale,
        devices=n_dev,
        op_s={k: v * scale for k, v in op_s.items()},
        module_op_s={k: v * scale for k, v in module_op_s.items()},
        module_runs={k: v // n_dev for k, v in module_runs.items()},
        idle_by_host={k: v * scale for k, v in idle_by_host.items()},
        planes={p.name: [line.name for line in p.lines] for p in planes},
    )
