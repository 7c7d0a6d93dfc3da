"""From a profiler trace to device busy time, idle gaps and program times.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Its planes are read with ``jax.profiler.ProfileData``: device planes are
named ``/device:TPU:<n>`` and hold a line ``XLA Ops`` (one event per
operation run) and a line ``XLA Modules`` (one per program run); the host
plane holds the harness's spans, named ``bench.*``. All events carry a
start and a duration in nanoseconds on one clock.

Over the stretch that the span ``bench.window`` covers:

* busy: the union of the intervals in which an operation ran, per chip,
  averaged over chips;
* idle gaps: the stretches of the first chip outside that union, each put
  down to the innermost ``bench.*`` span open on the host at its middle;
* device ops and programs: time summed by name. An op's trace name is
  its whole HLO instruction; it is shortened to the instruction's name,
  opcode and result shape. Ops nested in a loop are listed beside the loop,
  whose time holds theirs.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    idle_gaps: list = field(default_factory=list)    # [label, seconds]
    device_ops: list = field(default_factory=list)   # [name, seconds]
    modules: dict = field(default_factory=dict)      # name -> [count, seconds]

    def module_time(self, substring: str) -> tuple[int, float]:
        """(runs, seconds) of the programs whose name holds ``substring``,
        per chip."""
        sel = [v for k, v in self.modules.items() if substring in k]
        return sum(c for c, _ in sel), sum(s for _, s in sel)


def op_label(hlo: str) -> str:
    """``%copy.41 = bf16[1,16]{1,0:T(8,128)} copy(...)`` -> ``%copy.41 copy
    bf16[1,16]``; a tuple result is left out of the label."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    if rest.startswith("("):               # tuple result: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{", 1)[0]
    opcode = rest.split("(", 1)[0]
    return " ".join(x for x in (name, opcode, shape) if x)[:120]


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the harness's spans are TraceMes
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def newest_xplane(log_dir: str) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_planes(planes, top: int = 10) -> TraceSummary:
    """Reduce planes (objects with ``name`` and ``lines``; lines with
    ``name`` and ``events``; events with ``name``, ``start_ns`` and
    ``duration_ns``)."""
    spans, devices = [], []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == MODULES_LINE:
                    mods += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events]
            devices.append((plane.name, ops, mods))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    lo, hi = windows[0]
    if not devices:
        raise ValueError("trace holds no TPU device plane")
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))

    busy, first_merged = [], None
    op_time: dict[str, float] = {}
    modules: dict[str, list] = {}
    for _, ops, mods in devices:
        merged = _merge(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        if first_merged is None:
            first_merged = merged
        for name, s, e in ops:
            if e > lo and s < hi:
                label = op_label(name)
                op_time[label] = op_time.get(label, 0.0) + min(e, hi) - \
                    max(s, lo)
        for name, s, e in mods:
            if s >= lo and e <= hi:
                m = modules.setdefault(name, [0, 0.0])
                m[0] += 1
                m[1] += (e - s) * 1e-9
    n = len(devices)
    modules = {k: [c / n, t / n] for k, (c, t) in modules.items()}

    gaps, cursor = [], lo
    for s, e in first_merged:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    inner = [sp for sp in spans if sp[0] != WINDOW]
    by_label: dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        open_ = [sp for sp in inner if sp[1] <= mid <= sp[2]]
        label = max(open_, key=lambda sp: sp[1])[0] if open_ else WINDOW
        by_label[label] = by_label.get(label, 0.0) + (e - s) * 1e-9

    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / n * 1e-9, n_devices=n,
        idle_gaps=sorted(([k, v] for k, v in by_label.items()),
                         key=lambda kv: -kv[1])[:top],
        device_ops=sorted(([k, v * 1e-9 / n] for k, v in op_time.items()),
                          key=lambda kv: -kv[1])[:top],
        modules=modules)


def reduce(log_dir: str, top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(newest_xplane(log_dir)))
    return reduce_planes(pd.planes, top=top)
