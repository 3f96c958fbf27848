"""Reduce a JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  Device planes are ``/device:TPU:<n>``;
on each, the line ``XLA Ops`` holds one event per executed HLO op.  The host
plane holds the harness's ``TraceAnnotation`` spans: ``bench_window`` around
the measured window, and ``data``, ``dispatch`` and ``fence`` inside it.

Per device, within the window:

* busy: the union of the op intervals (a loop's own event included: the
  device runs its control between the body's ops); idle is the rest of the
  window;
* time by named scope: the summed durations of the innermost op events
  (a ``while`` or ``call`` event encloses the events of its body) whose
  ``op_name`` metadata in the compiled HLO text has that scope as a path
  component (a fusion carries its root's ``op_name``);
* exposed collective time: the union of the collective ops' intervals
  (``all-reduce``, ``all-gather``, ``reduce-scatter``,
  ``collective-permute``, ``all-to-all`` and their ``-start``/``-done``
  halves, by HLO opcode) less the part that non-collective ops cover;
* idle gaps: each stretch between busy intervals, labelled by the host span
  open at its middle;
* memory: the largest sum of the bytes allocated to arrays and the bytes
  the runtime holds reserved for programs' temporaries, as the allocator's
  ``MemoryAllocation`` and ``MemoryDeallocation`` events on the host plane
  record them at the same instant (``index_on_host`` names the device).

Numbers are averaged over the devices, except the idle gaps, which are
listed per gap.  ``python3 trace_reduce.py --describe <dir>`` prints the
planes, lines and a few events of a trace, to look at one by hand.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench_window"
HOST_SPANS = ("data", "dispatch", "fence")
SCOPES = ("fwd_bwd", "optimizer")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def load(trace_dir):
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = .*?metadata=\{op_name="([^"]*)"',
                    re.M)
_OPCODE = re.compile(r" = .*?\b([a-z][a-z0-9\-]*)\(")


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """{HLO instruction name: its ``op_name`` metadata} of a compiled
    module's text (``compiled.as_text()``)."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


@dataclasses.dataclass(frozen=True)
class Op:
    name: str                  # HLO instruction name, without the %
    opcode: str
    start: float               # ns
    end: float
    path: str                  # op_name metadata ("" when unknown)

    def in_scope(self, scope: str) -> bool:
        return re.search(rf"(^|/){re.escape(scope)}(/|$)", self.path) is not None

    @property
    def collective(self) -> bool:
        return COLLECTIVE.fullmatch(self.opcode.removesuffix("-start")
                                    .removesuffix("-done")) is not None

    def label(self) -> str:
        path = re.sub(r"^(jit\([^)]*\)/)+", "", self.path)
        return f"{self.name} [{path[-80:]}]" if path else self.name


def _op(ev, scopes: dict[str, str]) -> Op:
    text = ev.name
    name = text.split(" = ", 1)[0].lstrip("%")
    m = _OPCODE.search(text)
    return Op(name, m.group(1) if m else name, float(ev.start_ns),
              float(ev.end_ns), scopes.get(name, ""))


def device_ops(pd, scopes: dict[str, str] | None = None) -> dict[int, list[Op]]:
    out: dict[int, list[Op]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[int(m.group(1))] = [_op(ev, scopes or {})
                                        for ev in line.events]
    return out


def leaves(ops: list[Op]) -> list[Op]:
    """The ops that enclose no other op (a loop's event encloses its body's)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    container = [False] * len(ops)
    stack: list[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack:
            container[stack[-1]] = True
        stack.append(i)
    return [o for o, c in zip(ops, container) if not c]


def host_spans(pd) -> list[tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW or ev.name in HOST_SPANS:
                    out.append((ev.name, float(ev.start_ns), float(ev.end_ns)))
    return sorted(out, key=lambda s: s[1])


MEMORY_EVENTS = ("MemoryAllocation", "MemoryDeallocation")


def memory_peaks(pd, w0: float, w1: float) -> dict[int, int]:
    """{device index: largest bytes allocated + reserved} over the allocator
    events between ``w0`` and ``w1`` (ns)."""
    out: dict[int, int] = {}
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name not in MEMORY_EVENTS or not w0 <= ev.start_ns <= w1:
                    continue
                st = dict(ev.stats)
                if "index_on_host" not in st or "bytes_allocated" not in st:
                    continue
                d = int(st["index_on_host"])
                held = int(st["bytes_allocated"]) + int(st.get("bytes_reserved", 0))
                out[d] = max(out.get(d, 0), held)
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs, ys) -> list[tuple[float, float]]:
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # mean over devices
    steps: int                        # dispatches inside the window
    scope_s: dict                     # scope -> seconds, mean over devices
    collective_s: float               # mean over devices
    collective_exposed_s: float
    top_ops: list                     # [(name, seconds)], mean over devices
    idle_gaps: list                   # [(host span, seconds)], longest first
    memory_peak_bytes: int | None     # largest over devices; None: no events

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def summarize(pd, chips: int | None = None, hlo_text: str = "",
              scopes=SCOPES) -> Summary:
    spans = host_spans(pd)
    windows = [(a, b) for n, a, b in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span on the host plane")
    w0, w1 = windows[-1]
    inner = [(n, a, b) for n, a, b in spans if n != WINDOW and a >= w0 and b <= w1]
    steps = sum(1 for n, _, _ in inner if n == "dispatch")
    per_dev = device_ops(pd, hlo_scopes(hlo_text))
    if chips is not None:
        per_dev = {d: ops for d, ops in per_dev.items() if d < chips}
    if not per_dev:
        raise ValueError("no device plane with an 'XLA Ops' line")
    n = len(per_dev)
    busy = coll = exposed = 0.0
    scope_ns = {s: 0.0 for s in scopes}
    by_name: dict[str, float] = {}
    gaps: list[tuple[str, float]] = []
    for ops in per_dev.values():
        ops = [dataclasses.replace(o, start=max(o.start, w0), end=min(o.end, w1))
               for o in ops if o.end > w0 and o.start < w1]
        busy_iv = union((o.start, o.end) for o in ops)
        ops = leaves(ops)
        busy += length(busy_iv)
        coll_iv = union((o.start, o.end) for o in ops if o.collective)
        comp_iv = union((o.start, o.end) for o in ops if not o.collective)
        coll += length(coll_iv)
        exposed += length(coll_iv) - length(intersect(coll_iv, comp_iv))
        for s in scopes:
            scope_ns[s] += sum(o.end - o.start for o in ops if o.in_scope(s))
        for o in ops:
            by_name[o.label()] = by_name.get(o.label(), 0.0) + (o.end - o.start)
        edges = [w0] + [x for iv in busy_iv for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                label = next((nm for nm, s0, s1 in inner if s0 <= mid <= s1),
                             "other")
                gaps.append((label, (b - a) * 1e-9))
    top = sorted(((k, v * 1e-9 / n) for k, v in by_name.items()),
                 key=lambda kv: -kv[1])
    mem = {d: b for d, b in memory_peaks(pd, w0, w1).items() if d in per_dev}
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / n,
                   steps=steps,
                   scope_s={s: v * 1e-9 / n for s, v in scope_ns.items()},
                   collective_s=coll * 1e-9 / n,
                   collective_exposed_s=exposed * 1e-9 / n,
                   top_ops=top, idle_gaps=sorted(gaps, key=lambda g: -g[1]),
                   memory_peak_bytes=max(mem.values()) if mem else None)


def describe(pd, events_per_line: int = 4) -> None:
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:events_per_line]:
                print(f"    {ev.name!r} start {ev.start_ns} dur {ev.duration_ns} "
                      f"stats {list(ev.stats)}")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--describe":
        describe(load(sys.argv[2]))
    else:
        sys.exit("usage: trace_reduce.py --describe <trace dir>")
