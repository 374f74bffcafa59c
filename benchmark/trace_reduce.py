"""From a profiler trace (.xplane.pb) to intervals, and from intervals to
the numbers the per-layer metrics and the breakdown are made of.

Two halves. The interval arithmetic (union, gaps, subtraction,
attribution) is pure and works on (start, end) pairs in any one unit. The
reader turns one xplane into a `Trace`: per device the executed XLA ops
and XLA module runs, and the program's host spans that were mirrored into
the trace as `TraceAnnotation`s — all on the profiler's own clock, in
nanoseconds, so device gaps can be laid against what the host was doing.

What a TPU xplane looks like (jax 0.9.0 / libtpu 0.0.34, looked at by
hand in PR 22): one plane per chip named `/device:TPU:<n>`, with the lines
`XLA Modules` (one event per executed program, named `jit_<fn>(<hash>)`),
`XLA Ops` (one event per executed HLO instruction), `Async XLA Ops` (one
event per asynchronous pair, from its -start to its -done) and `Steps`.
An op event's name is the whole HLO instruction as text
(`%fusion.15 = bf16[256,112,112,64]{...} fusion(...), kind=kLoop, calls=...`)
and it carries no category, so the category is read from that text: the
opcode, and for a fusion its kind (on the TPU a convolution and what is
fused onto its output is a `kind=kOutput` fusion). Host threads are lines
of the plane `/host:CPU`; the program's spans are on the line of the
thread that opened them, on the same clock as the device events. On the
CPU backend (rehearsals only) there is no device plane: executed thunks
are host-plane events with an `hlo_op` stat, which the reader takes as
device 0.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

# Host spans the program records (obs/trace.py mirrors them into the
# profiler). Order is the order of attribution where spans of different
# threads overlap: the span that explains the device's wait best first.
HOST_SPANS = ("zoo.readback", "zoo.dispatch", "zoo.data",
              "serve.batch", "serve.coalesce")

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# `%name = shape opcode(operands), attributes` -> name; opcode is the word
# before the first "(" that follows " = <shape> ".
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = .*?[\]})] (?P<opcode>[a-z][a-z\-]*)\(")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?$")


# ---------------------------------------------------------------- intervals

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; ascending, disjoint."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of merged list `a` that merged list `b` does not cover.
    One pass over both."""
    out: List[Interval] = []
    j, nb = 0, len(b)
    for s, e in a:
        while j < nb and b[j][1] <= s:
            j += 1
        at, k = s, j
        while k < nb and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that a merged interval list leaves open."""
    return subtract([(lo, hi)], merged) if hi > lo else []


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    return subtract(a, subtract(a, b))


def attribute(open_gaps: Sequence[Interval],
              spans: Dict[str, Sequence[Interval]],
              order: Sequence[str] = HOST_SPANS) -> Dict[str, float]:
    """Split idle time by the host span open at the time. Where spans of
    several names overlap, the earliest in `order` takes the time; what no
    span covers is "unattributed"."""
    out: Dict[str, float] = {}
    rest = union(open_gaps)
    for name in order:
        cover = union(spans.get(name, ()))
        if not cover:
            continue
        took = total(intersect(rest, cover))
        if took > 0:
            out[name] = took
        rest = subtract(rest, cover)
    left = total(rest)
    if left > 0:
        out["unattributed"] = left
    return out


def exposed(collective: Iterable[Interval], compute: Iterable[Interval]) -> float:
    """Time inside collectives during which no compute op runs."""
    return total(subtract(union(collective), union(compute)))


# ------------------------------------------------------------------- reader

@dataclasses.dataclass
class Op:
    name: str      # the HLO instruction's name, e.g. "fusion.15"
    category: str  # "conv" | "collective" | "other"
    start: float
    end: float


def parse_op(text: str) -> Tuple[str, str]:
    """(name, category) of one executed op from the event's name: the HLO
    instruction as text on the TPU, the bare instruction name elsewhere.
    conv: a convolution, or a fusion built on one (kind=kOutput on the
    TPU, or named after it); collective: the cross-chip ops, start and done
    halves included; other: the rest (BatchNorm and elementwise chains,
    reductions, copies, the optimizer)."""
    m = _HLO.match(text)
    name, opcode = (m.group("name"), m.group("opcode")) if m else (
        text.lstrip("%"), re.sub(r"[.\d]+$", "", text.lstrip("%")))
    if _COLLECTIVE.match(opcode):
        return name, "collective"
    if (opcode == "convolution" or "convolution" in name
            or "conv_general_dilated" in name
            or (opcode == "fusion" and "kind=kOutput" in text)):
        return name, "conv"
    return name, "other"


@dataclasses.dataclass
class Trace:
    """One traced window. Times in ns on the profiler's clock."""

    ops: Dict[int, List[Op]]          # device -> executed ops, in order
    async_ops: Dict[int, List[Op]]    # device -> async pairs, start to done
    modules: Dict[int, List[Tuple[str, float, float]]]  # device -> runs
    host: Dict[str, List[Interval]]   # span name -> intervals
    _busy: Dict[int, List[Interval]] = dataclasses.field(default_factory=dict)

    @property
    def window(self) -> Optional[Interval]:
        """First op start to last op end over all devices."""
        ends = [(ops[0].start, max(o.end for o in ops))
                for ops in self.ops.values() if ops]
        if not ends:
            return None
        return min(s for s, _ in ends), max(e for _, e in ends)

    def busy(self, dev: int) -> List[Interval]:
        """Union of the executed ops' intervals on one device."""
        if dev not in self._busy:
            self._busy[dev] = union((o.start, o.end) for o in self.ops[dev])
        return self._busy[dev]

    def by_category(self, dev: int, category: str) -> List[Interval]:
        return [(o.start, o.end) for o in self.ops[dev]
                if o.category == category]

    def collectives(self, dev: int) -> List[Interval]:
        """Collective time on one device: the synchronous collective ops
        and the asynchronous ones from their start to their done."""
        return union(self.by_category(dev, "collective") + [
            (o.start, o.end) for o in self.async_ops.get(dev, ())
            if o.category == "collective"])

    def compute(self, dev: int) -> List[Interval]:
        """Union of the executed ops that are no collective."""
        return union((o.start, o.end) for o in self.ops[dev]
                     if o.category != "collective")

    def runs(self, dev: int, pattern: str) -> List[Interval]:
        """Executions of the modules whose name matches `pattern`."""
        rx = re.compile(pattern)
        return [(s, e) for n, s, e in self.modules.get(dev, ()) if rx.search(n)]

    def busy_per_run(self, dev: int, pattern: str) -> List[float]:
        """Device-busy ns inside each matching module run."""
        runs = union(self.runs(dev, pattern))
        merged = self.busy(dev)
        return [total(intersect([r], merged)) for r in runs]


def _events(line):
    for ev in line.events:
        s = float(ev.start_ns)
        yield ev, s, s + float(ev.duration_ns)


def _ops(line) -> List[Op]:
    cache: Dict[str, Tuple[str, str]] = {}
    out = []
    for ev, s, e in _events(line):
        text = ev.name
        if text not in cache:
            cache[text] = parse_op(text)
        out.append(Op(*cache[text], s, e))
    return out


def read_xplane(source, span_names: Sequence[str] = HOST_SPANS) -> Trace:
    """Read an .xplane.pb (a path, or the serialized bytes)."""
    from jax.profiler import ProfileData

    data = (ProfileData.from_serialized_xspace(source)
            if isinstance(source, (bytes, bytearray))
            else ProfileData.from_file(source))
    ops: Dict[int, List[Op]] = {}
    async_ops: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Tuple[str, float, float]]] = {}
    host: Dict[str, List[Interval]] = {}
    wanted = set(span_names)
    host_lines = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = _ops(line)
                elif line.name == "Async XLA Ops":
                    async_ops[dev] = _ops(line)
                elif line.name == "XLA Modules":
                    modules[dev] = [(ev.name, s, e) for ev, s, e in _events(line)]
        elif plane.name == "/host:CPU":
            host_lines = list(plane.lines)
    for line in host_lines:
        for ev, s, e in _events(line):
            if ev.name in wanted:
                host.setdefault(ev.name, []).append((s, e))
    if not ops:
        _read_cpu_thunks(host_lines, ops, modules)
    return Trace(ops=ops, async_ops=async_ops, modules=modules, host=host)


def _read_cpu_thunks(host_lines, ops, modules) -> None:
    """CPU backend (rehearsal): executed thunks (host events with an
    `hlo_op` stat) stand in for device 0, and a module run is the span of
    one run_id's thunks."""
    thunks: List[Op] = []
    runs: Dict[Tuple, List[float]] = {}
    for line in host_lines:
        for ev, s, e in _events(line):
            if e <= s:
                continue
            stats = dict(ev.stats)
            if "hlo_op" not in stats:
                continue
            thunks.append(Op(*parse_op(ev.name), s, e))
            key = (str(stats.get("hlo_module", "")), stats.get("run_id"))
            lo_hi = runs.setdefault(key, [s, e])
            lo_hi[0], lo_hi[1] = min(lo_hi[0], s), max(lo_hi[1], e)
    if thunks:
        ops[0] = sorted(thunks, key=lambda o: o.start)
        modules[0] = sorted(((k[0], v[0], v[1]) for k, v in runs.items()),
                            key=lambda r: r[1])


# ---------------------------------------------------------------- summaries

def device_summary(trace: Trace) -> Optional[Dict[str, float]]:
    """busy_s averaged over the devices, the traced window's length, and
    the idle share of the mean and of the worst device."""
    win = trace.window
    if win is None:
        return None
    lo, hi = win
    busy = {d: total(trace.busy(d)) for d in trace.ops}
    window_s = (hi - lo) / 1e9
    mean_busy = sum(busy.values()) / len(busy) / 1e9
    return {"busy_s": mean_busy, "window_s": window_s,
            "idle_pct_mean": 100.0 * (1 - mean_busy / window_s),
            "idle_pct_worst": 100.0 * (1 - min(busy.values()) / 1e9 / window_s)}


def _module_of(ops: List[Op], modules) -> List[str]:
    """For each op (in time order) the short name of the module run it
    lies in ("jit_step"), or "" — one sweep over both."""
    out, j = [], 0
    runs = sorted(modules, key=lambda r: r[1])
    for o in ops:
        while j < len(runs) and runs[j][2] <= o.start:
            j += 1
        inside = j < len(runs) and runs[j][1] <= o.start
        out.append(runs[j][0].split("(")[0] if inside else "")
    return out


def breakdown(trace: Trace, top: int = 10) -> Optional[Dict[str, List]]:
    """The device ops that took most time, as `module/op [category]`
    (seconds a device, averaged over the devices), and the idle time by
    what the host was doing."""
    win = trace.window
    if win is None:
        return None
    n = len(trace.ops)
    by_op: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for d, ops in trace.ops.items():
        for o, mod in zip(ops, _module_of(ops, trace.modules.get(d, ()))):
            key = f"{mod}/{o.name}" if mod else o.name
            if o.category != "other":
                key += f" [{o.category}]"
            by_op[key] = by_op.get(key, 0.0) + (o.end - o.start)
        for name, ns in attribute(gaps(trace.busy(d), *win), trace.host).items():
            idle[name] = idle.get(name, 0.0) + ns

    def rank(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": rank(by_op), "idle_gaps": rank(idle)}
