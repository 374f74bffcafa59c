"""The process's compile log: every trace, lowering and backend compile
JAX makes, by function name, and whether the persistent compilation cache
served it.

Why it exists: set-up (`setup_s`, the time before the first useful step)
is mostly tracing, lowering, compiling or loading compiled programs, and a
recompile inside a long job's loop is a stall nobody sees coming. JAX
reports all of it through `jax.monitoring`; this module listens and keeps
it, so that a benchmark reader, a journal or an operator can say which
function cost what, and which step recompiled.

One record per `/jax/core/compile/jaxpr_trace_duration` (`kind="trace"`),
`.../jaxpr_to_mlir_module_duration` (`"lower"`) and
`.../backend_compile_duration` (`"compile"`). A `compile` record says
`cache`: `"hit"` (served from the persistent cache; `load_s` is JAX's
`/jax/compilation_cache/cache_retrieval_time_sec`), `"miss"` (the cache
was asked and the program compiled) or `"off"` (the cache was not asked:
disabled, or no key could be made). The cache's events carry no function
name; they fire on the compiling thread inside the compile's time span,
so they go to the compile that thread has open and its end takes them.

Records nest: every `jnp` wrapper is a jitted function, so tracing a step
traces hundreds of them inside it, a lowering rule may trace a function
of its own (`threefry`'s does), and an eager constant inside a traced
function compiles a small program. JAX also reports each start (a scalar
event), so a per-thread stack knows what is open: a trace that starts
while anything is open is not recorded (its time stays in the outer
record, whose name is the one a user knows), and any other record's whole
duration is taken off the record it ran inside. `seconds` is therefore a
record's own time — sums over any set of records count no second twice —
and `span_s` is the whole duration JAX reported.

`within` is the innermost span the calling thread had open on an enabled
`Tracer` (`obs/trace.py:innermost`) and `ids` that span's args: JAX
compiles on the dispatching thread, so a recompile in the zoo loop reads
`within="zoo.dispatch", ids={"step": 1234, "epoch": 7}`.

Clock: `start` is on `time.perf_counter`, the `Tracer`'s clock. JAX
reports `time.time()`; `install()` takes the offset between the two once
(to about a microsecond; a wall clock that is stepped afterwards moves
later starts by the step, never a duration or a `within`).

Module-level like the compile cache it describes (`install` / `records`
/ `summary` / `clear`), lock-guarded, bounded: totals by (`fun_name`,
`kind`, `cache`) for ever, the newest `KEEP` records in full. The
listeners run only when JAX traces, lowers or compiles: a loop that
compiles nothing calls none of them.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from parallel_cnn_tpu.obs import trace as trace_lib

KEEP = 4096

_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclasses.dataclass(frozen=True)
class Record:
    """One trace, lowering or compile. `start` in seconds on
    `time.perf_counter`; `seconds` its own time, `span_s` with what ran
    inside it; `cache` and `load_s` are None but on a `compile`;
    `within` / `ids` are None outside any span."""

    kind: str
    fun_name: str
    start: float
    seconds: float
    span_s: float
    cache: Optional[str] = None
    load_s: Optional[float] = None
    within: Optional[str] = None
    ids: Optional[Dict[str, Any]] = None
    thread: int = 0
    thread_name: str = ""


class _Open:
    """A trace, lowering or compile a thread has started and not ended."""

    __slots__ = ("kind", "inside", "cache", "load_s")

    def __init__(self, kind: str):
        self.kind = kind
        self.inside = 0.0  # seconds of the records kept apart that ran in it
        self.cache = None  # of a compile: the cache's word on it
        self.load_s = None


class _Log:
    """The store behind the module's functions."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: collections.deque = collections.deque(maxlen=KEEP)
        # (fun_name, kind, cache) -> [requests, seconds, load seconds]
        self._totals: Dict[Tuple[str, str, Optional[str]], List[float]] = {}
        self._journals: "weakref.WeakSet" = weakref.WeakSet()
        self._threads = threading.local()  # .open: this thread's `_Open`s
        self.installed = False
        self.offset = 0.0  # perf_counter - time.time(), taken by install()
        self.requests = 0  # compile records so far (one integer read)

    def _open(self) -> List[_Open]:
        """What the calling thread has open, innermost last."""
        stack = getattr(self._threads, "open", None)
        if stack is None:
            stack = self._threads.open = []
        return stack

    def _compiling(self) -> _Open:
        """The compile the calling thread has open: whose the cache's
        events are (they carry no name). One nobody reads where there is
        none (installed while it was open)."""
        stack = self._open()
        if stack and stack[-1].kind == "compile":
            return stack[-1]
        return _Open("compile")

    # -- the four listeners (jax.monitoring's signatures) -------------------

    def on_scalar(self, event: str, value: float, **kw: Any) -> None:
        kind = _KINDS.get(event)
        if kind is not None:  # JAX's word that one starts
            self._open().append(_Open(kind))

    def on_event(self, event: str, **kw: Any) -> None:
        if event == _CACHE_ASKED:
            self._compiling().cache = "miss"  # until the cache says otherwise
        elif event == _CACHE_HIT:
            self._compiling().cache = "hit"

    def on_duration(self, event: str, duration: float, **kw: Any) -> None:
        if event == _CACHE_LOAD:
            self._compiling().load_s = float(duration)

    def on_time_span(self, event: str, start_time: float, end_time: float,
                     **kw: Any) -> None:
        kind = _KINDS.get(event)
        if kind is None:
            return
        span_s = end_time - start_time
        stack = self._open()
        # (empty or another kind on top: installed while this one was open)
        mine = (stack.pop() if stack and stack[-1].kind == kind
                else _Open(kind))
        if kind == "trace" and stack:
            # a function traced while another is traced or lowered: its
            # time is the outer record's, which has what ran in this one
            # (and was kept apart) to take off
            stack[-1].inside += mine.inside
            return
        if stack:
            stack[-1].inside += span_s
        inside = mine.inside
        cache = load_s = None
        if kind == "compile":
            cache, load_s = mine.cache or "off", mine.load_s
        span = trace_lib.innermost()
        thread = threading.current_thread()
        rec = Record(
            kind, str(kw.get("fun_name", "")), start_time + self.offset,
            max(span_s - inside, 0.0), span_s, cache, load_s,
            span.name if span is not None else None,
            dict(span.args) if span is not None else None,
            thread.ident or 0, thread.name)
        with self._lock:
            self._records.append(rec)
            total = self._totals.setdefault(
                (rec.fun_name, kind, cache), [0, 0.0, 0.0])
            total[0] += 1
            total[1] += rec.seconds
            total[2] += load_s or 0.0
            if kind == "compile":
                self.requests += 1
            journals = list(self._journals) if kind == "compile" else ()
        if journals:
            # the span's ids first: the record's own fields win a clash
            fields = {k: v for k, v in (rec.ids or {}).items() if k != "kind"}
            fields.update(fun_name=rec.fun_name, seconds=rec.seconds,
                          cache=cache, load_s=load_s, within=rec.within)
            for journal in journals:
                try:
                    journal.emit("compile", **fields)
                except ValueError:  # closed without a detach: drop it
                    self.detach(journal)

    # -- what the module's functions call -----------------------------------

    def install(self) -> None:
        import jax.monitoring as mon

        with self._lock:
            if self.installed:
                return
            self.installed = True
            # Between two reads of the span clock, so the offset is off by
            # at most half of what the three reads took.
            p0 = time.perf_counter()
            wall = time.time()
            self.offset = 0.5 * (p0 + time.perf_counter()) - wall
        mon.register_scalar_listener(self.on_scalar)
        mon.register_event_listener(self.on_event)
        mon.register_event_duration_secs_listener(self.on_duration)
        mon.register_event_time_span_listener(self.on_time_span)

    def attach(self, journal) -> None:
        with self._lock:
            self._journals.add(journal)

    def detach(self, journal) -> None:
        with self._lock:
            self._journals.discard(journal)

    def records(self) -> List[Record]:
        with self._lock:
            return list(self._records)

    def totals(self) -> Dict[Tuple[str, str, Optional[str]], Tuple[int, float, float]]:
        with self._lock:
            return {k: tuple(v) for k, v in self._totals.items()}

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self._totals.clear()
            self.requests = 0


_LOG = _Log()


def install() -> None:
    """Register the listeners with `jax.monitoring`, once a process
    (idempotent). Called by `utils/backend.py:enable_compile_cache` — the
    first thing every entry point does — and by `obs.from_config` for an
    enabled bundle."""
    _LOG.install()


def installed() -> bool:
    return _LOG.installed


def attach(journal) -> None:
    """Every later `compile` record is also one `compile` event of this
    journal (held weakly) until `detach`."""
    _LOG.attach(journal)


def detach(journal) -> None:
    _LOG.detach(journal)


def records() -> List[Record]:
    """The newest `KEEP` records, oldest first."""
    return _LOG.records()


def requests() -> int:
    """Compile requests so far (`compile` records, hits included): what
    `zoo.train`'s epoch record differences."""
    return _LOG.requests


def totals() -> Dict[Tuple[str, str, Optional[str]], Tuple[int, float, float]]:
    """(`fun_name`, `kind`, `cache`) -> (requests, seconds, load seconds),
    since the process started or `clear()`."""
    return _LOG.totals()


def summary() -> Dict[str, Any]:
    """The totals in one flat record: `programs` (compile requests),
    `hits`, `misses`, seconds by kind, and `load_s` (the part of
    `compile_s` spent reading the persistent cache)."""
    out = {"programs": 0, "hits": 0, "misses": 0, "trace_s": 0.0,
           "lower_s": 0.0, "compile_s": 0.0, "load_s": 0.0}
    for (_, kind, cache), (n, seconds, load_s) in _LOG.totals().items():
        out[f"{kind}_s"] += seconds
        if kind == "compile":
            out["programs"] += n
            out["load_s"] += load_s
            if cache == "hit":
                out["hits"] += n
            elif cache == "miss":
                out["misses"] += n
    return out


def clear() -> None:
    """Forget every record and total (the listeners stay)."""
    _LOG.clear()


def trace_events(pid: int, since: float = 0.0) -> List[Dict[str, Any]]:
    """The kept records that started at or after `since` (seconds on the
    span clock) as Chrome-trace `X` events of `cat="compile"`, each
    thread's on a lane of its own (`tid` + 1, named `<thread> compiles`)
    beside that thread's spans: what `Obs.finish()` writes into
    `<run>_trace.json` after the tracer's own events."""
    out: List[Dict[str, Any]] = []
    named = set()
    for rec in records():
        if rec.start < since:
            continue
        lane = rec.thread + 1
        if lane not in named:
            named.add(lane)
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": lane,
                        "args": {"name": f"{rec.thread_name} compiles"}})
        args = {k: v for k, v in (("cache", rec.cache), ("load_s", rec.load_s),
                                  ("within", rec.within)) if v is not None}
        args.update(rec.ids or {})
        out.append({"ph": "X", "name": f"{rec.kind} {rec.fun_name}",
                    "cat": "compile", "pid": pid, "tid": lane,
                    "ts": rec.start * 1e6, "dur": rec.span_s * 1e6,
                    "args": args})
    return out
