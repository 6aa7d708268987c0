"""Live spans and counters of the host code on the chip path.

Every other view of ``repro.obs`` is rebuilt after the fact from the
simulated runtime's event log.  This one records the real host work of the
estimate front and the planner as it runs:

* ``span(name, **counts)`` is a context manager.  It opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so the span lands in
  any profiler trace on the device trace's clock, and it keeps a record:
  name, start and end on ``time.perf_counter_ns``, its own id, its parent's
  and its root's (every span of one request shares the root's id), the
  time its children took, and a dict of counts.
* ``count(**counts)`` adds to the innermost open span of the calling
  thread.  Each thread keeps its own stack of open spans.
* Closed records go into a ring of ``CAPACITY`` records.  ``records(since,
  until)`` hands back those that start and end inside a window, and raises
  where the ring has already dropped a record of it: a reader never
  undercounts.
* The first span of the process registers its one ``jax.monitoring``
  listener.  Each backend compilation is then counted into the innermost
  open span of the thread that compiled (``compiles``, ``compile_ms``) and
  logged with its time, by every recorder still alive;
  ``compiles(since, until)`` reads that log, ``"none"`` naming a
  compilation outside any span.
* ``forest()`` gives the ring as ``repro.obs.spans.Span`` trees, which
  ``repro.obs.export.to_chrome_trace(spans={"host": forest()})`` writes.

There is no switch: with the profiler off a span costs two clock reads, an
annotation that records nothing, and an append.  ``TRACER`` is the process's
recorder, which the program's spans use; a test may build its own.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
import weakref

from repro.obs.spans import Span

__all__ = ["CAPACITY", "COMPILE_EVENT", "Record", "Compile", "Tracer",
           "TRACER", "span", "count", "records", "compiles", "forest"]

# ample for set-up and a minute of jobs at the benchmark's sizes: a
# lineitem job over 12 blocks records 51 spans, a text job 8.  A full ring
# holds ~27 MiB.
CAPACITY = 1 << 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
NO_SPAN = "none"


class Record:
    """One span: open while on its thread's stack, then kept in the ring.
    Times are ``time.perf_counter_ns``; a kept record is not changed again.
    """

    __slots__ = ("name", "counts", "id", "parent", "root", "start_ns",
                 "end_ns", "child_ns", "seq", "_tracer", "_stack",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, counts: dict):
        self._tracer, self.name, self.counts = tracer, name, counts

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns

    @property
    def self_ns(self) -> int:
        """The span's duration less its children's, which nest in it."""
        return self.end_ns - self.start_ns - self.child_ns

    def __repr__(self) -> str:
        return (f"Record({self.name!r}, id={self.id}, parent={self.parent}, "
                f"root={self.root}, dur_ns={self.dur_ns}, "
                f"counts={self.counts})")

    def __enter__(self) -> "Record":
        tracer = self._tracer
        try:
            stack = tracer._local.stack
        except AttributeError:
            stack = tracer._stack()
        if stack:
            top = stack[-1]
            self.parent, self.root = top.id, top.root
            self.id = next(tracer._ids)
        else:
            if tracer._annotation is None:
                tracer._start()
            self.parent = None
            self.id = self.root = next(tracer._ids)
        self.child_ns = 0
        self._stack = stack
        self._annotation = annotation = tracer._annotation(self.name)
        annotation.__enter__()
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = end = time.perf_counter_ns()
        stack = self._stack
        stack.pop()
        if stack:
            stack[-1].child_ns += end - self.start_ns
        self._annotation.__exit__(*exc)
        tracer = self._tracer
        # a kept record holds no reference back: a recorder is freed with
        # its last user, and leaves the compile listener's set
        self._stack = self._annotation = self._tracer = None
        # lock-free: a deque's append is atomic, and the close sequence
        # number tells afterwards how many records the ring dropped
        self.seq = next(tracer._closes)
        tracer._ring.append(self)


@dataclasses.dataclass(frozen=True, slots=True)
class Compile:
    """One backend compilation: when it ended, how long it took, and the
    innermost span open on its thread (``"none"`` outside any)."""

    t_ns: int
    secs: float
    span: str
    span_id: int | None
    seq: int               # order of logging, as a span's ``seq``


class Tracer:
    """A ring of closed spans and a log of compilations."""

    def __init__(self):
        self._ring: collections.deque = collections.deque(maxlen=CAPACITY)
        self._compiles: collections.deque = collections.deque(maxlen=CAPACITY)
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._closes = itertools.count()
        self._compile_seq = itertools.count()
        self._annotation = None

    def _start(self) -> None:
        """On the first span: the profiler's annotation, and a place among
        the recorders the compile listener feeds (JAX is imported here, not
        before)."""
        import jax

        global _listening
        with _listen_lock:
            if not _listening:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_event)
                _listening = True
            _live.add(self)
            self._annotation = jax.profiler.TraceAnnotation

    def _stack(self) -> list:
        """This thread's stack of open spans."""
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    @property
    def dropped(self) -> int:
        """Records the ring has dropped, oldest first."""
        return _dropped(list(self._ring))

    def _on_compile(self, secs: float) -> None:
        stack = self._stack()
        top = stack[-1] if stack else None
        if top is not None:
            c = top.counts
            c["compiles"] = c.get("compiles", 0) + 1
            c["compile_ms"] = c.get("compile_ms", 0.0) + secs * 1e3
        self._compiles.append(Compile(
            time.perf_counter_ns(), float(secs), top.name if top else NO_SPAN,
            top.id if top else None, next(self._compile_seq)))

    def span(self, name: str, **counts) -> Record:
        return Record(self, name, counts)

    def count(self, **counts) -> None:
        """Add to the innermost open span of this thread (none: no-op)."""
        stack = self._stack()
        if not stack:
            return
        c = stack[-1].counts
        for k, v in counts.items():
            c[k] = c.get(k, 0) + v

    def records(self, since_ns: int | None = None,
                until_ns: int | None = None) -> list:
        """Closed spans that start at or after ``since_ns`` and end at or
        before ``until_ns``, in order of closing.  ``since_ns`` None reads
        what the ring holds.  Where the ring has dropped records, a window
        that reaches back to its oldest record may have lost some, and
        raises ``LookupError``."""
        return _window(self._ring, since_ns, until_ns, "start_ns", "end_ns")

    def compiles(self, since_ns: int | None = None,
                 until_ns: int | None = None) -> list:
        """Compilations that ended inside the window, in order; raises as
        ``records`` does."""
        return _window(self._compiles, since_ns, until_ns, "t_ns", "t_ns")

    def forest(self, since_ns: int | None = None,
               until_ns: int | None = None) -> list:
        """The window's spans as ``Span`` trees, roots in order of start.

        ``cat`` is the span's layer, its name before the first dot; ``meta``
        holds its counts and ids; times are seconds.  A span whose parent
        left the ring, or lies outside the window, is a root."""
        recs = self.records(since_ns, until_ns)
        ids = {r.id for r in recs}
        kids: dict = {}
        for r in recs:
            parent = r.parent if r.parent in ids else None
            kids.setdefault(parent, []).append(r)

        def build(r: Record) -> Span:
            meta = dict(r.counts, id=r.id, parent=r.parent, root=r.root)
            children = sorted(kids.get(r.id, ()), key=lambda c: c.start_ns)
            return Span(r.name, r.name.split(".")[0], "host",
                        r.start_ns * 1e-9, r.end_ns * 1e-9,
                        tuple(sorted(meta.items())),
                        tuple(build(c) for c in children))

        return [build(r) for r in sorted(kids.get(None, ()),
                                         key=lambda c: c.start_ns)]


# the process's one compile listener feeds every recorder that has opened a
# span and is still alive
_live: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_listening = False
_listen_lock = threading.Lock()


def _on_event(event: str, secs: float, **_) -> None:
    if event == COMPILE_EVENT:
        for t in list(_live):
            t._on_compile(secs)


def _dropped(entries: list) -> int:
    """How many entries a ring dropped: its newest ``seq`` + 1 less its
    length (appends race, so the newest is not always the last)."""
    return max(e.seq for e in entries) + 1 - len(entries) if entries else 0


def _window(ring, since_ns, until_ns, start: str, end: str) -> list:
    """The entries of ``ring`` whose ``start`` and ``end`` fall inside
    [since_ns, until_ns].  Entries leave the ring in the order they entered
    it, so one dropped entry ended no later than the oldest one kept: a
    window that begins after that one's end has lost nothing."""
    entries = list(ring)
    if since_ns is not None and entries and _dropped(entries) \
            and since_ns <= getattr(entries[0], end):
        raise LookupError(
            f"the window from {since_ns} ns reaches back past the "
            f"{_dropped(entries)} entries the ring has dropped")
    return [e for e in entries
            if (since_ns is None or getattr(e, start) >= since_ns)
            and (until_ns is None or getattr(e, end) <= until_ns)]


TRACER = Tracer()
span = TRACER.span
count = TRACER.count
records = TRACER.records
compiles = TRACER.compiles
forest = TRACER.forest
