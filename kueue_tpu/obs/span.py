"""Spans: the one timing mechanism of the engine, and the span model
the admission tracer serves.

**SpanRecorder** (one per engine, ``eng.spans``; always on) records a
real span tree per ``Engine.schedule_once()``, where the work happens:

    schedule_once                 controllers/engine.py — attrs seq, mode
    ├─ intake                     the engine's events since the last
    │  │                          cycle (absent where there were none):
    │  │                          from the first event to this root's
    │  │                          start; attr seq (this root's). Its
    │  │                          children are tallies, one a kind, in
    │  │                          the order first called: ts the first
    │  │                          call's start, dur the calls' sum,
    │  │                          attr calls (SpanRecorder.call)
    │  ├─ finish · submit         Engine.finish / submit (attrs
    │  │                          requeued, requeue_queues: a finish's
    │  │                          cohort requeue)
    │  └─ restore · tick          Engine.restore_workload / tick (attr
    │                             tick_scanned, and an eviction's
    │                             requeue counts)
    ├─ pre_hooks
    ├─ cycle                      oracle bridge: try_cycle; attrs, if
    │  │                          it launched, from the cycle program's
    │  │                          own output: preempt_slots (the slots
    │  │                          its preemptor was run for),
    │  │                          preempt_skipped (ordered candidates
    │  │                          the preemptor's scans passed over as
    │  │                          invalid), lattice (preempt_slots > 0:
    │  │                          the launch took the preemptor's branch);
    │  │                          and from the program's shapes:
    │  │                          preempt_columns (the width its
    │  │                          preemptor runs at, 0 where it has none:
    │  │                          batched.preempt_width)
    │  ├─ host_encode             _encode_cycle up to the device cycle
    │  │  └─ tas_place            (attrs heads, pending; and, where no
    │  │                          sim_nomination runs, mask_narrowed_heads)
    │  ├─ sim_nomination          multi-flavor groups on preempting CQs
    │  │  │                       only; host_encode runs on after it;
    │  │  │                       attrs heads, rows, launches, overflow,
    │  │  │                       mask_narrowed_heads (the cycle's heads
    │  │  │                       whose flavor mask excludes a flavor of
    │  │  │                       their queue's resource groups),
    │  │  │                       masked_flavor_cells (the cells of the
    │  │  │                       grid those masks left out of the walk)
    │  │  ├─ flavor_grid          ops/assign.flavor_grid + readback
    │  │  │                       (attr launched_s)
    │  │  ├─ sim_rows             one row a Preempt-gated cell
    │  │  ├─ sim_launch           the sim program, one block of rows
    │  │  │                       a launch (attrs rows, rows_padded,
    │  │  │                       launches, rows_classified; bytes
    │  │  │                       moved, upload_s, device_wait_s,
    │  │  │                       readback_s, launched_s)
    │  │  ├─ fungibility_fold     the flavor walk, array code
    │  │  └─ sim_targets          the cycle program's slot overrides
    │  ├─ upload                  host arrays -> device (attrs bytes)
    │  ├─ dispatch                cycle_step(...) returning futures
    │  ├─ device_wait             block_until_ready on the outputs (attr
    │  │                          launched_s: the launch's window)
    │  ├─ readback                np.asarray of the outputs (attrs bytes)
    │  ├─ verdict_decode          attrs lattice (the branch of the
    │  │                          launch that served it), device_heads,
    │  │                          victim_entries, reclaim_victims (the
    │  │                          committed victims of another
    │  │                          ClusterQueue than their preemptor's)
    │  ├─ apply · finalize
    │  └─ host_tail               hybrid cycles only
    ├─ snapshot · decide · apply  sequential path (no bridge; fallback)
    └─ gc_sweep · journal_sync · listeners

Every span carries name, start and duration on the recorder's clock
(``perf_counter`` unless the simulator injects its own through
``Engine.wall_clock``), its children, and counts taken at the same
boundary as attrs; the root's ``seq`` is the id all of a cycle's spans
share. Entering a span also enters ``jax.profiler.TraceAnnotation(
"kueue.<name>")`` (a flag check while no profiler session is open), so
a profiler capture holds the same tree on the device trace's clock with
no switch to flip; so does every call of an engine entry point
(``kueue.finish``, ``kueue.submit``, ``kueue.restore``, ``kueue.tick``),
inside a cycle and on another thread too, where it tallies nothing. A
device launch marks its window, from the dispatch of its first program
to its outputs being ready (with a remote oracle, the round trip to
it), as attr ``launched_s`` of the span open when it ends and as a
``kueue.launch`` annotation carrying the program's name
(SpanRecorder.launch). Each program JAX compiles, or reads from its
persistent cache, adds 1 to attr ``compiles`` of the span open in the
compiling thread. ``phase_seconds`` turns a tree
into ``Engine.last_cycle_phases``: seconds by leaf name, the counts its
attrs hold (COUNT_KEYS), and the keys of the window it closes
(WINDOW_KEYS).

**CycleTracer** (obs/tracer.py, attached on demand) serves per-cycle
trees with decisions and rationale:

    cycle/<seq>                      (kind="cycle")
    ├── phase/<name> ...             (kind="phase") the recorder's tree
    │                                for this schedule_once(), nested
    │                                as above, true ts / dur
    ├── workload/<key>               (kind="workload") — one per decided
    │     attrs: decision, flavors, reasons, preemption, rationale ...
    └── ...

Timestamps are microseconds since the recorder's epoch (``epoch`` is the
``(clock(), time.time_ns())`` pair taken together, so a tree can be laid
on any other clock), matching the Chrome/Perfetto trace-event ``ts``
unit so export is a straight mapping.

``correlation_id`` is the cross-artifact join key: derived purely from
(cycle seq, canonical decisions), so the tracer, the flight recorder and
the journal compute the SAME id independently — no plumbing between the
subsystems, and replaying a trace regenerates identical ids.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterator, Optional


@dataclass(slots=True)
class Span:
    """One node of a cycle's span tree. Slotted: a traced bench drain
    allocates one span per decided workload per cycle, and the
    per-instance ``__dict__`` was a measurable share of the tracer's
    wall-clock overhead."""

    name: str
    kind: str                      # "span" | "cycle" | "phase" | "workload"
    ts: float                      # µs since the recorder's epoch
    dur: float                     # µs
    attrs: dict = field(default_factory=dict)
    children: list = field(default_factory=list)

    def child(self, name: str, kind: str, ts: float, dur: float,
              **attrs) -> "Span":
        s = Span(name, kind, ts, dur, dict(attrs))
        self.children.append(s)
        return s

    def walk(self) -> Iterator["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()

    def find(self, pred: Callable[["Span"], bool]) -> Optional["Span"]:
        for s in self.walk():
            if pred(s):
                return s
        return None

    def to_dict(self) -> dict:
        """JSON shape served at /debug/trace."""
        return {"name": self.name, "kind": self.kind,
                "ts": round(self.ts, 1), "dur": round(self.dur, 1),
                "attrs": self.attrs,
                "children": [c.to_dict() for c in self.children]}


# The spans whose self time is ``unattributed``; everything directly
# under one of them is a leaf of the identity
#   sum(leaves) + unattributed == schedule_once.
CONTAINERS = frozenset({"schedule_once", "cycle", "sim_nomination"})

# Keys of Engine.last_cycle_phases that repeat time the leaf keys
# already hold: a nested span (tas_place, inside host_encode), a
# container's wall, and the LEGACY AGGREGATES, which keep the meaning
# the bridge's perf_counter marks gave them, mark to mark, for the
# readers that predate the tree (benchmark encode_ms / verdict_decode_ms
# / unused_speculation_ms, bench.py, profile_apply.py), to retire with
# them:
#   encode = the ``cycle`` span's first child's start (host_encode) to
#            verdict_decode's start (the time between the spans
#            included)
#   device = verdict_decode (the host's verdict scan, never the device;
#            the name is the legacy)
AGGREGATE_KEYS = frozenset({"tas_place", "schedule_once", "encode",
                            "device", "sim_nomination"})

# Keys of Engine.last_cycle_phases that are counts of this
# schedule_once(), not seconds, summed from span attrs recorded at the
# same boundary as the time, so that a reader holding a window's dicts
# has the window's own counts:
#   n_launches, n_lattice_launches  ``cycle`` spans that launched the
#       cycle program (attr ``lattice``), and those whose launch took
#       the fused preemptor's branch
#   n_preempt_slots, n_preempt_skipped  the same spans' attrs
#       ``preempt_slots`` and ``preempt_skipped``
#   n_reclaim_victims  verdict_decode's attr ``reclaim_victims``
#   n_device_cycles, n_device_heads verdict_decode spans, and the heads
#       the device decided in them (attr ``device_heads``)
#   n_commit_victim_entries  the slots the fused preemptor gave a victim
#       set (attr ``victim_entries``): at most that many steps of the
#       commit's loop take the branch that removes victims
#   n_sim_heads, n_sim_rows, n_sim_launches, n_sim_overflow
#       ``sim_nomination`` spans' attrs: the heads whose flavor choice
#       needed preemption simulations, the (head, flavor, resource)
#       cells simulated, the sim program's launches, and the heads the
#       sim program handed to the host (more candidates than it scans)
#   n_sim_rows_classified  the ``sim_launch`` span's attr
#       ``rows_classified``: the rows the sim program's launches
#       classified, each launch its live rows in whole chunks
#   n_mask_narrowed_heads  attr ``mask_narrowed_heads`` of the
#       ``sim_nomination`` span, or of ``host_encode`` where no nomination
#       runs: the cycle's heads whose flavor mask (labels, taints,
#       tolerations: rowcache.flavor_ok) excludes a flavor of their
#       ClusterQueue's resource groups
#   n_masked_flavor_cells  ``sim_nomination``'s attr
#       ``masked_flavor_cells``: the (head, flavor, resource) cells of
#       ops/assign.flavor_grid those masks left out of the walk
COUNT_KEYS = frozenset({"n_launches", "n_lattice_launches",
                        "n_preempt_slots", "n_preempt_skipped",
                        "n_device_cycles", "n_device_heads",
                        "n_commit_victim_entries", "n_reclaim_victims",
                        "n_sim_heads", "n_sim_rows", "n_sim_launches",
                        "n_sim_overflow", "n_sim_rows_classified",
                        "n_mask_narrowed_heads", "n_masked_flavor_cells"})

# The engine's entry points the ``intake`` tree tallies, by kind.
TALLY_KINDS = ("finish", "submit", "restore", "tick")

# Keys of Engine.last_cycle_phases of the window a cycle closes: the
# engine's events since the cycle before, and the device launches of
# the cycle. Every one, in every deciding cycle, and none a leaf:
#   intake            seconds, the ``intake`` tree's tallies summed
#   intake_<kind>     seconds, each kind's tally (TALLY_KINDS)
#   device_launched   seconds, the launch windows (attr ``launched_s``)
#   host_bound        intake + schedule_once - device_launched: the
#                     engine's seconds with no device program of its own
#                     running (close_phases)
#   n_intake_calls    the tallies' ``calls``
# and counts summed over the ``intake`` tree and the cycle's own:
#   n_requeued        attr ``requeued``: workloads moved from parked back
#                     into their queue by a cohort's requeue
#   n_requeue_queues  attr ``requeue_queues``: the ClusterQueues those
#                     requeues visited
#   n_tick_scanned    attr ``tick_scanned``: running workloads tick()
#                     looked at
#   n_compiles        attr ``compiles``: programs JAX compiled or read
#                     back from its persistent cache
# These four counts are also the running totals of the metric family
# scheduler_work_total, by kind (WORK_KINDS: the key less its ``n_``),
# idle cycles' windows included.
WINDOW_KEYS = frozenset(
    {"intake", "device_launched", "host_bound", "n_intake_calls",
     "n_requeued", "n_requeue_queues", "n_tick_scanned", "n_compiles"}
    | {"intake_" + kind for kind in TALLY_KINDS})

WORK_KINDS = ("requeued", "requeue_queues", "tick_scanned", "compiles")

# Span attrs summed over a whole tree into WINDOW_KEYS.
_WINDOW_ATTRS = (("launched_s", "device_launched"),
                 ("requeued", "n_requeued"),
                 ("requeue_queues", "n_requeue_queues"),
                 ("tick_scanned", "n_tick_scanned"),
                 ("compiles", "n_compiles"))

# The recorder whose root or tallied call last opened on this thread,
# for the one jax.monitoring listener of the process: JAX reports a
# compile on the thread that compiles.
_recording = threading.local()
_compile_listener_registered = False


def _on_compile(event: str, secs: float, **_) -> None:
    """What JAX compiles, or reads back from its persistent cache: attr
    ``compiles`` of the span open in the compiling thread."""
    if event == "/jax/core/compile/backend_compile_duration":
        rec = getattr(_recording, "rec", None)
        if rec is not None:
            rec.add(compiles=1)


def _listen_for_compiles() -> None:
    global _compile_listener_registered
    if not _compile_listener_registered:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_compile)
        _compile_listener_registered = True


class SpanRecorder:
    """Real spans, always on: one tree per schedule_once(), the last
    ``retain`` kept. Code that runs in sequence uses ``begin`` /
    ``next`` / ``end``; ``with rec.span(name) as s`` closes whatever
    was left open beneath it when the block unwinds, so an exception
    cannot leave the stack out of step. Decision code calls this and
    never reads it (graftlint D1/O1).

    Between two trees, ``with rec.call(kind)`` tallies the engine's
    entry points on an ``intake`` tree, which the next root takes as
    its first child the instant it opens; ``with rec.launch(program)``
    marks a device launch's window.

    The recorder belongs to the thread that opened its newest root (the
    one that built it, before any): a call from another thread, such as
    a submit from an HTTP handler while the loop runs a cycle, is only
    annotated, and ``add`` from it is dropped, so that no thread pushes
    onto or pops from a stack another is recording on.

    What a cycle allocates is part of the cost (the engine sweeps the
    young generation every cycle, ``apply_serving_gc_posture``): a
    Span, its attrs and child list, and the profiler annotation, per
    span; the context manager is the recorder itself. A call allocates
    its context manager and annotation, and a span only for the first
    call of a kind between two cycles. Spans hold no parent pointer, so
    a tree dropped from the ring is freed by reference count alone."""

    __slots__ = ("clock", "epoch", "trees", "_open", "_scopes", "_marks",
                 "_annotation", "_intake", "_tallies", "_owner")

    def __init__(self, retain: int = 8,
                 clock: Callable[[], float] = time.perf_counter):
        self.trees: deque = deque(maxlen=retain)  # finished roots
        self._open: list = []     # the open spans, root first
        self._scopes: list = []   # their profiler annotations
        self._marks: list = []    # stack depth at each ``with``
        # jax.profiler.TraceAnnotation, from the first root or call that
        # finds jax imported (this module never imports it: no session
        # can be open in a process that has not).
        self._annotation = None
        self._intake: Optional[Span] = None  # open between two roots
        self._tallies: dict = {}             # its children, by kind
        self._owner = threading.get_ident()  # the thread recording
        self.set_clock(clock)

    def set_clock(self, clock: Callable[[], float]) -> None:
        """Time spans on ``clock`` from here on, with a fresh epoch:
        ``(clock(), time.time_ns())`` read together. An open ``intake``
        tree, timed on the clock before, is dropped."""
        self.clock = clock
        self.epoch = (clock(), time.time_ns())
        self._intake = None
        self._tallies = {}

    # -- recording --

    def begin(self, name: str, **attrs) -> Span:
        return self._push(name, attrs, self.clock())

    def end(self, **attrs) -> Span:
        return self._pop(self.clock(), attrs)

    def next(self, name: str, **attrs) -> Span:
        """End the open span and begin ``name`` at the same instant."""
        t = self.clock()
        self._pop(t, None)
        return self._push(name, attrs, t)

    def span(self, name: str, **attrs) -> "SpanRecorder":
        self._push(name, attrs, self.clock())
        return self

    def call(self, kind: str) -> "_Call":
        """``with rec.call(kind)``: one call of an engine entry point
        (TALLY_KINDS), annotated ``kueue.<kind>``. Where no span is open
        it is timed on the ``intake`` tree's tally of its kind, open
        while it runs; inside a span (a cycle, another call) its time is
        that span's, and from a thread that does not own the recorder it
        is not timed: neither tallies anything."""
        return _Call(self, kind)

    def launch(self, program: str) -> "_Launch":
        """``with rec.launch(program)`` around a device launch, from the
        dispatch of its first program to its outputs being ready: the
        seconds go to attr ``launched_s`` of the span open when it ends,
        and the window is a ``kueue.launch`` annotation carrying
        ``program``."""
        return _Launch(self, program)

    def add(self, **amounts) -> None:
        """Add ``amounts`` to the innermost open span's attrs of the
        same names (code that runs inside a leaf and has bytes or
        seconds to report, without a span of its own); with no span
        open, or from a thread that does not own the recorder, they are
        dropped."""
        if not self._open or threading.get_ident() != self._owner:
            return
        attrs = self._open[-1].attrs
        for key, more in amounts.items():
            attrs[key] = attrs.get(key, 0) + more

    def __enter__(self) -> Span:
        self._marks.append(len(self._open))
        return self._open[-1]

    def __exit__(self, *exc) -> bool:
        depth = self._marks.pop() - 1
        while len(self._open) > depth:
            self._pop(self.clock(), None)
        return False

    def _resolve_annotation(self):
        if "jax" in sys.modules:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
            _listen_for_compiles()
        return self._annotation

    def _push(self, name: str, attrs: dict, t: float) -> Span:
        stack = self._open
        annotation = self._annotation
        if not stack:
            if annotation is None:
                annotation = self._resolve_annotation()
            self._owner = threading.get_ident()
            _recording.rec = self
        scope = None
        if annotation is not None:
            scope = annotation("kueue." + name)
            scope.__enter__()
        self._scopes.append(scope)
        ts = (t - self.epoch[0]) * 1e6
        s = Span(name, "span", ts, 0.0, attrs, [])
        if stack:
            stack[-1].children.append(s)
        elif self._intake is not None:
            intake = self._intake
            intake.dur = ts - intake.ts
            if "seq" in attrs:
                intake.attrs["seq"] = attrs["seq"]
            s.children.append(intake)
            self._intake = None
            self._tallies = {}
        stack.append(s)
        return s

    def _pop(self, t: float, attrs: Optional[dict]) -> Span:
        s = self._open.pop()
        s.dur = (t - self.epoch[0]) * 1e6 - s.ts
        if attrs:
            s.attrs.update(attrs)
        scope = self._scopes.pop()
        if scope is not None:
            scope.__exit__(None, None, None)
        if not self._open:
            self.trees.append(s)
        return s

    def _tally(self, kind: str, t: float) -> Span:
        """The open ``intake`` tree's tally of ``kind``, opened (and the
        tree with it) at ``t`` by the first such call."""
        tally = self._tallies.get(kind)
        if tally is None:
            ts = (t - self.epoch[0]) * 1e6
            if self._intake is None:
                self._intake = Span("intake", "span", ts, 0.0, {}, [])
            tally = self._tallies[kind] = self._intake.child(
                kind, "span", ts, 0.0, calls=0)
        return tally

    # -- reading (obs zone, tests, operator surfaces) --

    def open_root(self) -> Optional[Span]:
        """The tree being recorded, for a reader that runs inside it
        (a cycle listener); its open spans still have ``dur`` 0."""
        return self._open[0] if self._open else None

    def is_open(self, s: Span) -> bool:
        return any(o is s for o in self._open)

    def last(self) -> Optional[Span]:
        return self.trees[-1] if self.trees else None


class _Call:
    """SpanRecorder.call's context manager."""

    __slots__ = ("rec", "kind", "scope", "tally", "t0")

    def __init__(self, rec: SpanRecorder, kind: str):
        self.rec = rec
        self.kind = kind

    def __enter__(self) -> None:
        rec = self.rec
        annotation = rec._annotation or rec._resolve_annotation()
        self.scope = scope = (annotation("kueue." + self.kind)
                              if annotation is not None else None)
        if scope is not None:
            scope.__enter__()
        if threading.get_ident() != rec._owner or rec._open:
            self.tally = None
            return
        self.t0 = t = rec.clock()
        self.tally = tally = rec._tally(self.kind, t)
        rec._open.append(tally)  # what the call adds lands on it
        rec._scopes.append(None)
        _recording.rec = rec

    def __exit__(self, *exc) -> bool:
        rec, tally = self.rec, self.tally
        if tally is not None:
            t = rec.clock()
            while rec._open[-1] is not tally:  # left open by the call
                rec._pop(t, None)
            rec._open.pop()
            rec._scopes.pop()
            tally.dur += (t - self.t0) * 1e6
            tally.attrs["calls"] += 1
        if self.scope is not None:
            self.scope.__exit__(None, None, None)
        return False


class _Launch:
    """SpanRecorder.launch's context manager."""

    __slots__ = ("rec", "program", "scope", "t0")

    def __init__(self, rec: SpanRecorder, program: str):
        self.rec = rec
        self.program = program

    def __enter__(self) -> None:
        rec = self.rec
        annotation = rec._annotation
        self.scope = scope = (annotation("kueue.launch", program=self.program)
                              if annotation is not None else None)
        if scope is not None:
            scope.__enter__()
        self.t0 = rec.clock()

    def __exit__(self, *exc) -> bool:
        rec = self.rec
        t = rec.clock()
        if self.scope is not None:
            self.scope.__exit__(None, None, None)
        rec.add(launched_s=t - self.t0)
        return False


def phase_seconds(root: Span) -> dict:
    """A schedule_once() tree as ``Engine.last_cycle_phases``: seconds,
    one key per leaf name, ``tas_place`` (nested in host_encode),
    ``sim_nomination`` (that subtree's wall) and the legacy aggregates;
    the counts of COUNT_KEYS; and WINDOW_KEYS, but ``host_bound``, which
    ``close_phases`` adds with what else only the closed root knows."""
    out: dict = {}
    launches = lattice = slots = skipped = 0
    boxes = [root]
    while boxes:
        box = boxes.pop()
        if "lattice" in box.attrs:  # this container launched
            launches += 1
            lattice += box.attrs["lattice"]
            slots += box.attrs.get("preempt_slots", 0)
            skipped += box.attrs.get("preempt_skipped", 0)
        for c in box.children:
            if c.name == "intake":  # before the root: no leaf of it
                continue
            if c.name in CONTAINERS:
                boxes.append(c)
                if c.name == "cycle":
                    _cycle_aggregates(c, out)
                elif c.name == "sim_nomination":
                    _add(out, "sim_nomination", c.dur * 1e-6)
                    for attr in ("heads", "rows", "launches", "overflow"):
                        _add(out, "n_sim_" + attr, c.attrs.get(attr, 0))
                    _mask_counts(c, out)
                continue
            _add(out, c.name, c.dur * 1e-6)
            if c.name == "sim_launch":
                _add(out, "n_sim_rows_classified",
                     c.attrs.get("rows_classified", 0))
            _mask_counts(c, out)  # host_encode, where no nomination ran
            for s in c.children:  # tas_place, in host_encode
                if s.name in AGGREGATE_KEYS:
                    _add(out, s.name, s.dur * 1e-6)
    if launches:
        out["n_launches"] = launches
        out["n_lattice_launches"] = lattice
        out["n_preempt_slots"] = slots
        out["n_preempt_skipped"] = skipped
    out.update(window_keys(root))
    return out


def window_keys(root: Span) -> dict:
    """WINDOW_KEYS of a schedule_once() tree, but ``host_bound``."""
    out: dict = {}
    intake = root.children[0] if (
        root.children and root.children[0].name == "intake") else None
    total, calls = 0.0, 0
    for kind in TALLY_KINDS:
        out["intake_" + kind] = 0.0
    for tally in intake.children if intake is not None else ():
        out["intake_" + tally.name] = secs = tally.dur * 1e-6
        total += secs
        calls += tally.attrs["calls"]
    out["intake"] = total
    out["n_intake_calls"] = calls
    sums = dict.fromkeys((key for _, key in _WINDOW_ATTRS), 0)
    for s in root.walk():
        if s.attrs:
            for attr, key in _WINDOW_ATTRS:
                if attr in s.attrs:
                    sums[key] += s.attrs[attr]
    out.update(sums)
    return out


def _add(out: dict, key: str, value) -> None:
    out[key] = out.get(key, 0) + value


def _mask_counts(span: Span, out: dict) -> None:
    for attr in ("mask_narrowed_heads", "masked_flavor_cells"):
        if attr in span.attrs:
            _add(out, "n_" + attr, span.attrs[attr])


def _cycle_aggregates(cycle: Span, out: dict) -> None:
    """What is read off the ``cycle`` subtree alone: the legacy
    aggregates, mark to mark, and the counts its spans carry."""
    for c in cycle.children:
        if c.name == "verdict_decode":
            # (Else the bridge declined the cycle before a verdict.)
            out["encode"] = (c.ts - cycle.children[0].ts) * 1e-6
            out["device"] = c.dur * 1e-6
            _add(out, "n_device_cycles", 1)
            if "device_heads" in c.attrs:
                _add(out, "n_device_heads", c.attrs["device_heads"])
            if "victim_entries" in c.attrs:
                _add(out, "n_commit_victim_entries",
                     c.attrs["victim_entries"])
            if "reclaim_victims" in c.attrs:
                _add(out, "n_reclaim_victims",
                     c.attrs["reclaim_victims"])


def close_phases(phases: dict, root: Span) -> None:
    """Complete ``phase_seconds(root)``'s dict, in place, once the root
    has closed: ``listeners`` (open while the listeners read the dict),
    ``schedule_once`` = the root's wall, and ``unattributed`` = the
    containers' self time, so that leaves + unattributed ==
    schedule_once by construction; and ``host_bound``."""
    for c in root.children:
        if c.name == "listeners":
            phases["listeners"] = c.dur * 1e-6
    total = root.dur * 1e-6
    phases["unattributed"] = total - sum(leaf_phases(phases).values())
    phases["schedule_once"] = total
    phases["host_bound"] = (phases["intake"] + total
                            - phases["device_launched"])


def leaf_phases(phases: dict) -> dict:
    """``last_cycle_phases`` without the keys that repeat time, without
    the counts and without the window's keys: the seconds a reader may
    add up or lay end to end."""
    return {k: v for k, v in phases.items()
            if k not in AGGREGATE_KEYS and k not in COUNT_KEYS
            and k not in WINDOW_KEYS}


def correlation_id(seq: int, decisions: list) -> str:
    """Deterministic cross-artifact id for one cycle: ``<seq>-<crc32 of
    the canonical decision record>``. Every subsystem that holds (seq,
    decisions) — tracer, flight recorder, journal, replayer — derives
    the same id with no coordination."""
    from kueue_tpu.replay.trace import decision_digest

    return f"{seq:06d}-{decision_digest(decisions):08x}"
