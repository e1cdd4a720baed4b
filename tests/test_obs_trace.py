"""Admission tracing (obs/): span trees, structured rationale,
correlation ids, Perfetto export, explain, and the digest-neutrality
contract (a traced run decides byte-identically to an untraced run)."""

import json
import os
import re
import sys

import pytest

from kueue_tpu.api.types import (
    ClusterQueue,
    ClusterQueuePreemption,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.controllers.engine import Engine
from kueue_tpu.obs import explain_workload, render_explain
from kueue_tpu.obs.span import correlation_id

CPU = "cpu"
CID_RE = re.compile(r"^\d{6}-[0-9a-f]{8}$")


def make_engine(nominal=1000, preemption=False):
    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    eng.create_cluster_queue(ClusterQueue(
        name="cq",
        preemption=(ClusterQueuePreemption(
            within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY)
            if preemption else ClusterQueuePreemption()),
        resource_groups=(ResourceGroup(
            (CPU,),
            (FlavorQuotas("default", {CPU: ResourceQuota(nominal)}),)),),
    ))
    eng.create_local_queue(LocalQueue("lq", "default", "cq"))
    return eng


def submit(eng, name, cpu, priority=0):
    eng.clock += 0.5
    wl = Workload(name=name, queue_name="lq", priority=priority,
                  pod_sets=(PodSet("main", 1, {CPU: cpu}),))
    eng.submit(wl)
    return wl


def drain(eng, limit=50):
    for _ in range(limit):
        if eng.schedule_once() is None:
            break


class TestSpanTrees:
    def test_cycle_span_tree_shape(self):
        eng = make_engine()
        tracer = eng.attach_tracer()
        submit(eng, "ok", 600)
        submit(eng, "big", 5000)  # exceeds quota: inadmissible
        drain(eng)
        assert tracer.cycles_traced >= 1
        root = tracer.spans[0]
        assert root.kind == "cycle"
        assert root.attrs["mode"] == "sequential"
        assert CID_RE.match(root.attrs["cid"])
        assert root.dur >= 0
        phases = [s for s in root.children if s.kind == "phase"]
        # The engine's events before the cycle (the two submits): first,
        # ending where the cycle begins.
        intake = phases.pop(0)
        assert intake.name == "phase/intake"
        assert intake.ts + intake.dur == pytest.approx(root.ts)
        assert [c.name for c in intake.children] == ["phase/submit"]
        assert {s.name for s in phases} >= {
            "phase/pre_hooks", "phase/snapshot", "phase/decide",
            "phase/apply"}
        # The engine's own spans (obs.span.SpanRecorder), as timed:
        # inside the cycle span, in order, none overlapping the next.
        for a, b in zip(phases, phases[1:]):
            assert root.ts <= a.ts and a.ts + a.dur <= b.ts + 1e-6
        assert phases[-1].ts + phases[-1].dur <= root.ts + root.dur + 1e-6

    def test_admitted_span_carries_flavors(self):
        eng = make_engine()
        tracer = eng.attach_tracer()
        submit(eng, "ok", 600)
        drain(eng)
        _, span = tracer.find_workload("default/ok")
        assert span is not None
        assert span.attrs["decision"] == "admitted"
        assert span.attrs["cluster_queue"] == "cq"
        assert span.attrs["flavors"] == {"main": {CPU: "default"}}

    def test_rejected_span_carries_reasons(self):
        eng = make_engine()
        tracer = eng.attach_tracer()
        submit(eng, "big", 5000)
        drain(eng)
        _, span = tracer.find_workload("default/big")
        assert span is not None
        assert span.attrs["decision"] != "admitted"
        # Either structured per-podset reasons or the assignment
        # message must explain the rejection.
        assert span.attrs.get("reasons") or span.attrs.get("message")
        # The flavor-search rationale names the flavor that was tried.
        searches = [r for r in span.attrs.get("rationale", ())
                    if r["kind"] == "flavor_search"]
        assert searches and "default" in searches[0]["tried"]

    def test_preemption_rationale(self):
        eng = make_engine(preemption=True)
        tracer = eng.attach_tracer()
        submit(eng, "low", 800, priority=0)
        drain(eng)
        submit(eng, "high", 800, priority=10)
        eng.schedule_once()  # the preempting cycle, before requeues win
        _, span = tracer.find_workload("default/high")
        assert span is not None
        assert span.attrs["decision"] == "preempting"
        chosen = span.attrs["preemption_chosen"]
        assert any(t[0] == "default/low" for t in chosen)
        pre = [r for r in span.attrs["rationale"]
               if r["kind"] == "preemption"]
        assert pre and "default/low" in pre[0]["considered"]
        assert pre[0]["strategy"] in ("classical", "fair")

    def test_trace_metrics_and_sse_summary(self):
        eng = make_engine()
        eng.attach_tracer()
        events = []
        eng.event_listeners.append(events.append)
        submit(eng, "ok", 600)
        drain(eng)
        assert eng.registry.counter("trace_cycles_total").get(
            ("sequential",)) >= 1
        assert eng.registry.counter(
            "trace_workload_decisions_total").get(("admitted",)) >= 1
        summaries = [e for e in events if e.kind == "cycle_trace"]
        assert summaries and "cid=" in summaries[0].detail

    def test_retention_ring_bounded(self):
        eng = make_engine(nominal=100_000)
        tracer = eng.attach_tracer(retain=3)
        for i in range(8):
            submit(eng, f"w{i}", 100)
            eng.schedule_once()
        assert len(tracer.spans) == 3
        assert tracer.cycles_traced == 8

    def test_attach_is_idempotent_and_detach_clean(self):
        eng = make_engine()
        tracer = eng.attach_tracer()
        assert eng.attach_tracer() is tracer
        n_pre = len(eng.pre_cycle_hooks)
        tracer.detach()
        assert eng.tracer is None
        assert len(eng.pre_cycle_hooks) == n_pre - 1
        submit(eng, "ok", 600)
        drain(eng)  # no tracer: cycles run clean
        assert not tracer.spans


class TestCorrelation:
    def test_cid_joins_flight_trace_and_journal(self, tmp_path):
        from kueue_tpu.replay.recorder import FlightRecorder
        from kueue_tpu.replay.trace import TraceReader
        from kueue_tpu.store.journal import (
            attach_new_journal,
            rebuild_engine,
        )

        eng = make_engine()
        journal_path = str(tmp_path / "j.jsonl")
        attach_new_journal(eng, journal_path)
        eng.attach_tracer()
        trace_path = str(tmp_path / "t.jsonl")
        rec = FlightRecorder(eng, trace_path, bootstrap=True)
        submit(eng, "ok", 600)
        drain(eng)
        rec.close()

        frames = [f for f in TraceReader(trace_path)
                  if f.get("f") == "cycle"]
        assert frames
        for f in frames:
            assert f["cid"] == correlation_id(f["seq"], f["decisions"])
        cids = {f["cid"] for f in frames}
        journaled = set()
        with open(journal_path, encoding="utf-8") as fh:
            for line in fh:
                rec_obj = json.loads(line)
                if rec_obj.get("kind") == "cycle_trace":
                    journaled.add(rec_obj["obj"]["name"])
        assert cids <= journaled
        # The unknown journal kind must not break cold restarts.
        reb = rebuild_engine(journal_path)
        assert reb.workloads["default/ok"].is_admitted

    def test_traced_run_digest_identical_to_untraced(self, tmp_path):
        from kueue_tpu.replay.recorder import FlightRecorder

        def run(path, traced):
            eng = Engine()
            rec = FlightRecorder(eng, path)
            if traced:
                eng.attach_tracer()
            eng.create_resource_flavor(ResourceFlavor("default"))
            eng.create_cluster_queue(ClusterQueue(
                name="cq",
                preemption=ClusterQueuePreemption(
                    within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY),
                resource_groups=(ResourceGroup(
                    (CPU,), (FlavorQuotas(
                        "default", {CPU: ResourceQuota(1000)}),)),),
            ))
            eng.create_local_queue(LocalQueue("lq", "default", "cq"))
            for i in range(6):
                submit(eng, f"w{i}", 400, priority=i)
                eng.schedule_once()
            drain(eng)
            rec.close()
            return rec.digest

        untraced = run(str(tmp_path / "a.jsonl"), traced=False)
        traced = run(str(tmp_path / "b.jsonl"), traced=True)
        assert traced == untraced


class TestPerfettoExport:
    def _tools(self):
        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        from trace_schema import check_trace_events
        return check_trace_events

    def test_live_export_validates(self, tmp_path):
        from kueue_tpu.obs import write_perfetto

        check = self._tools()
        eng = make_engine(preemption=True)
        tracer = eng.attach_tracer()
        submit(eng, "low", 800)
        drain(eng)
        submit(eng, "high", 800, priority=10)
        drain(eng)
        out = str(tmp_path / "trace.json")
        n = write_perfetto(list(tracer.spans), out)
        with open(out, encoding="utf-8") as fh:
            doc = json.load(fh)
        assert check(doc) == []
        assert n == len(doc["traceEvents"])
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert phases == {"M", "X", "i"}
        # The engine's events before each cycle, on the cycles' lane.
        assert {e["name"] for e in doc["traceEvents"]} >= {
            "phase/intake", "phase/submit"}
        # The decision lane carries the rationale args.
        instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert any(e["args"].get("decision") == "preempting"
                   for e in instants)

    def test_offline_export_from_flight_trace(self, tmp_path):
        from kueue_tpu.obs import spans_from_flight_trace, write_perfetto
        from kueue_tpu.replay.recorder import FlightRecorder

        check = self._tools()
        eng = make_engine()
        rec = FlightRecorder(eng, str(tmp_path / "t.jsonl"),
                             bootstrap=True)
        # No tracer attached: the recording alone must export.
        submit(eng, "ok", 600)
        drain(eng)
        rec.close()
        roots = spans_from_flight_trace(str(tmp_path / "t.jsonl"))
        assert roots
        assert CID_RE.match(roots[0].attrs["cid"])
        wl = [s for s in roots[0].children if s.kind == "workload"]
        assert wl and wl[0].attrs["decision"] == "admitted"
        # The recorded intake (the submit) is laid just before the cycle.
        (intake,) = [s for s in roots[0].children
                     if s.name == "phase/intake"]
        assert intake.attrs["seconds"] > 0
        assert intake.ts + intake.dur == pytest.approx(roots[0].ts)
        out = str(tmp_path / "trace.json")
        write_perfetto(roots, out)
        with open(out, encoding="utf-8") as fh:
            assert check(json.load(fh)) == []


class TestExplain:
    def test_pending_probe_reports_rejection(self):
        eng = make_engine()
        submit(eng, "ok", 600)
        submit(eng, "big", 5000)
        drain(eng)
        report = explain_workload(eng, "default/big")
        assert report["status"] == "pending"
        assert report["cluster_queue"] == "cq"
        probe = report["probe"]
        assert probe["verdict"] == "no-fit"
        assert probe.get("reasons") or probe.get("message")
        text = render_explain(report)
        assert "If scheduled now: no-fit" in text

    def test_preemption_probe_names_victims(self):
        eng = make_engine(preemption=True)
        submit(eng, "low", 800)
        drain(eng)
        eng.clock += 0.5
        hi = Workload(name="high", queue_name="lq", priority=10,
                      pod_sets=(PodSet("main", 1, {CPU: 800}),))
        eng.submit(hi)
        # Probe BEFORE any cycle sees it: pure what-if.
        report = explain_workload(eng, "default/high")
        probe = report["probe"]
        assert probe["verdict"] == "preempt"
        assert ["default/low", probe["preemption_chosen"][0][1]] in \
            probe["preemption_chosen"]
        assert any(r["kind"] == "preemption"
                   for r in probe.get("rationale", ()))
        # The probe must not have perturbed state: low stays admitted,
        # and the real cycle still decides the preemption normally.
        assert eng.workloads["default/low"].is_admitted
        drain(eng)
        assert eng.workloads["default/low"].is_evicted or \
            eng.workloads["default/high"].is_admitted

    def test_trace_section_present_with_tracer(self):
        eng = make_engine()
        eng.attach_tracer()
        submit(eng, "big", 5000)
        drain(eng)
        report = explain_workload(eng, "default/big")
        assert "trace" in report
        assert CID_RE.match(report["trace"]["cid"])
        assert report["trace"]["mode"] == "sequential"
        assert "Last traced decision" in render_explain(report)

    def test_admitted_and_missing(self):
        eng = make_engine()
        submit(eng, "ok", 600)
        drain(eng)
        report = explain_workload(eng, "default/ok")
        assert report["status"] == "admitted"
        assert "probe" not in report
        missing = explain_workload(eng, "default/nope")
        assert not missing["found"]
        assert "not found" in render_explain(missing)

    def test_explain_on_journal_rebuilt_engine(self, tmp_path):
        """The kueuectl story: explain answers from a cold journal
        rebuild, with no tracer ever attached."""
        from kueue_tpu.store.journal import (
            attach_new_journal,
            rebuild_engine,
        )

        eng = make_engine()
        attach_new_journal(eng, str(tmp_path / "j.jsonl"))
        submit(eng, "ok", 600)
        submit(eng, "big", 5000)
        drain(eng)
        reb = rebuild_engine(str(tmp_path / "j.jsonl"))
        report = explain_workload(reb, "default/big")
        assert report["status"] == "pending"
        assert report["probe"]["verdict"] == "no-fit"
        # Probing never perturbs scheduling state.
        before = {k: w.is_admitted for k, w in reb.workloads.items()}
        drain(reb)
        assert {k: w.is_admitted
                for k, w in reb.workloads.items()} == before


class TestOracleBridgePath:
    """The device path lands in the same capture points: span trees and
    explain carry the same structure when the oracle bridge decides."""

    def _engine(self):
        pytest.importorskip("jax")
        eng = make_engine(nominal=3000)
        eng.attach_oracle()
        tracer = eng.attach_tracer()
        return eng, tracer

    def test_device_cycle_span_and_explain(self):
        eng, tracer = self._engine()
        for i in range(4):
            submit(eng, f"w{i}", 1000)
        submit(eng, "big", 50_000)
        drain(eng)
        modes = {root.attrs["mode"] for root in tracer.spans}
        assert modes - {"sequential"}, \
            f"oracle bridge never ran a device/hybrid cycle: {modes}"
        admitted = [k for k, w in eng.workloads.items() if w.is_admitted]
        assert admitted
        _, span = tracer.find_workload(admitted[0])
        assert span is not None and span.attrs["decision"] == "admitted"
        report = explain_workload(eng, "default/big")
        assert report["status"] == "pending"
        assert report["probe"]["verdict"] == "no-fit"
        assert (report["probe"].get("reasons")
                or report["probe"].get("message"))
