"""Metrics registry, visibility server, debugger dump, config, feature
gates."""

import json

from kueue_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.config import features
from kueue_tpu.config.api import Configuration, from_dict, load
from kueue_tpu.controllers.engine import Engine
from kueue_tpu.visibility.server import VisibilityServer, dump_state

CPU = "cpu"


def make_engine(nominal=1000):
    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    eng.create_cluster_queue(ClusterQueue(
        name="cq",
        resource_groups=(ResourceGroup(
            (CPU,),
            (FlavorQuotas("default", {CPU: ResourceQuota(nominal)}),)),),
    ))
    eng.create_local_queue(LocalQueue("lq", "default", "cq"))
    eng.create_local_queue(LocalQueue("lq2", "default", "cq"))
    return eng


def submit(eng, name, cpu, lq="lq", priority=0):
    eng.clock += 0.5
    wl = Workload(name=name, queue_name=lq, priority=priority,
                  pod_sets=(PodSet("main", 1, {CPU: cpu}),))
    eng.submit(wl)
    return wl


def test_metrics_counters_and_render():
    eng = make_engine()
    submit(eng, "a", 600)
    submit(eng, "b", 600)
    eng.schedule_once()
    eng.schedule_once()
    reg = eng.registry
    assert reg.counter("admitted_workloads_total").get(("cq",)) == 1
    assert reg.counter("quota_reserved_workloads_total").get(("cq",)) == 1
    assert reg.counter("admission_attempts_total").get(("success",)) >= 1
    assert reg.gauge("pending_workloads").get(("cq", "inadmissible")) == 1
    text = reg.render()
    assert "kueue_tpu_admitted_workloads_total" in text
    assert "kueue_tpu_admission_attempt_duration_seconds_bucket" in text


def test_visibility_positions():
    eng = make_engine(nominal=100)
    submit(eng, "w1", 600, lq="lq", priority=0)
    submit(eng, "w2", 600, lq="lq2", priority=10)
    submit(eng, "w3", 600, lq="lq", priority=5)
    vis = VisibilityServer(eng)
    summary = vis.pending_workloads_for_cq("cq")
    names = [i.name for i in summary.items]
    assert names == ["w2", "w3", "w1"]  # priority order
    assert [i.position_in_cluster_queue for i in summary.items] == [0, 1, 2]
    lq_items = vis.pending_workloads_for_lq("default", "lq")
    assert [i.name for i in lq_items] == ["w3", "w1"]
    assert [i.position_in_local_queue for i in lq_items] == [0, 1]


def test_debugger_dump():
    eng = make_engine()
    submit(eng, "a", 600)
    submit(eng, "b", 600)
    eng.schedule_once()
    state = dump_state(eng)
    assert state["admitted"]["default/a"]["clusterQueue"] == "cq"
    assert "default/b" in (state["queues"]["cq"]["active"]
                           + state["queues"]["cq"]["inadmissible"])
    json.dumps(state)  # serializable


def test_config_load_and_validate(tmp_path):
    p = tmp_path / "config.json"
    p.write_text(json.dumps({
        "namespace": "scheduling",
        "manageJobsWithoutQueueName": True,
        "waitForPodsReady": {"enable": True, "timeout": 120,
                             "requeuingStrategy": {"backoffBaseSeconds": 10}},
        "fairSharing": {"enable": True},
        "featureGates": {"TASBalancedPlacement": True},
    }))
    cfg = load(str(p))
    assert cfg.namespace == "scheduling"
    assert cfg.manage_jobs_without_queue_name
    assert cfg.wait_for_pods_ready.timeout_seconds == 120
    assert cfg.fair_sharing.enable
    assert cfg.feature_gates["TASBalancedPlacement"]


def test_config_validation_rejects_bad():
    cfg = from_dict({"waitForPodsReady": {"enable": True, "timeout": -1}})
    assert cfg.validate()


def test_feature_gates():
    assert features.enabled("FlavorFungibility")
    assert not features.enabled("ConcurrentAdmission")
    features.set_feature("ConcurrentAdmission", True)
    assert features.enabled("ConcurrentAdmission")
    features.reset()
    assert not features.enabled("ConcurrentAdmission")
    assert not features.enabled("SomeUnknownGate")


def test_unadmitted_per_reason_bookkeeping():
    """unadmitted_workloads.go: per-CQ per-reason gauges track pending
    workloads through their lifecycle."""
    eng = make_engine()
    w_ok = submit(eng, "ok", 500)
    w_big = submit(eng, "big", 5000)  # exceeds quota -> NoFit
    assert eng.unadmitted.count_for_cq("cq", "NoReservation") == 2
    eng.schedule_once()
    eng.schedule_once()
    # ok admitted (removed); big requeued inadmissible with NoFit.
    assert eng.unadmitted.count_for_cq("cq", "NoReservation") == 0
    assert eng.unadmitted.count_for_cq("cq", "NoFit") == 1
    assert eng.registry.gauge("unadmitted_workloads").get(
        ("cq", "NoFit", "")) == 1
    eng.finish(w_big.key)
    assert eng.unadmitted.count_for_cq("cq") == 0


def test_lifecycle_metric_families_populated():
    eng = make_engine()
    wl = submit(eng, "w", 500)
    eng.schedule_once()
    assert wl.is_admitted
    lq = ("default/lq",)
    r = eng.registry
    assert r.counter("local_queue_admitted_workloads_total").get(lq) == 1
    assert r.counter("local_queue_quota_reserved_workloads_total").get(lq) == 1
    eng.evict(wl, "Preempted")
    assert r.counter("local_queue_evicted_workloads_total").get(
        lq + ("Preempted",)) == 1
    assert r.counter("evicted_workloads_once_total").get(
        ("cq", "Preempted")) == 1
    eng.evict(eng.workloads["default/w"], "Preempted")  # not admitted: no-op-ish
    # once_total stays 1 even if evicted again later.
    assert r.counter("evicted_workloads_once_total").get(
        ("cq", "Preempted")) == 1
    assert r.histogram("workload_eviction_latency_seconds").totals[
        ("cq", "Preempted")] >= 1
    eng.schedule_once()
    eng.finish(wl.key)
    assert r.counter("finished_workloads_total").get(("cq", "Succeeded")) == 1


def test_phase_timing_recorded():
    eng = make_engine()
    submit(eng, "w", 500)
    eng.schedule_once()
    # The sequential path's leaves, what brackets them in
    # schedule_once(), the two totals (obs.span.phase_seconds), and the
    # window's keys: the submit before the cycle, no launch.
    from kueue_tpu.obs.span import WINDOW_KEYS

    assert set(eng.last_cycle_phases) == {
        "pre_hooks", "snapshot", "decide", "apply", "listeners",
        "unattributed", "schedule_once"} | WINDOW_KEYS
    assert eng.last_cycle_phases["n_intake_calls"] == 1
    assert all(v >= 0 for v in eng.last_cycle_phases.values())
    h = eng.registry.histogram("scheduler_phase_duration_seconds")
    assert h.totals[("decide",)] == 1


def test_resource_and_cohort_gauges():
    from kueue_tpu.api.types import Cohort

    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    eng.create_cohort(Cohort("root"))
    eng.create_cohort(Cohort("child", parent="root"))
    eng.create_cluster_queue(ClusterQueue(
        name="cq", cohort="child",
        resource_groups=(ResourceGroup(
            ("cpu",),
            (FlavorQuotas("default",
                          {"cpu": ResourceQuota(1000,
                                                borrowing_limit=200)}),)),)))
    eng.create_local_queue(LocalQueue("lq", "default", "cq"))
    submit(eng, "w", 600)
    submit(eng, "pend", 600)
    eng.schedule_once()
    eng.sync_resource_metrics()
    g = eng.registry.gauge
    assert g("cluster_queue_resource_usage").get(
        ("cq", "default", "cpu")) == 600
    assert g("cluster_queue_resource_reservation").get(
        ("cq", "default", "cpu")) == 600
    assert g("cluster_queue_nominal_quota").get(
        ("cq", "default", "cpu")) == 1000
    assert g("cluster_queue_borrowing_limit").get(
        ("cq", "default", "cpu")) == 200
    assert g("cluster_queue_resource_pending").get(("cq", "cpu")) == 600
    assert g("local_queue_resource_usage").get(
        ("default/lq", "default", "cpu")) == 600
    assert g("reserving_active_workloads").get(("cq",)) == 1
    assert g("cohort_subtree_quota").get(("child", "default", "cpu")) == 1000
    assert g("cohort_subtree_resource_reservations").get(
        ("child", "default", "cpu")) == 600
    assert g("cohort_subtree_admitted_active_workloads").get(("child",)) == 1
    assert g("cohort_info").get(("child", "root")) == 1
    assert g("cluster_queue_info").get(("cq", "child")) == 1
    # Render covers the new families without error.
    text = eng.registry.render()
    assert "kueue_tpu_cohort_subtree_quota" in text


def test_resource_gauges_clear_when_sources_vanish():
    eng = make_engine()
    wl = submit(eng, "w", 500)
    eng.schedule_once()
    eng.sync_resource_metrics()
    g = eng.registry.gauge
    assert g("cluster_queue_resource_usage").get(
        ("cq", "default", CPU)) == 500
    eng.finish(wl.key)
    eng.sync_resource_metrics()
    assert g("cluster_queue_resource_usage").get(
        ("cq", "default", CPU)) == 0
    assert g("local_queue_resource_usage").get(
        ("default/lq", "default", CPU)) == 0


def test_custom_metric_labels_from_cq_metadata():
    """pkg/metrics/custom_labels.go: configured entries add
    custom_<name> label pairs sourced from CQ labels/annotations."""
    from kueue_tpu.config.api import from_dict

    cfg = from_dict({"metrics": {"customLabels": [
        {"name": "team"},
        {"name": "tier", "sourceAnnotationKey": "example.com/tier"}]}})
    eng = Engine(config=cfg)
    eng.create_resource_flavor(ResourceFlavor("default"))
    eng.create_cluster_queue(ClusterQueue(
        name="cq", labels={"team": "ml"},
        annotations={"example.com/tier": "prod"},
        resource_groups=(ResourceGroup(
            (CPU,),
            (FlavorQuotas("default", {CPU: ResourceQuota(1000)}),)),)))
    eng.create_local_queue(LocalQueue("lq", "default", "cq"))
    wl = submit(eng, "w", 500)
    eng.schedule_once()
    assert wl.is_admitted
    key = ("cq", ("custom_team", "ml"), ("custom_tier", "prod"))
    assert eng.registry.counter("admitted_workloads_total").get(key) == 1
    rendered = eng.registry.render()
    assert 'custom_team="ml"' in rendered
    eng.evict(wl, "Preempted")
    assert eng.registry.counter("evicted_workloads_total").get(
        ("cq", "Preempted", ("custom_team", "ml"),
         ("custom_tier", "prod"))) == 1


def test_profiled_context_writes_trace(tmp_path):
    """Engine.profiled captures a JAX profiler trace (the pprof-server
    analog, configuration_types.go:140)."""
    from kueue_tpu.api.types import (
        ClusterQueue,
        FlavorQuotas,
        LocalQueue,
        PodSet,
        ResourceFlavor,
        ResourceGroup,
        ResourceQuota,
        Workload,
    )
    from kueue_tpu.controllers.engine import Engine

    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("d"))
    eng.create_cluster_queue(ClusterQueue(
        name="cq", resource_groups=(ResourceGroup(
            ("cpu",),
            (FlavorQuotas("d", {"cpu": ResourceQuota(1000)}),)),)))
    eng.create_local_queue(LocalQueue("lq", "default", "cq"))
    eng.submit(Workload(name="w", queue_name="lq",
                        pod_sets=(PodSet("m", 1, {"cpu": 100}),)))
    trace_dir = str(tmp_path / "traces")
    with eng.profiled(trace_dir):
        eng.schedule_once()
    assert eng.workloads["default/w"].is_admitted
    import os
    found = [f for _, _, fs in os.walk(trace_dir) for f in fs]
    assert found, "profiler wrote no trace files"


def test_profiled_noop_without_dir(monkeypatch):
    from kueue_tpu.controllers.engine import Engine

    monkeypatch.delenv("KUEUE_TPU_PROFILE", raising=False)
    eng = Engine()
    with eng.profiled():
        pass


class TestEventStream:
    """The /events SSE surface (round-4 verdict ask #10): a connected
    session observes admissions PUSHED from the engine's event fan-out
    — no polling."""

    def _world(self):
        from kueue_tpu.api.types import (
            ClusterQueue,
            FlavorQuotas,
            LocalQueue,
            PodSet,
            ResourceFlavor,
            ResourceGroup,
            ResourceQuota,
            Workload,
        )
        from kueue_tpu.controllers.engine import Engine

        eng = Engine()
        eng.create_resource_flavor(ResourceFlavor("default"))
        eng.create_cluster_queue(ClusterQueue(
            name="cq", resource_groups=(ResourceGroup(
                ("cpu",), (FlavorQuotas(
                    "default", {"cpu": ResourceQuota(4000)}),)),)))
        eng.create_local_queue(LocalQueue("lq", "default", "cq"))
        return eng, Workload, PodSet

    def test_sse_pushes_admission_without_polling(self):
        import http.client
        import json as _json
        import threading
        import time as _time

        from kueue_tpu.visibility.http_server import ServingEndpoint

        eng, Workload, PodSet = self._world()
        ep = ServingEndpoint(eng, port=0)
        ep.start()
        got: dict = {}
        ready = threading.Event()

        def subscribe():
            conn = http.client.HTTPConnection("127.0.0.1", ep.port,
                                              timeout=30)
            conn.request("GET", "/events")
            resp = conn.getresponse()
            got["content_type"] = resp.headers.get("Content-Type")
            event = None
            ready.set()
            while True:
                line = resp.fp.readline().decode()
                if line.startswith("event:"):
                    event = line.split(":", 1)[1].strip()
                elif line.startswith("data:") and event == "Admitted":
                    got["admitted"] = _json.loads(
                        line.split(":", 1)[1])
                    return

        t = threading.Thread(target=subscribe, daemon=True)
        t.start()
        assert ready.wait(10)
        _time.sleep(0.1)  # listener registration races the first event
        eng.submit(Workload(name="w", queue_name="lq",
                            pod_sets=(PodSet("main", 1,
                                             {"cpu": 1000}),)))
        eng.schedule_once()
        t.join(timeout=20)
        ep.stop()
        assert not t.is_alive(), "no Admitted event arrived on the stream"
        assert got["content_type"].startswith("text/event-stream")
        assert got["admitted"]["workload"] == "default/w"
        assert got["admitted"]["clusterQueue"] == "cq"

    def test_sse_heartbeat_comments_on_idle_stream(self):
        """An idle /events connection still carries traffic: SSE comment
        heartbeats every heartbeat_seconds (invisible to EventSource,
        but enough to keep proxy/LB idle timeouts from dropping the
        stream)."""
        import http.client
        import threading

        from kueue_tpu.visibility.http_server import ServingEndpoint

        eng, _, _ = self._world()
        ep = ServingEndpoint(eng, port=0, heartbeat_seconds=0.1)
        ep.start()
        beats: list = []
        done = threading.Event()

        def subscribe():
            conn = http.client.HTTPConnection("127.0.0.1", ep.port,
                                              timeout=30)
            conn.request("GET", "/events")
            resp = conn.getresponse()
            while len(beats) < 3:
                line = resp.fp.readline().decode()
                if line.startswith(": keep-alive"):
                    beats.append(line)
            done.set()

        t = threading.Thread(target=subscribe, daemon=True)
        t.start()
        # The engine is completely idle: the heartbeat comments are the
        # ONLY traffic on the stream.
        assert done.wait(10), "heartbeat comments did not arrive"
        ep.stop()
        assert len(beats) >= 3

    def test_dashboard_page_wires_event_source(self):
        from kueue_tpu.visibility.dashboard import DASHBOARD_HTML

        assert "EventSource(\"/events\")" in DASHBOARD_HTML
