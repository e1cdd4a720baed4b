"""Durability + restart: the journal (store/journal.py) is the analog of
the reference's "Kubernetes API as durable store" — workload status
transitions persist as apply records and a cold-started engine rebuilds
its caches/queues from the log (the informer-rebuild path), preserving
admissions, requeue backoffs, and in-flight preemption state."""

import random

import pytest

from kueue_tpu.api.serde import from_jsonable, to_jsonable
from kueue_tpu.api.types import (
    ClusterQueue,
    ClusterQueuePreemption,
    Cohort,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PodSetTopologyRequest,
    PreemptionPolicy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Topology,
    TopologyLevel,
    TopologyMode,
    Workload,
)
from kueue_tpu.controllers.engine import Engine
from kueue_tpu.store.journal import (
    Journal,
    attach_new_journal,
    rebuild_engine,
)
from kueue_tpu.tas.snapshot import HOSTNAME_LABEL, Node


def test_serde_roundtrip_workload():
    wl = Workload(
        name="w", queue_name="lq", priority=7,
        pod_sets=(PodSet("main", 4, {"cpu": 1000},
                         topology_request=PodSetTopologyRequest(
                             mode=TopologyMode.REQUIRED, level="rack",
                             slice_size=2, slice_level="rack")),
                  PodSet("side", 1, {"mem": 64})))
    wl.set_condition("Admitted", True, reason="x", now=3.0)
    wl.status.requeue_count = 2
    wl.status.requeue_at = 9.5
    wl.status.unhealthy_nodes = ("n1",)
    data = to_jsonable(wl)
    import json
    back = from_jsonable(json.loads(json.dumps(data)))
    assert back == wl


def test_serde_roundtrip_cluster_queue():
    cq = ClusterQueue(
        name="cq", cohort="co",
        preemption=ClusterQueuePreemption(
            within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY),
        resource_groups=(ResourceGroup(
            ("cpu",), (FlavorQuotas(
                "f", {"cpu": ResourceQuota(100, borrowing_limit=50)}),)),))
    assert from_jsonable(to_jsonable(cq)) == cq


def build_world(eng, preemption=False):
    eng.create_resource_flavor(ResourceFlavor("default"))
    eng.create_cohort(Cohort("co"))
    for i in range(3):
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort="co",
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY)
            if preemption else ClusterQueuePreemption(),
            resource_groups=(ResourceGroup(
                ("cpu",),
                (FlavorQuotas("default",
                              {"cpu": ResourceQuota(2000)}),)),)))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))


def engine_state(eng):
    return {
        "workloads": {
            k: (wl.is_admitted, wl.is_finished, wl.status.requeue_count,
                wl.status.requeue_at,
                None if wl.status.admission is None else
                to_jsonable(wl.status.admission))
            for k, wl in sorted(eng.workloads.items())},
        "pending": sorted(
            key for pcq in eng.queues.cluster_queues.values()
            for key in list(pcq.items) + list(pcq.inadmissible)),
        "usage": {
            name: sorted((str(fr), v)
                         for fr, v in cqs.node.usage.items() if v)
            for name, cqs in eng.cache.snapshot().cluster_queues.items()},
    }


def submit_random(eng, rng, n, schedule_every):
    """Shared randomized submit/schedule cadence for the restart suites
    (one definition so every restart world stays identical in shape)."""
    for i in range(n):
        eng.clock += 0.5
        eng.submit(Workload(
            name=f"w{i}", queue_name=f"lq{rng.randrange(3)}",
            priority=rng.choice([0, 5]),
            pod_sets=(PodSet("main", 1,
                             {"cpu": rng.choice([800, 1500])}),)))
        if i % schedule_every == schedule_every - 1:
            eng.schedule_once()


def test_kill_restart_preserves_state(tmp_path):
    rng = random.Random(4)
    eng = Engine()
    build_world(eng, preemption=True)
    attach_new_journal(eng, str(tmp_path / "journal.jsonl"))
    submit_random(eng, rng, 12, schedule_every=3)
    # One more cycle that issues preemptions and leaves them in flight
    # (victims evicted + requeued, preemptors still pending).
    eng.schedule_once()
    state_before = engine_state(eng)
    assert any(w.is_admitted for w in eng.workloads.values())

    # "Kill": drop the engine; cold-start from the journal.
    reb = rebuild_engine(str(tmp_path / "journal.jsonl"))
    assert reb.clock == eng.clock
    assert engine_state(reb) == state_before

    # Both continue identically.
    for e in (eng, reb):
        for _ in range(30):
            r = e.schedule_once()
            if r is None or not r.assumed:
                break
            e.tick(0.0)
    assert engine_state(reb) == engine_state(eng)


def test_restart_preserves_requeue_backoff(tmp_path):
    eng = Engine()
    build_world(eng)
    attach_new_journal(eng, str(tmp_path / "j.jsonl"))
    eng.submit(Workload(name="w", queue_name="lq0",
                        pod_sets=(PodSet("main", 1, {"cpu": 500}),)))
    eng.schedule_once()
    wl = eng.workloads["default/w"]
    eng.evict(wl, "Preempted", backoff_seconds=60.0)
    assert wl.status.requeue_at is not None

    reb = rebuild_engine(str(tmp_path / "j.jsonl"))
    rwl = reb.workloads["default/w"]
    assert rwl.status.requeue_at == wl.status.requeue_at
    assert rwl.status.requeue_count == 1
    # Before the backoff expires nothing schedules; after, it re-admits.
    reb.schedule_once()
    assert not reb.workloads["default/w"].is_admitted
    reb.tick(61.0)
    reb.schedule_once()
    assert reb.workloads["default/w"].is_admitted


def test_restart_with_tas_assignments(tmp_path):
    eng = Engine()
    eng.create_topology(Topology("dc", (TopologyLevel("rack"),
                                        TopologyLevel(HOSTNAME_LABEL))))
    eng.create_resource_flavor(ResourceFlavor(name="tas",
                                              topology_name="dc"))
    for h in range(4):
        eng.create_node(Node(name=f"h{h}",
                             labels={"rack": f"r{h % 2}",
                                     HOSTNAME_LABEL: f"h{h}"},
                             capacity={"cpu": 4000}))
    eng.create_cluster_queue(ClusterQueue(
        name="cq", resource_groups=(ResourceGroup(
            ("cpu",), (FlavorQuotas("tas",
                                    {"cpu": ResourceQuota(16000)}),)),)))
    eng.create_local_queue(LocalQueue("lq", "default", "cq"))
    attach_new_journal(eng, str(tmp_path / "j.jsonl"))
    eng.submit(Workload(
        name="gang", queue_name="lq",
        pod_sets=(PodSet("main", 4, {"cpu": 1000},
                         topology_request=PodSetTopologyRequest(
                             mode=TopologyMode.REQUIRED, level="rack")),)))
    eng.schedule_once()
    wl = eng.workloads["default/gang"]
    assert wl.is_admitted
    ta = wl.status.admission.pod_set_assignments[0].topology_assignment
    assert ta is not None

    reb = rebuild_engine(str(tmp_path / "j.jsonl"))
    rwl = reb.workloads["default/gang"]
    rta = rwl.status.admission.pod_set_assignments[0].topology_assignment
    assert rta == ta
    # TAS usage reconstructed: a second 4-pod gang must not double-book
    # the same rack capacity.
    reb.submit(Workload(
        name="gang2", queue_name="lq",
        pod_sets=(PodSet("main", 4, {"cpu": 1000},
                         topology_request=PodSetTopologyRequest(
                             mode=TopologyMode.REQUIRED, level="rack")),)))
    reb.schedule_once()
    wl2 = reb.workloads["default/gang2"]
    if wl2.is_admitted:
        ta2 = wl2.status.admission.pod_set_assignments[0] \
            .topology_assignment
        used = {d.values for d in ta.domains}
        # Disjoint leaf capacity: combined per-leaf demand within 4000.
        for d in ta2.domains:
            if d.values in used:
                kept = sum(x.count for x in ta.domains
                           if x.values == d.values)
                assert (kept + d.count) * 1000 <= 4000


def test_deleted_node_stays_deleted(tmp_path):
    eng = Engine()
    eng.create_topology(Topology("dc", (TopologyLevel("rack"),
                                        TopologyLevel(HOSTNAME_LABEL))))
    eng.create_resource_flavor(ResourceFlavor(name="tas",
                                              topology_name="dc"))
    attach_new_journal(eng, str(tmp_path / "j.jsonl"))
    for h in range(2):
        eng.create_node(Node(name=f"h{h}",
                             labels={"rack": "r0",
                                     HOSTNAME_LABEL: f"h{h}"},
                             capacity={"cpu": 4000}))
    eng.mark_node_unhealthy("h1", "died")
    reb = rebuild_engine(str(tmp_path / "j.jsonl"))
    assert "h0" in reb.cache.nodes
    assert "h1" not in reb.cache.nodes


def test_rejected_workload_stays_inactive(tmp_path):
    from kueue_tpu.controllers.admissionchecks import (
        AdmissionCheck,
        AdmissionCheckManager,
        CheckState,
    )

    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    mgr = AdmissionCheckManager(eng)
    mgr.create_admission_check(AdmissionCheck("manual"))
    eng.create_cluster_queue(ClusterQueue(
        name="cq0", admission_checks=("manual",),
        resource_groups=(ResourceGroup(
            ("cpu",), (FlavorQuotas("default",
                                    {"cpu": ResourceQuota(2000)}),)),)))
    eng.create_local_queue(LocalQueue("lq0", "default", "cq0"))
    attach_new_journal(eng, str(tmp_path / "j.jsonl"))
    eng.submit(Workload(name="w", queue_name="lq0",
                        pod_sets=(PodSet("main", 1, {"cpu": 500}),)))
    eng.schedule_once()
    wl = eng.workloads["default/w"]
    assert wl.status.admission is not None and not wl.is_admitted
    wl.status.admission_check_states["manual"] = CheckState.REJECTED
    eng.reconcile_workload(wl)
    assert not wl.active

    reb = rebuild_engine(str(tmp_path / "j.jsonl"))
    rwl = reb.workloads["default/w"]
    assert not rwl.active
    reb.schedule_once()
    assert not reb.workloads["default/w"].is_admitted


def test_restart_rearms_pending_node_replacement(tmp_path):
    eng = Engine()
    eng.create_topology(Topology("dc", (TopologyLevel("rack"),
                                        TopologyLevel(HOSTNAME_LABEL))))
    eng.create_resource_flavor(ResourceFlavor(name="tas",
                                              topology_name="dc"))
    for h in range(3):
        eng.create_node(Node(name=f"h{h}",
                             labels={"rack": "r0",
                                     HOSTNAME_LABEL: f"h{h}"},
                             capacity={"cpu": 4000}))
    eng.create_cluster_queue(ClusterQueue(
        name="cq", resource_groups=(ResourceGroup(
            ("cpu",), (FlavorQuotas("tas",
                                    {"cpu": ResourceQuota(12000)}),)),)))
    eng.create_local_queue(LocalQueue("lq", "default", "cq"))
    attach_new_journal(eng, str(tmp_path / "j.jsonl"))
    eng.submit(Workload(
        name="w", queue_name="lq",
        pod_sets=(PodSet("main", 2, {"cpu": 1000},
                         topology_request=PodSetTopologyRequest(
                             mode=TopologyMode.PREFERRED,
                             level="rack")),)))
    eng.schedule_once()
    wl = eng.workloads["default/w"]
    assert wl.is_admitted
    failed = wl.status.admission.pod_set_assignments[0] \
        .topology_assignment.domains[0].values[-1]
    eng.mark_node_unhealthy(failed, "died")
    assert eng.workloads["default/w"].status.unhealthy_nodes

    # Restart before the replacement pass ran.
    reb = rebuild_engine(str(tmp_path / "j.jsonl"))
    reb.schedule_once()  # runs the second pass
    rwl = reb.workloads["default/w"]
    assert not rwl.status.unhealthy_nodes, "replacement never ran"
    new_nodes = {d.values[-1] for d in rwl.status.admission.
                 pod_set_assignments[0].topology_assignment.domains}
    assert failed not in new_nodes


def test_torn_tail_repaired_for_subsequent_appends(tmp_path):
    """A torn tail must not swallow records appended after restart."""
    path = str(tmp_path / "j.jsonl")
    eng = Engine()
    build_world(eng)
    attach_new_journal(eng, path)
    eng.submit(Workload(name="w", queue_name="lq0",
                        pod_sets=(PodSet("main", 1, {"cpu": 500}),)))
    with open(path, "a") as fh:
        fh.write('{"op": "apply", "kind": "workload", "obj": {"trunc')
    reb = rebuild_engine(path)
    reb.clock += 1
    reb.submit(Workload(name="w2", queue_name="lq1",
                        pod_sets=(PodSet("main", 1, {"cpu": 500}),)))
    reb.schedule_once()
    reb2 = rebuild_engine(path)
    assert "default/w2" in reb2.workloads
    assert reb2.workloads["default/w2"].is_admitted


def test_torn_tail_line_ignored(tmp_path):
    eng = Engine()
    build_world(eng)
    attach_new_journal(eng, str(tmp_path / "j.jsonl"))
    eng.submit(Workload(name="w", queue_name="lq0",
                        pod_sets=(PodSet("main", 1, {"cpu": 500}),)))
    eng.schedule_once()
    with open(tmp_path / "j.jsonl", "a") as fh:
        fh.write('{"op": "apply", "kind": "workload", "obj": {"trunc')
    reb = rebuild_engine(str(tmp_path / "j.jsonl"))
    assert reb.workloads["default/w"].is_admitted


def test_corrupt_final_line_with_newline_trimmed(tmp_path):
    """A torn write that happens to end on the newline byte leaves a
    complete-but-unparseable final line; reattach must trim exactly that
    one record (not just newline-less fragments)."""
    path = str(tmp_path / "j.jsonl")
    eng = Engine()
    build_world(eng)
    attach_new_journal(eng, path)
    eng.submit(Workload(name="w", queue_name="lq0",
                        pod_sets=(PodSet("main", 1, {"cpu": 500}),)))
    eng.schedule_once()
    with open(path) as fh:
        n_lines = len(fh.readlines())
    with open(path, "a") as fh:
        fh.write('{"op": "apply", "kind": "workload", "obj": {"trunc\n')
    reb = rebuild_engine(path)
    assert reb.workloads["default/w"].is_admitted
    with open(path) as fh:
        lines = fh.readlines()
    assert len(lines) == n_lines, "repair did not trim the corrupt line"
    assert all(line.endswith("\n") for line in lines)


def test_corruption_mid_file_raises(tmp_path):
    """A corrupt record FOLLOWED by valid records is not a crash
    artifact — replaying past it would silently drop state, so replay
    must refuse (JournalCorruption), not trim."""
    from kueue_tpu.store.journal import JournalCorruption

    path = str(tmp_path / "j.jsonl")
    eng = Engine()
    build_world(eng)
    attach_new_journal(eng, path)
    eng.submit(Workload(name="w", queue_name="lq0",
                        pod_sets=(PodSet("main", 1, {"cpu": 500}),)))
    eng.schedule_once()
    with open(path) as fh:
        lines = fh.readlines()
    lines[len(lines) // 2] = '{"op": "apply", "kind": "wor\n'
    with open(path, "w") as fh:
        fh.writelines(lines)
    with pytest.raises(JournalCorruption):
        list(Journal(path).replay())
    with pytest.raises(JournalCorruption):
        rebuild_engine(path)


def test_sync_on_cycle_boundary(tmp_path):
    """Engine.schedule_once calls journal.sync() after every non-idle
    cycle: appends since the last sync are flushed+fsynced, and an idle
    loop never touches the disk (the dirty flag gates the no-op)."""
    path = str(tmp_path / "j.jsonl")
    eng = Engine()
    build_world(eng)
    journal = attach_new_journal(eng, path)  # fsync=False per append
    journal.sync()
    eng.submit(Workload(name="w", queue_name="lq0",
                        pod_sets=(PodSet("main", 1, {"cpu": 500}),)))
    assert journal._dirty, "append did not mark the journal dirty"
    r = eng.schedule_once()
    assert r is not None
    assert not journal._dirty, "cycle boundary did not sync"
    # Idle cycles: no appends, sync stays a no-op.
    eng.schedule_once()
    assert not journal._dirty


def test_compact_preserves_rebuild(tmp_path):
    eng = Engine()
    build_world(eng)
    journal = attach_new_journal(eng, str(tmp_path / "j.jsonl"))
    for i in range(6):
        eng.clock += 1
        eng.submit(Workload(name=f"w{i}", queue_name=f"lq{i % 3}",
                            pod_sets=(PodSet("main", 1,
                                             {"cpu": 600}),)))
        eng.schedule_once()
    eng.finish("default/w0")
    before = engine_state(eng)
    n_before = sum(1 for _ in journal.replay())
    journal.compact()
    n_after = sum(1 for _ in journal.replay())
    assert n_after < n_before
    reb = rebuild_engine(str(tmp_path / "j.jsonl"))
    assert engine_state(reb) == before


def test_serde_roundtrip_check_states_and_templates():
    """Journal-reachable types outside api.types (CheckState,
    PodSetUpdate, PodTemplate/ContainerSpec) must round-trip."""
    from kueue_tpu.controllers.admissionchecks import CheckState, PodSetUpdate
    from kueue_tpu.utils.podtemplate import ContainerSpec, PodTemplate

    wl = Workload(name="w", pod_sets=(PodSet(
        "main", 1, {"cpu": 100},
        template=PodTemplate(containers=[
            ContainerSpec("app", {"cpu": 100}, {"cpu": 200})])),))
    wl.status.admission_check_states["prov"] = CheckState.PENDING
    wl.status.admission_check_updates["prov"] = (
        PodSetUpdate.make("main", node_selector={"zone": "a"}),)
    back = from_jsonable(to_jsonable(wl))
    assert back.status.admission_check_states["prov"] == CheckState.PENDING
    assert back.status.admission_check_updates["prov"][0].node_selector \
        == (("zone", "a"),)
    assert back.pod_sets[0].template.containers[0].limits == {"cpu": 200}


def test_inadmissible_workload_not_resurrected_on_restart(tmp_path):
    """A namespace-selector-mismatched workload parks inadmissible at
    NOMINATION (scheduler.go:636) and must stay parked — not admitted —
    across a journal rebuild."""
    from kueue_tpu.store.journal import Journal

    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    eng.create_cluster_queue(ClusterQueue(
        name="cq", namespace_selector={"team": "ml"},
        resource_groups=(ResourceGroup(
            ("cpu",),
            (FlavorQuotas("default", {"cpu": ResourceQuota(1000)}),)),)))
    eng.create_local_queue(LocalQueue("lq", "default", "cq"))
    eng.attach_journal(Journal(str(tmp_path / "j.jsonl")))
    wl = Workload(name="w", queue_name="lq",
                  pod_sets=(PodSet("main", 1, {"cpu": 100}),))
    assert eng.submit(wl)  # queued; validated during nomination
    eng.schedule_once()
    assert "default/w" in eng.queues.cluster_queues["cq"].inadmissible

    reb = rebuild_engine(str(tmp_path / "j.jsonl"))
    reb.schedule_once()
    assert not reb.workloads["default/w"].is_admitted


def test_versioned_read_tolerates_renames_and_unknown_fields():
    """api/conversion.py: journals from other schema versions replay —
    renamed fields map, unknown fields drop, missing fields default
    (the apis/{v1beta1,v1beta2} conversion analog)."""
    from kueue_tpu.api import conversion

    data = to_jsonable(Workload(
        name="w", pod_sets=(PodSet("main", 1, {"cpu": 100}),)))
    # A newer writer added a field we do not know.
    data["from_the_future"] = {"x": 1}
    back = from_jsonable(data)
    assert back.name == "w"
    # A renamed field maps onto its new name. (The table is the
    # module's own: put back what it held, or the next test on this
    # worker reads Workload records without the schema's own renames.)
    held = dict(conversion.FIELD_RENAMES.get("Workload", {}))
    conversion.register_rename("Workload", "legacy_queue", "queue_name")
    try:
        data2 = to_jsonable(Workload(name="w2"))
        del data2["queue_name"]
        data2["legacy_queue"] = "lq9"
        assert from_jsonable(data2).queue_name == "lq9"
        # A retired field drops.
        conversion.register_rename("Workload", "dead_field", None)
        data3 = to_jsonable(Workload(name="w3"))
        data3["dead_field"] = True
        assert from_jsonable(data3).name == "w3"
    finally:
        conversion.FIELD_RENAMES["Workload"] = held


def test_journal_records_are_versioned_and_upgraded(tmp_path):
    import json as _json

    from kueue_tpu.api.conversion import SCHEMA_VERSION

    eng = Engine()
    build_world(eng)
    attach_new_journal(eng, str(tmp_path / "j.jsonl"))
    eng.submit(Workload(name="w", queue_name="lq0",
                        pod_sets=(PodSet("main", 1, {"cpu": 100}),)))
    with open(tmp_path / "j.jsonl") as f:
        records = [_json.loads(line) for line in f if line.strip()]
    assert all(r["v"] == SCHEMA_VERSION for r in records)
    # An unversioned (round-1) journal replays through the upgrader.
    legacy = tmp_path / "legacy.jsonl"
    with open(legacy, "w") as f:
        for r in records:
            r = dict(r)
            r.pop("v")
            f.write(_json.dumps(r) + "\n")
    reb = rebuild_engine(str(legacy))
    assert "default/w" in reb.workloads


def test_restart_then_oracle_fast_path(tmp_path):
    """Cold-start from the journal, then attach the batched oracle: the
    rebuilt queue manager's row cache and admitted aggregates must feed
    device cycles that match a never-killed engine running the same
    continuation sequentially."""
    rng = random.Random(9)
    eng = Engine()
    build_world(eng, preemption=True)
    attach_new_journal(eng, str(tmp_path / "j.jsonl"))
    submit_random(eng, rng, 14, schedule_every=4)

    reb = rebuild_engine(str(tmp_path / "j.jsonl"))
    assert engine_state(reb) == engine_state(eng)
    # The rebuilt pending world must be fully represented in the row
    # cache (journal replay flows through the same queue hooks).
    rows = reb.queues.rows
    pending_keys = {k for pcq in reb.queues.cluster_queues.values()
                    for k in list(pcq.items) + list(pcq.inadmissible)}
    row_keys = {info.key for info in rows.info_of if info is not None}
    assert pending_keys == row_keys

    reb.attach_oracle()
    for e in (eng, reb):
        for _ in range(40):
            r = e.schedule_once()
            if r is None or (not r.assumed and not any(
                    en.status.value == "preempting" for en in r.entries)):
                break
            e.tick(0.0)
    assert engine_state(reb) == engine_state(eng)
    assert reb.oracle.cycles_on_device > 0
