"""One way through a cycle (oracle/engine_bridge.py, ISSUE 32): every
schedule_once() encodes the engine's state as it then is, launches the
cycle program once and commits its verdicts — whatever the client did
or did not do since the last one, and whatever the bridge did with the
cycle before. The yardstick is the sequential core: the same world and
the same gaps on an engine with no oracle attached decide the same,
cycle for cycle. And the phase dict of every kind of cycle holds the
documented keys and adds up."""

import re

import pytest

jax = pytest.importorskip("jax")

from kueue_tpu.api.types import (  # noqa: E402
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.controllers.engine import Engine  # noqa: E402
from kueue_tpu.obs import span as span_mod  # noqa: E402
from kueue_tpu.replay.trace import (  # noqa: E402
    canonical_decisions,
    decision_digest,
)
from tests import test_span_tree as trees  # noqa: E402

QUEUES = 2
BACKLOG = 12  # a queue: one head a cycle, so every cycle has work

# What the client does between two cycles, one letter a gap: nothing
# (q), a submit (s), a step of the engine's clock (c). A served loop
# speaks before every cycle; a drain loop never; the last two turn from
# one to the other and back.
SHAPES = ["sssss", "ccccc", "qqqqq", "qsqq", "qscqq"]


def make_engine(oracle: bool):
    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    for i in range(QUEUES):
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort=f"co{i}",
            resource_groups=(ResourceGroup(
                ("cpu",),
                (FlavorQuotas("default", {"cpu": ResourceQuota(1000)}),)),),
        ))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    if oracle:
        eng.attach_oracle()
    for n in range(BACKLOG):
        for i in range(QUEUES):
            submit(eng, f"w{i}-{n}", lq=f"lq{i}")
    return eng


def submit(eng, name, lq="lq0", count=1, priority=0, **podset):
    eng.submit(Workload(
        name=name, queue_name=lq, priority=priority,
        pod_sets=(PodSet("main", count, {"cpu": 50}, **podset),)))


def speak(eng, gap, i):
    if gap == "s":
        submit(eng, f"late{i}", lq=f"lq{i % QUEUES}")
    elif gap == "c":
        eng.clock += 1.0
    else:
        assert gap == "q"


class Loop:
    """An engine, the chained digest of what it has decided, and — with
    the oracle attached — every call of the cycle program."""

    def __init__(self, oracle: bool):
        self.eng = make_engine(oracle)
        self.digests, self.launches = [], []
        if oracle:
            inner = self.eng.oracle.executor.cycle_step

            def tap(tensors, statics):
                self.launches.append(self.eng.cycle_seq)
                return inner(tensors, statics)

            self.eng.oracle.executor.cycle_step = tap

    def cycle(self):
        r = self.eng.schedule_once()
        assert r is not None and r.stats.admitted
        self.digests.append(decision_digest(
            canonical_decisions(r), self.digests[-1] if self.digests else 0))
        return self.eng.spans.last()


def run(oracle: bool, gaps: str):
    """Cycle 0 on the backlog, then one cycle after each gap."""
    loop = Loop(oracle)
    roots = []
    for i, gap in enumerate("q" + gaps):
        speak(loop.eng, gap, i)
        roots.append(loop.cycle())
    return loop, roots


@pytest.mark.parametrize("gaps", SHAPES)
def test_one_launch_a_schedule_once(gaps):
    loop, roots = run(True, gaps)
    assert not loop.eng.oracle.fallback_reasons
    assert len(loop.launches) == len(roots) == len(set(loop.launches))
    for root in roots:
        assert root.attrs["mode"] == "device"
        assert [n for n in trees.names(root) if n != "intake"] == [
            "pre_hooks", "cycle", "listeners"]
        cyc = trees.child(root, "cycle")
        assert trees.names(cyc) == trees.ENCODE + trees.COMMIT
        counts = span_mod.phase_seconds(root)
        assert (counts["n_launches"], counts["n_device_cycles"]) == (1, 1)
    assert loop.eng.last_cycle_phases["n_launches"] == 1


@pytest.mark.parametrize("gaps", SHAPES)
def test_the_device_path_decides_what_the_sequential_core_decides(gaps):
    device, _ = run(True, gaps)
    sequential, roots = run(False, gaps)
    assert all(r.attrs["mode"] == "sequential" for r in roots)
    assert device.digests == sequential.digests
    assert len(set(device.digests)) == len(gaps) + 1


def sat_out(loop, how, monkeypatch):
    """Bring about a cycle the bridge hands whole to the sequential
    path: before the encode (``world``) or by it (``all-host``: every
    head is a partial-admission one, the host's)."""
    if how == "world":
        if loop.eng.oracle is not None:
            monkeypatch.setattr(loop.eng.oracle, "world_is_fast_path_safe",
                                lambda: False)
        submit(loop.eng, "late2")
    else:
        for i in range(QUEUES):
            submit(loop.eng, f"partial{i}", lq=f"lq{i}", count=4,
                   priority=10, min_count=1)
    root = loop.cycle()
    monkeypatch.undo()
    return root


@pytest.mark.parametrize("how", ["world", "all-host"])
def test_the_cycle_after_one_the_bridge_sat_out(how, monkeypatch):
    device, sequential = Loop(True), Loop(False)
    for loop in (device, sequential):
        loop.cycle()
        submit(loop.eng, "late1")
        loop.cycle()
    assert len(device.launches) == 2
    out = sat_out(device, how, monkeypatch)
    sat_out(sequential, how, monkeypatch)
    assert device.eng.oracle.fallback_reasons == {how: 1}
    assert out.attrs["mode"] == "sequential"
    assert "snapshot" in trees.names(out)
    assert len(device.launches) == 2
    for loop in (sequential, device):
        submit(loop.eng, "late3")
        after = [loop.cycle(), loop.cycle()]  # the second: a quiet gap
    assert device.eng.oracle.fallback_reasons == {how: 1}
    assert len(device.launches) == 4
    for root in after:
        assert root.attrs["mode"] == "device"
        assert span_mod.phase_seconds(root)["n_launches"] == 1
    assert device.digests == sequential.digests


# -- the phase dict of every kind of cycle -----------------------------


def documented_spans() -> set:
    """The span names of the recorder's tree in obs/span.py's
    docstring (the tracer's tree below it draws its branches longer)."""
    found = set()
    for line in span_mod.__doc__.splitlines():
        m = re.search(r"[├└]─ (\S+(?: · \S+)*)", line)
        if m:
            found.update(m.group(1).split(" · "))
    return found


def lattice_cycle():
    eng = trees.make_engine()
    trees.submit(eng, "low", 600)
    trees.cycle(eng)
    trees.submit(eng, "high", 600, priority=10)
    r, root = trees.cycle(eng)
    assert r.stats.preempting == 1
    assert trees.child(root, "cycle").attrs == {
        "lattice": True, "preempt_slots": 1, "preempt_skipped": 0,
        "preempt_columns": 1}
    return eng


def sim_nomination_cycle():
    eng = trees.make_engine(flavors=2)
    trees.submit(eng, "w", 400)
    r, root = trees.cycle(eng)
    assert r.stats.admitted == 1
    assert "sim_nomination" in trees.names(trees.child(root, "cycle"))
    return eng


def hybrid_cycle():
    eng = trees.make_engine(cohorts=2)
    trees.submit(eng, "dev", 400, lq="lq0")
    trees.submit(eng, "partial", 400, lq="lq1", min_count=1)
    _, root = trees.cycle(eng)
    assert root.attrs["mode"] == "hybrid"
    return eng


def fallback_cycle():
    eng = trees.make_engine(preemption=False)
    trees.submit(eng, "partial", 400, min_count=1)
    trees.cycle(eng)
    assert eng.oracle.fallback_reasons == {"all-host": 1}
    return eng


@pytest.mark.parametrize("build", [lattice_cycle, sim_nomination_cycle,
                                   hybrid_cycle, fallback_cycle])
def test_phase_dict_adds_up_and_holds_documented_keys_only(build):
    eng = build()
    ph = eng.last_cycle_phases
    trees.assert_nested(eng.spans.last())
    trees.assert_adds_up(ph)
    spans = documented_spans()
    assert span_mod.CONTAINERS - {"schedule_once"} < spans
    assert {"host_encode", "tas_place", "sim_launch", "host_tail", "decide",
            "journal_sync"} <= spans
    assert set(ph) <= (spans | {"unattributed"} | span_mod.AGGREGATE_KEYS
                       | span_mod.COUNT_KEYS | span_mod.WINDOW_KEYS)
    assert set(span_mod.leaf_phases(ph)) <= \
        (spans - span_mod.CONTAINERS) | {"unattributed"}
