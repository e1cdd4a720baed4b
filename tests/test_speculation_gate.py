"""The speculation gate (oracle/engine_bridge.py, ISSUE 28): the bridge
speculates the next cycle only after a quiet gap — one in which the
state token did not move between the end of a try_cycle and the next
cycle's _take_speculation. A served loop (a client speaks before every
cycle) speculates once and never again; a drain loop speculates after
every cycle and uses every speculation, as before the gate; a gap the
bridge did not observe counts as quiet; decisions never move."""

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
from kueue_tpu.obs.span import phase_seconds  # noqa: E402
from kueue_tpu.replay.trace import (  # noqa: E402
    canonical_decisions,
    decision_digest,
)

QUEUES = 2
BACKLOG = 12  # a queue: one head a cycle, so every cycle has work


def make_engine():
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
    eng.attach_oracle()
    for n in range(BACKLOG):
        for i in range(QUEUES):
            submit(eng, f"w{i}-{n}", lq=f"lq{i}")
    return eng


def submit(eng, name, lq="lq0", **podset):
    eng.submit(Workload(name=name, queue_name=lq,
                        pod_sets=(PodSet("main", 1, {"cpu": 50}, **podset),)))


def speak(eng, gap, i):
    """What the client does between two cycles: nothing (a quiet gap),
    a submit, or a step of the engine's clock (mutated gaps)."""
    if gap == "s":
        submit(eng, f"late{i}", lq=f"lq{i % QUEUES}")
    elif gap == "c":
        eng.clock += 1.0
    else:
        assert gap == "q"


def run(gaps):
    """Cycle 0 on the backlog, then one cycle after each gap. Returns
    the engine, each cycle's root span, and the chained decision
    digests."""
    eng = make_engine()
    roots, digests, digest = [], [], 0
    for i, gap in enumerate("q" + gaps):
        speak(eng, gap, i)
        r = eng.schedule_once()
        assert r is not None and r.stats.admitted
        digest = decision_digest(canonical_decisions(r), digest)
        digests.append(digest)
        roots.append(eng.spans.last())
    assert not eng.oracle.fallback_reasons
    return eng, roots, digests


def child(span, name):
    (c,) = [c for c in span.children if c.name == name]
    return c


def took(root):
    return child(child(root, "cycle"), "take_speculation").attrs["outcome"]


def gate_closed(root):
    spec = child(root, "speculate")
    if spec.attrs.get("gate") == "closed":
        assert spec.attrs == {"gate": "closed"} and not spec.children
        return True
    assert "gate" not in spec.attrs
    assert "lattice" in spec.attrs and spec.children  # it launched
    return False


# gaps -> what each cycle's take_speculation learned, which cycles the
# gate was closed in (no speculation made), and the bridge's counts.
SCENARIOS = {
    # (a) the served loop: a client speaks before every cycle.
    "served-submit": ("sssss",
                      ["none", "discarded", "none", "none", "none", "none"],
                      [1, 2, 3, 4, 5],
                      dict(speculated=1, used=0, discarded=1, skipped=5)),
    "served-clock": ("ccccc",
                     ["none", "discarded", "none", "none", "none", "none"],
                     [1, 2, 3, 4, 5],
                     dict(speculated=1, used=0, discarded=1, skipped=5)),
    # (b) the drain loop: every gap quiet, every speculation used — what
    # the loop did before the gate (PR 27's tree: speculated 6, used 5).
    "drain": ("qqqqq",
              ["none", "used", "used", "used", "used", "used"],
              [],
              dict(speculated=6, used=5, discarded=0, skipped=0)),
    # (c) regime turns: one discard at quiet -> mutated, one cycle with
    # nothing to use at mutated -> quiet, then used again.
    "turns": ("qsqq",
              ["none", "used", "discarded", "none", "used"],
              [2],
              dict(speculated=4, used=2, discarded=1, skipped=1)),
    "turns-long": ("qscqq",
                   ["none", "used", "discarded", "none", "none", "used"],
                   [2, 3],
                   dict(speculated=4, used=2, discarded=1, skipped=2)),
}


@pytest.mark.parametrize("name", SCENARIOS)
def test_gate_follows_the_gaps(name):
    gaps, outcomes, closed, stats = SCENARIOS[name]
    eng, roots, _ = run(gaps)
    assert [took(r) for r in roots] == outcomes
    assert [i for i, r in enumerate(roots) if gate_closed(r)] == closed
    assert eng.oracle.pipeline_stats == stats
    # The tree's own counts say the same, schedule_once() by
    # schedule_once(): a skip where the gate was closed, one launch
    # there and two where a speculation was made.
    phases = [phase_seconds(r) for r in roots]
    assert [p.get("n_spec_skipped", 0) for p in phases] == \
        [int(i in closed) for i in range(len(roots))]
    assert sum(p.get("n_spec_skipped", 0) for p in phases) == \
        stats["skipped"]
    assert sum(p.get("n_spec_used", 0) for p in phases) == stats["used"]
    assert sum(p.get("n_spec_discarded", 0) for p in phases) == \
        stats["discarded"]
    fresh = sum(o != "used" for o in outcomes)
    assert sum(p["n_launches"] for p in phases) == \
        fresh + stats["speculated"]


@pytest.mark.parametrize("name", SCENARIOS)
def test_verdicts_are_those_of_the_serial_loop(name, monkeypatch):
    """(d) With KUEUE_TPU_PIPELINE=0 nothing is speculated, skipped or
    stamped, and every cycle decides the same."""
    gaps = SCENARIOS[name][0]
    _, _, gated = run(gaps)
    monkeypatch.setenv("KUEUE_TPU_PIPELINE", "0")
    eng, roots, serial = run(gaps)
    assert gated == serial
    assert eng.oracle.pipeline_stats == dict(
        speculated=0, used=0, discarded=0, skipped=0)
    assert all(not child(r, "speculate").attrs
               and not child(r, "speculate").children for r in roots)


@pytest.mark.parametrize("how", ["world", "all-host"])
def test_an_unobserved_gap_leaves_the_gate_open(how, monkeypatch):
    """(e) A cycle the bridge hands to the sequential path makes no
    stamp, so the gaps around it are not observed, and the next device
    cycle speculates — though the gate was closed before it, and the
    token moved across it."""
    eng = make_engine()
    bridge = eng.oracle
    assert eng.schedule_once() is not None       # speculates
    submit(eng, "late1")
    assert eng.schedule_once() is not None       # discards; gate closes
    assert gate_closed(eng.spans.last())
    assert bridge.pipeline_stats == dict(
        speculated=1, used=0, discarded=1, skipped=1)

    if how == "world":  # declined before take_speculation
        monkeypatch.setattr(bridge, "world_is_fast_path_safe",
                            lambda: False)
        submit(eng, "late2")
    else:  # declined after it, by the encode: every root is the host's
        for i in range(QUEUES):
            eng.submit(Workload(
                name=f"partial{i}", queue_name=f"lq{i}", priority=10,
                pod_sets=(PodSet("main", 4, {"cpu": 50}, min_count=1),)))
    eng.schedule_once()
    assert bridge.fallback_reasons == {how: 1}
    assert "speculate" not in [c.name for c in eng.spans.last().children]
    monkeypatch.undo()

    submit(eng, "late3")                         # a mutated gap, unseen
    assert eng.schedule_once() is not None
    assert not bridge.fallback_reasons.keys() - {how}
    assert took(eng.spans.last()) == "none"
    assert not gate_closed(eng.spans.last())     # open: it speculated
    assert bridge.pipeline_stats == dict(
        speculated=2, used=0, discarded=1, skipped=1)
    assert eng.schedule_once() is not None       # quiet: used
    assert took(eng.spans.last()) == "used"
