"""Serving-boundary integration: the engine drives a standalone oracle
service process over a socket (snapshot tensors in, verdict tensors
out) and applies verdicts through its own assume path
(scheduler.go:856-910 semantics); transport failure falls back to the
sequential path per cycle."""

import random
import re
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from kueue_tpu.api.types import (  # noqa: E402
    ClusterQueue,
    ClusterQueuePreemption,
    Cohort,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.controllers.engine import Engine  # noqa: E402
from kueue_tpu.oracle import wire  # noqa: E402


@pytest.fixture(scope="module")
def oracle_proc():
    """A real standalone oracle service process."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "kueue_tpu.oracle.service", "--port", "0",
         "--platform", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd="/root/repo")
    line = proc.stdout.readline()
    m = re.search(r"listening on ([\d.]+):(\d+)", line)
    assert m, f"unexpected server banner: {line!r}"
    yield proc, (m.group(1), int(m.group(2)))
    proc.kill()
    proc.wait()


def build_engine(remote=None, preemption=True, seed=0):
    rng = random.Random(seed)
    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    eng.create_cohort(Cohort("co"))
    for i in range(4):
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort="co",
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY)
            if preemption else ClusterQueuePreemption(),
            resource_groups=(ResourceGroup(
                ("cpu",), (FlavorQuotas("default",
                                        {"cpu": ResourceQuota(
                                            2000)}),)),)))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    for i in range(20):
        eng.clock += 0.5
        eng.submit(Workload(name=f"w{i}", queue_name=f"lq{rng.randrange(4)}",
                            priority=rng.choice([0, 5]),
                            pod_sets=(PodSet("main", 1,
                                             {"cpu": rng.choice(
                                                 [700, 1500])}),)))
    return eng


def drain(eng, cycles=60):
    for _ in range(cycles):
        r = eng.schedule_once()
        if r is None or (not r.assumed and not any(
                e.preemption_targets for e in r.entries)):
            break
        eng.tick(0.0)
    return {k: (w.is_admitted, w.is_finished)
            for k, w in sorted(eng.workloads.items())}


def test_ping(oracle_proc):
    _, addr = oracle_proc
    sock = socket.create_connection(addr, timeout=10)
    wire.send_msg(sock, wire.pack("ping", {}, {}))
    op, tensors, meta = wire.unpack(wire.recv_msg(sock))
    assert op == "pong"
    sock.close()


def test_engine_against_remote_oracle(oracle_proc):
    _, addr = oracle_proc
    remote = build_engine(seed=3)
    remote.attach_oracle(remote_address=addr)
    seq = build_engine(seed=3)
    state_remote = drain(remote)
    state_seq = drain(seq)
    assert remote.oracle.cycles_on_device > 0, "remote path never used"
    assert remote.oracle.fallback_reasons.get("remote-error", 0) == 0
    assert state_remote == state_seq


def test_remote_cycle_records_the_wire_spans(oracle_proc):
    """Over the wire the executor's part of the cycle's span tree is
    upload (serialize), device_wait (send, solve, receive) and readback
    (deserialize), in the engine's own tree; there is no dispatch."""
    _, addr = oracle_proc
    eng = build_engine(seed=3)
    eng.attach_oracle(remote_address=addr)
    assert eng.oracle.executor.spans is eng.spans
    eng.schedule_once()
    (cyc,) = [c for c in eng.spans.last().children if c.name == "cycle"]
    names = [c.name for c in cyc.children]
    assert names[:5] == ["host_encode", "upload", "upload", "device_wait",
                         "readback"]
    assert "dispatch" not in names
    wire_spans = {c.name: c for c in cyc.children[2:5]}
    assert wire_spans["upload"].attrs["bytes"] > 0
    assert wire_spans["readback"].attrs["bytes"] > 0
    assert {"upload", "device_wait", "readback"} <= set(
        eng.last_cycle_phases)
    # The round trip is the launch's window as the engine sees it: the
    # engine's host is not bound while it waits on the service.
    ph = eng.last_cycle_phases
    assert wire_spans["device_wait"].attrs["launched_s"] \
        == ph["device_launched"] > 0
    assert ph["host_bound"] + ph["device_launched"] == pytest.approx(
        ph["intake"] + ph["schedule_once"])


def test_remote_roundtrip_tensor_integrity(oracle_proc):
    """cycle_step over the wire equals cycle_step in-process."""
    from kueue_tpu.bench.scenario import baseline_like
    from kueue_tpu.cache.snapshot import build_snapshot
    from kueue_tpu.oracle.batched import BatchedDrainSolver
    from kueue_tpu.obs.span import SpanRecorder
    from kueue_tpu.oracle.service import LocalExecutor, RemoteExecutor

    _, addr = oracle_proc
    scen = baseline_like(n_cohorts=3, cqs_per_cohort=3, n_workloads=96,
                         sized_to_fit=False, nominal_per_cq=9000)
    snap = build_snapshot(scen.cluster_queues, scen.cohorts, scen.flavors,
                          [])
    solver = BatchedDrainSolver(snap, scen.pending_infos())
    w, wl = solver.world, solver.wls
    W = wl.num_workloads
    tensors = dict(pending=np.asarray(wl.eligible & (wl.cq >= 0)),
                   inadmissible=np.zeros(W, bool),
                   usage=w.usage,
                   **{k: np.asarray(v)
                      for k, v in solver._host_args().items()})
    statics = dict(depth=w.depth, num_resources=w.num_resources,
                   num_cqs=w.num_cqs, fair_mode=False,
                   num_flavors=max(w.num_flavors, 1))
    spans = SpanRecorder()  # an executor records into its engine's
    local = LocalExecutor(spans).cycle_step(dict(tensors), dict(statics))
    rex = RemoteExecutor(*addr, spans=spans)
    remote = rex.cycle_step(dict(tensors), dict(statics))
    rex.close()
    assert len(local) == len(remote)
    for a, b in zip(local, remote):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_server_death_falls_back_to_sequential():
    """Kill the server mid-run: every subsequent cycle falls back to the
    sequential path and the engine still drains correctly."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "kueue_tpu.oracle.service", "--port", "0",
         "--platform", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd="/root/repo")
    line = proc.stdout.readline()
    m = re.search(r"listening on ([\d.]+):(\d+)", line)
    addr = (m.group(1), int(m.group(2)))
    try:
        eng = build_engine(preemption=False, seed=5)
        eng.attach_oracle(remote_address=addr)
        r = eng.schedule_once()
        assert r is not None and eng.oracle.cycles_on_device > 0
        proc.kill()
        proc.wait()
        time.sleep(0.1)
        state = drain(eng)
        assert eng.oracle.fallback_reasons.get("remote-error", 0) > 0
        seq = build_engine(preemption=False, seed=5)
        assert drain(seq) == state
    finally:
        proc.kill()
        proc.wait()
