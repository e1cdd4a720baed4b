"""Columnar apply (controllers/colapply.py) equivalence and chaos
suite: the columnar batch-assume path must be byte-identical to the
serial escape hatch (KUEUE_TPU_COLUMNAR=0) — same chained decision
digests, same final admitted state, same tensor-row free-list order —
and the fault layer's sigkill@admission ordinal must fire at the same
admission count on the bulk path as on the per-entry path, with
crash-recovery converging to the uninterrupted control: zero lost,
zero duplicate admissions."""

import os
import signal
import subprocess
import sys
import time

import pytest

jax = pytest.importorskip("jax")

from kueue_tpu.replay.trace import (  # noqa: E402
    canonical_decisions,
    decision_digest,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARMS = {
    "serial": {"KUEUE_TPU_COLUMNAR": "0"},
    "columnar": {"KUEUE_TPU_COLUMNAR": "1"},
}


def _set_arm(monkeypatch, arm: str) -> None:
    for k, v in ARMS[arm].items():
        monkeypatch.setenv(k, v)


def _drain_digest(eng, max_cycles: int = 400):
    """Chained decision digest over a full drain — the same canonical
    stream the flight recorder checksums, so any reordered, lost or
    duplicated decision flips it."""
    digest = 0
    cycles = 0
    idle = 0
    for _ in range(max_cycles):
        r = eng.schedule_once()
        if r is None:
            idle += 1
            if idle >= 3:
                break
            continue
        idle = 0
        cycles += 1
        digest = decision_digest(canonical_decisions(r), digest)
        if r.stats.preempting:
            eng.tick(0.0)
    return digest, cycles


def _oracle_world(journal_path=None):
    """The process-kill churn world (preemption policies, priority
    churn — both fast and slow apply shapes) with the device path
    attached, so bulk_assume_batch is the apply loop under test."""
    from tests.test_process_kill_restart import build_world

    eng = build_world(journal_path)
    eng.attach_oracle()
    return eng


def _fingerprint(eng):
    from tests.test_process_kill_restart import fingerprint

    return fingerprint(eng)


class TestDigestIdentity:
    """Both COLUMNAR arms decide the same stream."""

    def _arm_digest(self, monkeypatch, arm):
        _set_arm(monkeypatch, arm)
        eng = _oracle_world()
        digest, cycles = _drain_digest(eng)
        assert cycles > 0, f"{arm}: no cycles ran"
        return digest, _fingerprint(eng)

    @pytest.mark.parametrize("arm", ["columnar"])
    def test_matches_serial(self, monkeypatch, arm):
        base = self._arm_digest(monkeypatch, "serial")
        assert self._arm_digest(monkeypatch, arm) == base, (
            f"{arm} arm diverged from the serial escape hatch")

    def test_columnar_flag_read_per_call(self, monkeypatch):
        # The escape hatch must not be baked in at import/attach time.
        from kueue_tpu.controllers import colapply

        monkeypatch.setenv("KUEUE_TPU_COLUMNAR", "0")
        assert not colapply.columnar_enabled()
        monkeypatch.setenv("KUEUE_TPU_COLUMNAR", "1")
        assert colapply.columnar_enabled()
        monkeypatch.delenv("KUEUE_TPU_COLUMNAR")
        assert colapply.columnar_enabled()


class TestChaosSeededIdentity:
    """Non-lethal fault arms (clock skew, oracle sidecar crash) decide
    identically columnar vs serial — chaos must not open a gap between
    the paths."""

    SPEC = "clock-skew@cycle:2:500,oracle-crash@cycle:4"

    def _arm(self, monkeypatch, arm):
        from kueue_tpu.replay.faults import arm_faults

        _set_arm(monkeypatch, arm)
        eng = _oracle_world()
        injector = arm_faults(eng, self.SPEC)
        digest, cycles = _drain_digest(eng)
        assert cycles > 0
        assert any(f.startswith("clock-skew@cycle:2")
                   for f in injector.fired), injector.fired
        return digest, _fingerprint(eng)

    def test_columnar_matches_serial_under_faults(self, monkeypatch):
        assert (self._arm(monkeypatch, "columnar")
                == self._arm(monkeypatch, "serial"))


class TestPsaColumns:
    def test_matches_admission_from_assignment(self, monkeypatch):
        """The flyweighted Admission halves must equal what the serial
        loop's admission_from_assignment builds."""
        from kueue_tpu.api.types import Admission
        from kueue_tpu.controllers.colapply import _psa_columns
        from kueue_tpu.workload_info import admission_from_assignment

        _set_arm(monkeypatch, "serial")
        eng = _oracle_world()
        seen = 0
        for _ in range(40):
            r = eng.schedule_once()
            if r is None:
                break
            if r.stats.preempting:
                eng.tick(0.0)
            for e in r.entries:
                if e.assignment is None or e.status.value != "assumed":
                    continue
                ref = admission_from_assignment(
                    e.info.cluster_queue, e.assignment.pod_sets)
                psas, flavor_dicts = _psa_columns(e.assignment.pod_sets)
                col = Admission(cluster_queue=e.info.cluster_queue,
                                pod_set_assignments=psas)
                assert col == ref
                # The shared PodSetResources.flavors dicts must be the
                # flavor-NAME maps the serial loop writes (a requeue
                # re-encodes rows from them), never the assignment's
                # FlavorAssignment objects.
                assert flavor_dicts == [
                    dict(psa.flavors)
                    for psa in ref.pod_set_assignments]
                seen += 1
        assert seen > 0, "no admissions to compare"


class TestRowBatchRelease:
    def test_batch_release_matches_serial_free_order(self):
        """on_remove_batch must leave the free list (which future row
        allocation consumes) and the hash registry byte-identical to
        per-key removes — the columnar release is order-sensitive
        state, not just a sum."""
        import numpy as np

        from kueue_tpu.api.types import PodSet, Workload
        from kueue_tpu.tensor.rowcache import WorkloadRowCache
        from kueue_tpu.workload_info import WorkloadInfo

        def fill(rc):
            for i in range(32):
                wl = Workload(name=f"w{i}", queue_name="lq",
                              pod_sets=(PodSet("main", 1,
                                               {"cpu": 100 + i}),))
                info = WorkloadInfo.from_workload(wl, "cq")
                rc.on_push(info, (0.0, 0, float(i), np.int64(i)))
                row = rc._row_of[info.key]
                # Simulate the encoded state: scheduling-equivalence
                # hashes shared 4 ways so the batched release exercises
                # both the refcount-drop and the id-recycle branches.
                h = ("sig", i % 8)
                rc.hash_id[row] = rc._hashes.acquire(h)
                rc._hash_tuple[row] = h

        a, b = WorkloadRowCache(), WorkloadRowCache()
        fill(a)
        fill(b)
        keys = [f"default/w{i}" for i in (3, 0, 17, 17, 9, 31, 5)]
        for k in keys:  # dup key on purpose: second remove is a no-op
            a.on_remove(k)
        b.on_remove_batch(keys)
        assert a._free == b._free
        assert a._row_of == b._row_of
        assert a._hashes._id_of == b._hashes._id_of
        assert a._hashes._count == b._hashes._count
        assert sorted(a._hashes._free) == sorted(b._hashes._free)
        assert a._hash_tuple == b._hash_tuple
        assert a._tas_req == b._tas_req
        assert a._dirty == b._dirty
        # Refill consumes the free list in the same order on both.
        for i in (3, 0, 17):
            wl = Workload(name=f"r{i}", queue_name="lq",
                          pod_sets=(PodSet("main", 1, {"cpu": 1}),))
            info = WorkloadInfo.from_workload(wl, "cq")
            a.on_push(info, (0.0, 0, 1.0, np.int64(99)))
            b.on_push(info, (0.0, 0, 1.0, np.int64(99)))
        assert a._row_of == b._row_of


class _Boom(Exception):
    pass


class TestBulkKillOrdinal:
    """sigkill@admission:N under the columnar bulk path: the ordinal
    must fire at exactly N admissions even though the fast shape never
    passes through _admit, and a reboot from the journal must converge
    to the uninterrupted control — zero lost/duplicate admissions."""

    def _arm_and_boom(self, monkeypatch, path, n):
        from kueue_tpu.replay import faults
        from kueue_tpu.replay.faults import arm_faults
        from tests.test_process_kill_restart import run_churn

        monkeypatch.setattr(faults, "_die",
                            lambda: (_ for _ in ()).throw(_Boom()))
        eng = _oracle_world(path)
        injector = arm_faults(eng, f"sigkill@admission:{n}")
        with pytest.raises(_Boom):
            for _ in run_churn(eng):
                pass
        return eng, injector

    def test_ordinal_counts_bulk_admissions(self, monkeypatch, tmp_path):
        _set_arm(monkeypatch, "columnar")
        path = str(tmp_path / "j.jsonl")
        eng, injector = self._arm_and_boom(monkeypatch, path, 12)
        assert injector.admissions == 12, (
            f"kill fired at admission {injector.admissions}, wanted 12")

    def test_recovery_converges_to_control(self, monkeypatch, tmp_path):
        from tests.test_replay_faults import (
            _control_fingerprint,
            _recover_and_fingerprint,
        )

        _set_arm(monkeypatch, "columnar")
        path = str(tmp_path / "j.jsonl")
        self._arm_and_boom(monkeypatch, path, 12)
        # The dead engine's journal handle stays open — exactly like a
        # SIGKILL. Rebuild from the path and converge sequentially.
        _set_arm(monkeypatch, "serial")
        assert _recover_and_fingerprint(path) == _control_fingerprint(), (
            "post-kill recovery diverged from the uninterrupted control")

    def test_torn_tail_recovery_converges(self, monkeypatch, tmp_path):
        """Mid-apply kill plus a torn journal tail (the flushed,
        newline-less fragment a real crash leaves): the rebuild must
        trim the fragment and still converge to the control."""
        from kueue_tpu.replay.faults import _tear_journal_tail
        from tests.test_replay_faults import (
            _control_fingerprint,
            _recover_and_fingerprint,
        )

        _set_arm(monkeypatch, "columnar")
        path = str(tmp_path / "j.jsonl")
        eng, _ = self._arm_and_boom(monkeypatch, path, 12)
        _tear_journal_tail(eng.journal)
        with open(path, "rb") as fh:
            assert not fh.read().endswith(b"\n"), "tail not torn"
        _set_arm(monkeypatch, "serial")
        assert _recover_and_fingerprint(path) == _control_fingerprint(), (
            "torn-tail recovery diverged from the uninterrupted control")


# -- real-SIGKILL child arm (slow tier): the in-process _Boom tests
# above prove the ordinal and the convergence; this proves them under
# an actual SIGKILL on the columnar path, mirroring
# tests/test_replay_faults.py for the device path.

_CHILD = r"""
import sys
sys.path.insert(0, {repo!r})
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["KUEUE_TPU_COLUMNAR"] = "1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from tests.test_process_kill_restart import build_world, run_churn
from kueue_tpu.replay.faults import arm_faults

path, spec = sys.argv[1], sys.argv[2]
eng = build_world(path)
eng.attach_oracle()
injector = arm_faults(eng, spec)
for k in run_churn(eng):
    print(f"cycle {k}", flush=True)
print("done", flush=True)
"""


@pytest.mark.slow
def test_sigkill_mid_apply_recovers_to_control(tmp_path):
    from tests.test_replay_faults import (
        _control_fingerprint,
        _recover_and_fingerprint,
    )

    path = str(tmp_path / "j.jsonl")
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD.replace("{repo!r}", repr(REPO)),
         path, "sigkill@admission:12"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    deadline = time.monotonic() + 180
    while child.poll() is None and time.monotonic() < deadline:
        time.sleep(0.2)
    assert child.poll() is not None, "child never died; fault unarmed?"
    out = child.stdout.read()
    assert child.returncode == -signal.SIGKILL, (
        f"exit={child.returncode} out={out[-400:]} "
        f"err={child.stderr.read()[-800:]}")
    assert "done" not in out, "child finished churn — kill never fired"
    assert _recover_and_fingerprint(path) == _control_fingerprint(), (
        "post-crash recovery diverged from the control")
