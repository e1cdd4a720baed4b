"""Process start-up (kueue_tpu/utils/startup.py): where the compile
cache goes, and which device a measurement may run on."""

import os

import pytest

import jax

from kueue_tpu.utils import startup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """jax.config.update, recorded instead of applied."""
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    return updates


def test_cache_dir_from_outside_is_not_set_in_code(monkeypatch, tmp_path,
                                                   config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert startup.configure_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in config_updates
    assert "jax_persistent_cache_min_compile_time_secs" in config_updates


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = startup.configure_compile_cache()
    assert config_updates["jax_compilation_cache_dir"] == path
    # conftest.py pins the CPU backend: the per-CPU subdirectory applies.
    assert os.path.dirname(path) == os.path.join(REPO, ".jax_cache")
    assert os.path.basename(path).startswith("cpu-")


TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.mark.parametrize("stamp,rehearsal,jax_platforms,accepted", [
    (TPU, False, None, True),
    (TPU, True, "cpu", True),
    (CPU, False, "cpu", False),   # chip_smoke.py without --tiny
    (CPU, True, None, False),     # JAX fell back to the CPU: not a rehearsal
    (CPU, True, "tpu,cpu", False),
    (CPU, True, "cpu", True),     # the CPU, asked for by name
])
def test_measurement_device(monkeypatch, stamp, rehearsal, jax_platforms,
                            accepted):
    if jax_platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", jax_platforms)
    if accepted:
        assert startup.measurement_device(rehearsal, stamp=stamp) == stamp
    else:
        with pytest.raises(SystemExit) as exit_:
            startup.measurement_device(rehearsal, stamp=stamp)
        assert exit_.value.code not in (0, None)


def test_device_stamp_is_what_jax_reports():
    assert startup.device_stamp() == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}


def test_bench_exits_nonzero_when_a_scenario_raises(monkeypatch, capsys):
    """The JSON line still prints — with the error and the device stamp —
    and then the process fails: a run that lost a scenario is not a
    result."""
    import json
    import sys

    monkeypatch.syspath_prepend(REPO)
    import bench

    monkeypatch.setenv("KUEUE_TPU_BENCH_REPLAY", "/nonexistent.trace")
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exit_:
        bench.main()
    assert exit_.value.code not in (0, None)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "error" in line["scenarios"]["nonexistent.trace"]
    assert line["platform_trailer"] == startup.device_stamp()


def test_engine_beside_an_oracle_service_pins_itself_to_the_cpu(
        config_updates):
    """A chip belongs to one process. With a remote oracle that process
    is the service, so the engine — which still builds its cycle inputs
    with JAX — must choose the CPU platform before any backend starts
    (chip_smoke.py --sidecar proves the layout on the chip)."""
    from kueue_tpu.controllers.engine import Engine

    eng = Engine()
    eng.attach_oracle()
    assert "jax_platforms" not in config_updates
    eng.attach_oracle(remote_address=("127.0.0.1", 1))  # connects lazily
    assert config_updates["jax_platforms"] == "cpu"


def test_engine_beside_an_oracle_service_refuses_a_started_accelerator(
        monkeypatch, config_updates):
    """Once a backend has started the pin changes nothing; the engine
    then says so instead of opening the chip the service needs."""
    from kueue_tpu.controllers.engine import Engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = Engine()
    with pytest.raises(RuntimeError, match="CPU platform"):
        eng.attach_oracle(remote_address=("127.0.0.1", 1))
    assert eng.oracle is None
