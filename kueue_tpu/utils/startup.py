"""Process start-up shared by every entry point: which device a
measurement may run on, and where JAX's persistent compilation cache
lives (chip_smoke.py, bench.py, profile_apply.py, serve, the oracle
service, tests/conftest.py).

The cache directory must be placeable from outside and otherwise fixed —
a directory that moves is an empty cache:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    here sets a directory in code;
  * unset: ``<checkout>/.jax_cache`` (git-ignored). On the CPU backend
    only, a per-CPU subdirectory: XLA:CPU AOT entries embed the compiling
    machine's feature set and loading them on a CPU without those
    features can SIGILL the process — a checkout copied between machines
    must not be able to poison itself.

An installed package has no checkout — the path above would land in
site-packages — so the image sets the variable (Dockerfile) and deploy/
mounts a volume there.
"""

from __future__ import annotations

import hashlib
import os
import platform

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# Programs that compile faster than this are cheaper to recompile than
# to read back; everything on the decision path takes seconds.
_MIN_COMPILE_SECS = 0.2


def device_stamp() -> dict:
    """The device as JAX reports it — ``{"platform", "kind", "count"}`` —
    for stamping on every result. Starts the default backend."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def measurement_device(rehearsal: bool = False,
                       stamp: dict | None = None) -> dict:
    """The stamp of the device a measurement runs on (this process's,
    unless ``stamp`` brings that of the process that holds the chip).
    Exits non-zero unless it is a TPU: JAX handing back the CPU because
    it found no chip is a fallback, not a rehearsal. A caller that can
    rehearse (bench.py always, chip_smoke.py at ``--tiny``) says so, and
    then an explicit ``JAX_PLATFORMS=cpu`` — nothing else — gets the
    CPU, stamped as such."""
    if stamp is None:
        stamp = device_stamp()
    if stamp["platform"] != "tpu" and not (
            rehearsal and os.environ.get("JAX_PLATFORMS") == "cpu"):
        raise SystemExit(
            f"no TPU: JAX's platform here is {stamp['platform']!r}, and "
            "this run is not a CPU rehearsal asked for by name.")
    return stamp


def _cpu_fingerprint() -> str:
    fp = platform.machine()
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.startswith("flags"):
                    fp += "-" + hashlib.sha256(
                        line.encode()).hexdigest()[:10]
                    break
    except OSError:
        pass
    return fp


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Call after the process has settled its platform (it starts the
    default backend to learn whether that is the CPU)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        if jax.default_backend() == "cpu":
            path = os.path.join(path, "cpu-" + _cpu_fingerprint())
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_SECS)
    return path
