"""The deployable control-plane process: journal-backed engine + the
HTTP serving endpoint (metrics/visibility/dashboard/debugger) + the
scheduling loop, with the oracle in-process or as a remote sidecar.

Reference: cmd/kueue/main.go:126 (the manager main — config load,
controllers, visibility server, scheduler loop). This is the standalone
analog wired for the deploy story in deploy/ (docker-compose and k8s
manifests run this as the `engine` container with the oracle service as
a sidecar).

Environment:
  KUEUE_TPU_JOURNAL        journal path (durable store; default
                           ./kueue-journal.jsonl)
  KUEUE_TPU_ORACLE         "local" (default), "off", or "host:port" of
                           a kueue-tpu-oracle service
  KUEUE_TPU_HTTP_ADDR      bind address for the serving endpoint
                           (default 0.0.0.0:8080)
  KUEUE_TPU_AUTH_TOKEN     optional bearer token for the endpoint
  KUEUE_TPU_TICK_SECONDS   idle scheduling tick (default 0.25)
  KUEUE_TPU_RECORD         flight-recorder trace path (--record): every
                           input and every cycle's decision stream is
                           captured for deterministic replay
                           (kueuectl replay <trace>)
  KUEUE_TPU_FAULT          fault-injection spec (--fault), e.g.
                           "sigkill@admission:40" — the live-smoke side
                           of the replay/faults.py crash matrix
  KUEUE_TPU_TRACE          admission tracing (--trace): attach the
                           obs.CycleTracer — span trees at /debug/trace,
                           cycle summaries on /events, kueuectl explain
                           / trace export. Value is the span retention
                           ring size ("on"/"1"/empty mean the default)
  KUEUE_TPU_HA             "1" enables HA mode (--ha): replicas sharing
                           one journal elect a leader through a fenced
                           lease file; followers tail the journal and
                           serve reads/SSE, promotion is replay-verified
                           (kueue_tpu/ha). Related flags: --replica-id,
                           --lease, --lease-duration, --shed-rate,
                           --fanout-shards
  KUEUE_TPU_CKPT_INTERVAL  sealed-checkpoint cadence in non-idle cycles
                           (--checkpoint-interval; 0 = off). With a
                           checkpoint on disk, restart/promotion boots
                           from checkpoint + journal suffix instead of a
                           full genesis replay (store/checkpoint.py)
  KUEUE_TPU_CKPT_KEEP      checkpoints retained (--checkpoint-keep)
  KUEUE_TPU_SEGMENT_RECORDS / KUEUE_TPU_SEGMENT_BYTES
                           journal segment-rotation thresholds
                           (--segment-records / --segment-bytes; 0 =
                           off). Sealed segments older than the oldest
                           live checkpoint are reclaimed by retention
  KUEUE_TPU_MIN_FREE_BYTES disk budget floor (--min-free-bytes; 0 =
                           off): journal appends are refused below this
                           much free space — read-only degraded mode,
                           submits shed with 503 + Retry-After, the
                           scheduling loop parks, and the budget
                           re-arms automatically when space recovers
                           (store/diskguard.py)
  KUEUE_TPU_WATCHDOG_DEADLINE / KUEUE_TPU_WATCHDOG_HANG
                           cycle watchdog thresholds in seconds
                           (--watchdog-deadline / --watchdog-hang;
                           0/0 = watchdog off): overrun and hung-cycle
                           detection with stack capture and
                           breaker-style demotion (obs/watchdog.py)
  KUEUE_TPU_READ_REPLICA   "1" runs this process as a READ replica
                           (--read-replica): no admission cycles, no
                           writable journal handle — tail --journal,
                           serve staleness-stamped /read/* + SSE from
                           the rebuilt read model (kueue_tpu/readplane)
  KUEUE_TPU_FEDERATE       cell spec "name[@zone]=URL,..." (--federate):
                           run this process as a FEDERATION DISPATCHER
                           instead of an engine — no local engine; POST
                           /workloads routes to member cells with a
                           durable route journal (--journal), per-cell
                           breakers, whole-cell drain and zombie
                           fencing (kueue_tpu/federation)
"""

from __future__ import annotations

import os
import signal
import time


def _attach_overload(eng, args) -> None:
    """Overload-survival toolchain: cycle watchdog (when enabled by
    the flags) + the degradation ladder (always — it idles at rung 0
    until a trigger fires). Call BEFORE arming any fault plan: the
    hang fault relies on the watchdog's pre-cycle hook stamping the
    cycle start first."""
    if args.watchdog_deadline > 0 or args.watchdog_hang > 0:
        from kueue_tpu.obs.watchdog import attach_watchdog
        deadline = args.watchdog_deadline or args.watchdog_hang / 5.0
        hang = args.watchdog_hang or deadline * 5.0
        attach_watchdog(eng, deadline_s=deadline, hang_after_s=hang)
    if args.shed_rate > 0 and getattr(eng, "shedder", None) is None:
        # Plain (non-HA) serving gets the same admission front door the
        # HA replica wires in _main_ha: SLO-coupled token bucket on the
        # POST /workloads path. The ladder below squeezes it further.
        from kueue_tpu.ha.shedder import AdmissionShedder
        from kueue_tpu.obs.slo import attach_slo
        if getattr(eng, "slo", None) is None:
            attach_slo(eng)
        eng.shedder = AdmissionShedder(
            rate=args.shed_rate, slo=eng.slo, metrics=eng.registry,
            hub=getattr(eng, "fanout", None))
    from kueue_tpu.ha.ladder import attach_ladder
    attach_ladder(eng)


def attach_oracle(eng, spec: str) -> None:
    """``--oracle``: "local" (in-process), "off", or the "host:port" of
    an oracle service. With an oracle the process compiles device
    programs, so the persistent compile cache goes on — a restarted
    server then recompiles no bucket — once the platform is settled,
    which for a remote oracle happens inside attach_oracle."""
    if spec == "off":
        return
    if spec == "local":
        eng.attach_oracle()
    else:
        host, _, port = spec.rpartition(":")
        eng.attach_oracle(remote_address=(host or "127.0.0.1", int(port)))
    from kueue_tpu.utils.startup import configure_compile_cache
    configure_compile_cache()


def main(argv=None) -> None:
    import argparse

    parser = argparse.ArgumentParser(
        description="kueue_tpu control plane (engine + serving endpoint)")
    parser.add_argument("--journal",
                        default=os.environ.get("KUEUE_TPU_JOURNAL",
                                               "kueue-journal.jsonl"))
    parser.add_argument("--oracle",
                        default=os.environ.get("KUEUE_TPU_ORACLE", "local"))
    parser.add_argument("--http",
                        default=os.environ.get("KUEUE_TPU_HTTP_ADDR",
                                               "0.0.0.0:8080"))
    parser.add_argument("--tick", type=float,
                        default=float(os.environ.get(
                            "KUEUE_TPU_TICK_SECONDS", "0.25")))
    parser.add_argument("--record",
                        default=os.environ.get("KUEUE_TPU_RECORD"))
    parser.add_argument("--fault",
                        default=os.environ.get("KUEUE_TPU_FAULT"))
    parser.add_argument("--trace", nargs="?", const="on",
                        default=os.environ.get("KUEUE_TPU_TRACE"))
    parser.add_argument("--ha", action="store_true",
                        default=os.environ.get("KUEUE_TPU_HA") == "1")
    parser.add_argument("--read-replica", action="store_true",
                        default=os.environ.get(
                            "KUEUE_TPU_READ_REPLICA") == "1",
                        help="run as a stateless READ replica"
                             " (kueue_tpu/readplane): boot from sealed"
                             " checkpoints + journal suffix tail of"
                             " --journal, serve staleness-stamped"
                             " /read/* queries and SSE from the local"
                             " read model, never write, never lead")
    parser.add_argument("--federate",
                        default=os.environ.get("KUEUE_TPU_FEDERATE"),
                        help="run as a federation dispatcher over cells"
                             ' "name[@zone]=URL,..." (no local engine)')
    parser.add_argument("--replica-id",
                        default=os.environ.get("KUEUE_TPU_REPLICA_ID"))
    parser.add_argument("--lease",
                        default=os.environ.get("KUEUE_TPU_LEASE"))
    parser.add_argument("--lease-duration", type=float,
                        default=float(os.environ.get(
                            "KUEUE_TPU_LEASE_DURATION", "5.0")))
    parser.add_argument("--shed-rate", type=float,
                        default=float(os.environ.get(
                            "KUEUE_TPU_SHED_RATE", "0")))
    parser.add_argument("--fanout-shards", type=int,
                        default=int(os.environ.get(
                            "KUEUE_TPU_FANOUT_SHARDS", "4")))
    parser.add_argument("--checkpoint-interval", type=int,
                        default=int(os.environ.get(
                            "KUEUE_TPU_CKPT_INTERVAL", "0")),
                        help="write a sealed checkpoint every N non-idle"
                             " cycles (0 = off); restart then boots from"
                             " checkpoint + journal suffix")
    parser.add_argument("--checkpoint-keep", type=int,
                        default=int(os.environ.get(
                            "KUEUE_TPU_CKPT_KEEP", "2")),
                        help="how many sealed checkpoints to retain")
    parser.add_argument("--segment-records", type=int,
                        default=int(os.environ.get(
                            "KUEUE_TPU_SEGMENT_RECORDS", "0")),
                        help="roll the journal into a sealed segment"
                             " every N records (0 = off)")
    parser.add_argument("--segment-bytes", type=int,
                        default=int(os.environ.get(
                            "KUEUE_TPU_SEGMENT_BYTES", "0")),
                        help="roll the journal into a sealed segment"
                             " past N bytes (0 = off)")
    parser.add_argument("--min-free-bytes", type=int,
                        default=int(os.environ.get(
                            "KUEUE_TPU_MIN_FREE_BYTES", "0")),
                        help="disk budget floor: refuse journal appends"
                             " (read-only degraded mode, submits shed"
                             " 503) when the filesystem's free space"
                             " drops below N bytes; re-arms"
                             " automatically (0 = off)")
    parser.add_argument("--watchdog-deadline", type=float,
                        default=float(os.environ.get(
                            "KUEUE_TPU_WATCHDOG_DEADLINE", "0")),
                        help="cycle watchdog deadline in seconds:"
                             " cycles slower than this count as"
                             " overruns and feed the watchdog breaker"
                             " (0 = watchdog off unless"
                             " --watchdog-hang is set)")
    parser.add_argument("--watchdog-hang", type=float,
                        default=float(os.environ.get(
                            "KUEUE_TPU_WATCHDOG_HANG", "0")),
                        help="hung-cycle threshold in seconds: an"
                             " in-flight cycle older than this gets"
                             " its stacks captured and the breaker"
                             " fed mid-cycle (0 = default 5x deadline"
                             " when the watchdog is on)")
    args = parser.parse_args(argv)

    from kueue_tpu.store.journal import rebuild_engine
    from kueue_tpu.visibility.http_server import ServingEndpoint

    if args.federate:
        _main_federation(args)
        return
    if args.read_replica:
        _main_read_replica(args)
        return
    if args.ha:
        _main_ha(args)
        return

    # rebuild_engine re-attaches the journal for continued writes and
    # (when a sealed checkpoint exists) boots from checkpoint + suffix
    # instead of a full genesis replay — the bounded-time restart.
    eng = rebuild_engine(
        args.journal,
        journal_kwargs={"rotate_records": args.segment_records,
                        "rotate_bytes": args.segment_bytes,
                        "min_free_bytes": args.min_free_bytes})
    if args.checkpoint_interval > 0:
        from kueue_tpu.store.checkpoint import Checkpointer
        Checkpointer(eng, interval=args.checkpoint_interval,
                     keep=args.checkpoint_keep,
                     min_free_bytes=args.min_free_bytes)
    attach_oracle(eng, args.oracle)
    _attach_overload(eng, args)

    recorder = None
    if args.record:
        # Flight recorder: bootstrap frames replay the journal-rebuilt
        # world, then every input and cycle is captured — the trace is
        # a self-contained regression test (kueuectl replay <trace>).
        from kueue_tpu.replay.recorder import FlightRecorder
        recorder = FlightRecorder(eng, args.record, bootstrap=True,
                                  label=f"serve:{args.journal}")
    if args.fault:
        from kueue_tpu.replay.faults import arm_faults
        arm_faults(eng, args.fault)
    if args.trace:
        # Admission tracing: passive span trees over every cycle
        # (obs.CycleTracer). The flag value doubles as the retention
        # ring size; "on"/"true"/"1" keep the default.
        retain = (int(args.trace) if args.trace.isdigit()
                  and int(args.trace) > 1 else 64)
        eng.attach_tracer(retain=retain)

    host, _, port = args.http.rpartition(":")
    endpoint = ServingEndpoint(
        eng, host=host or "0.0.0.0", port=int(port),
        auth_token=os.environ.get("KUEUE_TPU_AUTH_TOKEN"))
    endpoint.start()
    print(f"kueue-tpu engine serving on {host or '0.0.0.0'}:"
          f"{endpoint.port} (journal={args.journal}, "
          f"oracle={args.oracle})", flush=True)

    stop = {"flag": False}

    def _stop(*_a):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)

    # The wait.UntilWithBackoff loop (scheduler.go:207): schedule while
    # fruitful, idle-tick otherwise; engine time advances with the wall
    # clock so backoffs and timeouts fire.
    from kueue_tpu.store.journal import JournalDegraded
    while not stop["flag"]:
        t0 = time.monotonic()
        try:
            result = eng.schedule_once()
        except JournalDegraded as e:
            # A mid-cycle ENOSPC raced past the cycle-boundary
            # writable() gate: park as idle — the next cycle's gate
            # probes and re-arms when the filesystem recovers.
            print(f"journal degraded, parking: {e}", flush=True)
            result = None
        eng.tick(time.monotonic() - t0 + args.tick
                 if result is None else time.monotonic() - t0)
        if result is None:
            time.sleep(args.tick)
    if recorder is not None:
        recorder.close()
    endpoint.stop()


def _main_federation(args) -> None:
    """Federation dispatcher mode: this process owns no engine. It
    routes POST /workloads to member cells (each a serve --ha deployment
    reached over HTTP), journals every route intent to ``--journal``
    before the handoff leaves the process, probes cell health through
    per-cell circuit breakers, drains a dead cell's unconfirmed routes
    to survivors, and fences zombie rejoins. The aggregated /events SSE
    stream republishes every member cell's events tagged with the cell
    name."""
    from kueue_tpu.federation import (
        CellHandle,
        FederationDispatcher,
        HTTPCellTransport,
    )
    from kueue_tpu.federation.aggregator import EventAggregator
    from kueue_tpu.metrics.registry import MetricsRegistry
    from kueue_tpu.visibility.fanout import FanoutHub
    from kueue_tpu.visibility.http_server import ServingEndpoint

    token = os.environ.get("KUEUE_TPU_AUTH_TOKEN")
    registry = MetricsRegistry()
    cells = []
    for spec in args.federate.split(","):
        spec = spec.strip()
        if not spec:
            continue
        ident, sep, url = spec.partition("=")
        if not sep or not url:
            raise SystemExit(f"bad --federate cell spec {spec!r}"
                             ' (want "name[@zone]=URL")')
        name, _, zone = ident.partition("@")
        cells.append(CellHandle(
            name.strip(), HTTPCellTransport(url.strip(),
                                            auth_token=token),
            zone=zone.strip(), metrics=registry))
    if not cells:
        raise SystemExit('--federate requires "name[@zone]=URL,..."')

    hub = FanoutHub(shards=args.fanout_shards)
    hub.metrics = registry
    dispatcher = FederationDispatcher(
        args.journal, cells, metrics=registry, hub=hub)
    aggregator = EventAggregator(cells, hub)
    aggregator.start()

    host, _, port = args.http.rpartition(":")
    endpoint = ServingEndpoint(
        None, host=host or "0.0.0.0", port=int(port),
        auth_token=token, hub=hub, federation=dispatcher)
    endpoint.start()
    print(f"kueue-tpu federation dispatcher serving on "
          f"{host or '0.0.0.0'}:{endpoint.port} "
          f"(journal={args.journal}, cells={len(cells)})", flush=True)
    for c in cells:
        print(f"federation: cell={c.name} zone={c.zone or '-'} "
              f"url={c.transport.base_url}", flush=True)

    stop = {"flag": False}

    def _stop(*_a):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)

    while not stop["flag"]:
        dispatcher.tick(time.time())
        time.sleep(args.tick)
    aggregator.stop()
    dispatcher.close()
    endpoint.stop()
    hub.close()


def _main_read_replica(args) -> None:
    """Read-replica mode (kueue_tpu/readplane): this process never
    runs admission cycles and never holds a writable journal handle.
    It tails ``--journal`` (checkpoint base + suffix rebuilds), serves
    staleness-stamped /read/* queries and /events SSE from its local
    read model, and rejects every write. Kill the leader and this
    process keeps answering — its answers just age, and they say so."""
    from kueue_tpu.metrics.registry import MetricsRegistry
    from kueue_tpu.readplane import ReadReplica
    from kueue_tpu.visibility.fanout import FanoutHub
    from kueue_tpu.visibility.http_server import ServingEndpoint

    identity = args.replica_id or f"read-{os.getpid()}"
    registry = MetricsRegistry()
    hub = FanoutHub(shards=args.fanout_shards, metrics=registry)
    replica = ReadReplica(args.journal, replica_id=identity, hub=hub,
                          metrics=registry)

    host, _, port = args.http.rpartition(":")
    endpoint = ServingEndpoint(
        lambda: replica.engine, host=host or "0.0.0.0", port=int(port),
        auth_token=os.environ.get("KUEUE_TPU_AUTH_TOKEN"),
        hub=hub, readplane=replica)
    endpoint.start()
    print(f"kueue-tpu read replica serving on {host or '0.0.0.0'}:"
          f"{endpoint.port} (journal={args.journal})", flush=True)
    print(f"readplane: replica={identity} journal={args.journal}",
          flush=True)

    stop = {"flag": False}

    def _stop(*_a):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)

    # Tail fast, sleep only when the journal is quiet: staleness is the
    # product this process sells, so the tail tick is a fraction of the
    # scheduling tick.
    tail_tick = min(args.tick, 0.05)
    while not stop["flag"]:
        try:
            n = replica.poll()
        except FileNotFoundError:
            # The leader hasn't created the journal yet: stay up,
            # answer "no read model", retry.
            n = 0
        if n == 0:
            time.sleep(tail_tick)
    endpoint.stop()
    hub.close()


def _main_ha(args) -> None:
    """HA replica mode: this process is one of N sharing ``--journal``
    and ``--lease``. It starts as a follower (reads + SSE immediately);
    winning the lease runs the replay-verified promotion before the
    first write. The serving endpoint resolves the engine per request
    because promotion swaps it."""
    from kueue_tpu.ha.replica import HAReplica
    from kueue_tpu.ha.shedder import AdmissionShedder
    from kueue_tpu.visibility.fanout import FanoutHub
    from kueue_tpu.visibility.http_server import ServingEndpoint

    identity = args.replica_id or f"{os.uname().nodename}-{os.getpid()}"
    lease_path = args.lease or args.journal + ".lease"
    hub = FanoutHub(shards=args.fanout_shards)
    shedder = (AdmissionShedder(rate=args.shed_rate, hub=hub)
               if args.shed_rate > 0 else None)

    def on_promote(eng, replica) -> None:
        # The promoted engine gets the full leader toolchain: oracle,
        # SLO engine (drives the shedder's refill factor), tracer,
        # flight recorder, and the fault plan (which needs engine.ha —
        # already set by the promotion protocol).
        attach_oracle(eng, args.oracle)
        from kueue_tpu.obs.slo import attach_slo
        attach_slo(eng)
        if shedder is not None:
            shedder.slo = eng.slo
            shedder.metrics = eng.registry
            eng.shedder = shedder
        hub.metrics = eng.registry
        replica.tailer.metrics = eng.registry
        replica.metrics = eng.registry
        if args.trace:
            retain = (int(args.trace) if args.trace.isdigit()
                      and int(args.trace) > 1 else 64)
            eng.attach_tracer(retain=retain)
        _attach_overload(eng, args)
        if args.record:
            from kueue_tpu.replay.recorder import FlightRecorder
            replica.recorder = FlightRecorder(
                eng, args.record, bootstrap=True,
                label=f"serve-ha:{identity}")
        if args.fault:
            from kueue_tpu.replay.faults import arm_faults
            arm_faults(eng, args.fault)

    replica = HAReplica(
        args.journal, lease_path, identity,
        lease_duration=args.lease_duration,
        hub=hub, shedder=shedder, on_promote=on_promote,
        checkpoint_interval=args.checkpoint_interval,
        checkpoint_keep=args.checkpoint_keep,
        segment_rotate_records=args.segment_records or None,
        segment_rotate_bytes=args.segment_bytes or None,
        min_free_bytes=args.min_free_bytes)

    host, _, port = args.http.rpartition(":")
    endpoint = ServingEndpoint(
        replica.engine_ref, host=host or "0.0.0.0", port=int(port),
        auth_token=os.environ.get("KUEUE_TPU_AUTH_TOKEN"),
        hub=hub, replica=replica)
    endpoint.start()
    print(f"kueue-tpu engine serving on {host or '0.0.0.0'}:"
          f"{endpoint.port} (journal={args.journal}, "
          f"oracle={args.oracle})", flush=True)
    print(f"ha: replica={identity} lease={lease_path} "
          f"duration={args.lease_duration}s", flush=True)

    stop = {"flag": False}

    def _stop(*_a):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)

    announced = {"role": "follower"}
    while not stop["flag"]:
        role = replica.step(time.time())
        if role != announced["role"]:
            announced["role"] = role
            print(f"ha: role={role} epoch={replica.epoch}", flush=True)
        if role == "leader":
            # Capture once: the renewal thread can fence (and null out)
            # replica.engine at any point between ticks.
            eng = replica.engine
            if eng is None:
                continue
            t0 = time.monotonic()
            try:
                result = eng.schedule_once()
            except Exception as e:  # noqa: BLE001 — a fenced write
                from kueue_tpu.store.journal import (
                    JournalDegraded,
                    JournalFenced,
                )
                if isinstance(e, JournalFenced):
                    replica._fence(f"journal fence tripped: {e}")
                    continue
                if isinstance(e, JournalDegraded):
                    # Mid-cycle ENOSPC raced past the cycle-boundary
                    # gate: stay leader, park this tick; the gate
                    # re-arms the budget when space recovers.
                    print(f"ha: journal degraded, parking: {e}",
                          flush=True)
                    time.sleep(args.tick)
                    continue
                raise
            eng.tick(
                time.monotonic() - t0 + args.tick
                if result is None else time.monotonic() - t0)
            if result is None:
                time.sleep(args.tick)
        else:
            time.sleep(args.tick)
    recorder = getattr(replica, "recorder", None)
    if recorder is not None:
        recorder.close()
    replica.resign()
    endpoint.stop()
    hub.close()


if __name__ == "__main__":
    main()
