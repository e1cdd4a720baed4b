"""The oracle serving boundary: the decision core as a standalone
process — snapshot tensors in, verdict tensors out.

SURVEY §7's architecture stance ("the decision core as a JAX/TPU service
exposed over an AdmissionCheck-style RPC API") as it actually ships:

  * OracleServer — a standalone process (``python -m
    kueue_tpu.oracle.service --port N``) hosting the two device programs
    the hybrid cycle needs: the batched cycle step
    (oracle/batched.cycle_step) and the sim program, the classical
    preemptor over a block of simulation rows
    (ops/preempt.sim_targets). It is stateless: every
    request carries the full dense snapshot (tensor/schema.py), every
    response the verdicts — the reference's "the API server is the
    durable store; the scheduler assumes and patches"
    (scheduler.go:856-910) maps to the engine applying verdicts through
    its own assume path.
  * RemoteExecutor — the engine-side client. OracleBridge routes its
    device calls through an executor; LocalExecutor runs in-process
    (the default), RemoteExecutor ships frames over a socket
    (oracle/wire.py) and raises RemoteOracleError on transport failure,
    which the bridge turns into a sequential-path fallback for the
    cycle (the BestEffortFIFO fallback contract).

Scope: cycle_step and sim_targets cross the boundary (the hot
decision programs); the sim-augmented nomination's flavor grid and TAS
placement currently run in the engine process (they share the device
through the same jit cache when local).
"""

from __future__ import annotations

import contextlib
import json
import os
import socket
import threading
from typing import Optional

import numpy as np

from kueue_tpu.obs.span import SpanRecorder
from kueue_tpu.oracle import wire


class RemoteOracleError(Exception):
    """Transport failure talking to the oracle service; the bridge falls
    back to the sequential path for the cycle."""


_CYCLE_STATICS = ("depth", "num_resources", "num_cqs", "fair_mode",
                  "num_flavors")


def _run_cycle_step(tensors: dict, statics: dict, spans: SpanRecorder):
    """One launch of the cycle program, verdicts read back to the host.
    ``spans`` (the engine's recorder, or a served connection's own)
    gets the call split where it blocks, and the launch's window from
    the dispatch to the outputs being ready."""
    import jax
    import jax.numpy as jnp

    from kueue_tpu.oracle import batched as B

    # Device-resident tensors (the bridge's per-spec-version world
    # cache) pass through untouched: jnp.asarray on a committed jax
    # array still pays an eager weak-type strip per call — ~2ms/cycle
    # of pure dispatch at tas_large scale.
    # The spans run on from one another; the ``with`` closes whichever
    # is open when the block ends or unwinds.
    with spans.span("upload") as upload:
        kwargs = {k: v if isinstance(v, jax.Array) else jnp.asarray(v)
                  for k, v in tensors.items()}
        upload.attrs["bytes"] = sum(
            kwargs[k].nbytes for k, v in tensors.items()
            if kwargs[k] is not v)
        spans.next("dispatch")
        with spans.launch("cycle_step"):
            out = B.cycle_step(**kwargs, **statics)
            spans.next("device_wait")
            jax.block_until_ready(out)
        readback = spans.next("readback")
        host = [np.asarray(o) for o in out]
        readback.attrs["bytes"] = sum(o.nbytes for o in host)
        del out  # the device's copies are released inside the span
    return host


def _run_sim_targets(tensors: dict, statics: dict, derived=None,
                     spans: Optional[SpanRecorder] = None):
    """One launch of the sim program (ops/preempt.sim_targets) over one
    block of rows; the four per-row answers read back to the host.
    ``spans`` (the engine's recorder, inside the bridge's ``sim_launch``
    span) is told the bytes the call moved (host arrays up, answers
    back) and the seconds it spent where it blocks: attrs bytes,
    upload_s, device_wait_s, readback_s; and the launch's window, from
    the dispatch of its first program to the answers being ready
    (SpanRecorder.launch: launched_s)."""
    import jax
    import jax.numpy as jnp

    from kueue_tpu.ops import preempt as pops
    from kueue_tpu.ops import quota as qops

    clock = spans.clock if spans is not None else (lambda: 0.0)
    t0 = clock()
    t = {k: v if isinstance(v, jax.Array) else jnp.asarray(v)
         for k, v in tensors.items()}
    with (spans.launch("sim_targets") if spans is not None
          else contextlib.nullcontext()):
        if derived is None:
            derived = qops.derive_world(
                t["nominal"], t["lend_limit"], t["borrow_limit"],
                t["usage"], t["parent"], depth=statics["depth"])
        t1 = clock()
        out = pops.sim_targets(
            t["slot_need"], t["slot_pri"], t["slot_ts"], t["slot_fr"],
            t["slot_req"], t["wcq_policy"], t["reclaim_policy"],
            t["bwc_forbidden"], t["bwc_threshold"], t["cq_has_parent"],
            t["adm_cq"], t["adm_pri"], t["adm_ts"], t["adm_qrt"],
            t["adm_uid"], t["adm_ev"], t["adm_usage"], derived["usage"],
            derived["subtree_quota"], t["lend_limit"], t["borrow_limit"],
            t["nominal"], t["ancestors"], t["height"], t["local_chain"],
            t["root_nodes"], t["root_of_cq"],
            slot_cq=t["slot_cq"], adm_rank=t["adm_rank"],
            adm_by_root=t["adm_by_root"],
            depth=statics["depth"], v_cap=statics["v_cap"],
            chunk=pops.SIM_CHUNK)
        jax.block_until_ready(out)
        t2 = clock()
    host = [np.asarray(o) for o in out]
    if spans is not None:
        spans.add(
            bytes=sum(t[k].nbytes for k, v in tensors.items()
                      if t[k] is not v) + sum(o.nbytes for o in host),
            upload_s=t1 - t0, device_wait_s=t2 - t1,
            readback_s=clock() - t2)
    return host


class LocalExecutor:
    """In-process execution (the default): the engine and the oracle
    share one JAX runtime and jit cache. ``spans``: the engine's
    recorder, where cycle_step records upload / dispatch / device_wait
    / readback."""

    def __init__(self, spans: SpanRecorder):
        self.spans = spans

    def cycle_step(self, tensors: dict, statics: dict):
        return _run_cycle_step(tensors, statics, self.spans)

    def sim_targets(self, tensors: dict, statics: dict, derived=None):
        return _run_sim_targets(tensors, statics, derived=derived,
                                spans=self.spans)


class RemoteExecutor:
    """Client side of the serving boundary: one persistent connection,
    reconnect-per-error, RemoteOracleError on transport failure."""

    def __init__(self, host: str, port: int, spans: SpanRecorder,
                 timeout: float = 60.0):
        self.host = host
        self.port = port
        self.timeout = timeout
        # As LocalExecutor.spans. Over the wire a cycle_step is upload
        # (serialize), device_wait (send, the service's solve, receive)
        # and readback (deserialize); there is no dispatch to tell.
        self.spans = spans
        self._sock: Optional[socket.socket] = None
        self._lock = threading.Lock()

    def _connect(self) -> socket.socket:
        if self._sock is None:
            try:
                self._sock = socket.create_connection(
                    (self.host, self.port), timeout=self.timeout)
            except OSError as e:
                raise RemoteOracleError(str(e)) from e
        return self._sock

    def _roundtrip(self, payload: bytes) -> bytes:
        with self._lock:
            try:
                sock = self._connect()
                wire.send_msg(sock, payload)
                return wire.recv_msg(sock)
            except (OSError, ConnectionError) as e:
                if self._sock is not None:
                    try:
                        self._sock.close()
                    except OSError:
                        pass
                    self._sock = None
                raise RemoteOracleError(str(e)) from e

    @staticmethod
    def _unpack(body: bytes) -> list:
        rop, out_tensors, out_meta = wire.unpack(body)
        if rop == "error":
            raise RemoteOracleError(out_meta.get("message", "remote error"))
        n = out_meta["n"]
        return [out_tensors[f"out{i}"] for i in range(n)]

    def cycle_step(self, tensors: dict, statics: dict):
        spans = self.spans
        with spans.span("upload") as upload:
            payload = wire.pack("cycle_step", tensors, statics)
            upload.attrs["bytes"] = len(payload)
        # The launch's window, as the engine sees it: the round trip.
        with spans.span("device_wait"), spans.launch("cycle_step"):
            body = self._roundtrip(payload)
        with spans.span("readback", bytes=len(body)):
            return self._unpack(body)

    def sim_targets(self, tensors: dict, statics: dict, derived=None):
        # The service re-derives quota state server-side.
        payload = wire.pack("sim_targets", tensors, statics)
        with self.spans.launch("sim_targets"):
            body = self._roundtrip(payload)
        return self._unpack(body)

    def close(self) -> None:
        with self._lock:
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None


class OracleServer:
    """``fault_after`` (the replay/faults.py crash matrix, --fault
    crash-after:N): hard-exit the process immediately after the Nth
    compute reply is sent — the sidecar-crash-mid-serving scenario.
    The engine side must surface RemoteOracleError, fall back to the
    sequential path for the cycle, and reconnect once the sidecar is
    restarted (crash recovery with zero lost/duplicate admissions)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 fault_after: Optional[int] = None):
        self._listener = socket.create_server((host, port))
        self.address = self._listener.getsockname()
        self.fault_after = fault_after
        self._served = 0
        self._served_lock = threading.Lock()

    def _count_and_maybe_crash(self) -> None:
        if self.fault_after is None:
            return
        with self._served_lock:
            self._served += 1
            crash = self._served >= self.fault_after
        if crash:
            import os
            # os._exit, not sys.exit: a real sidecar crash runs no
            # finalizers and leaves peers mid-read on the socket.
            os._exit(17)

    def serve_forever(self) -> None:
        while True:
            conn, _ = self._listener.accept()
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True)
            t.start()

    def _serve_conn(self, conn: socket.socket) -> None:
        # This connection's own recorder (one thread serves it): its
        # spans reach a profile of the service as kueue.* events.
        spans = SpanRecorder(retain=1)
        with conn:
            while True:
                try:
                    body = wire.recv_msg(conn)
                except (ConnectionError, OSError):
                    return
                try:
                    op, tensors, meta = wire.unpack(body)
                    if op == "ping":
                        reply = wire.pack("pong", {}, {"n": 0})
                    elif op == "cycle_step":
                        outs = _run_cycle_step(tensors, meta, spans)
                        reply = wire.pack(
                            "ok", {f"out{i}": o
                                   for i, o in enumerate(outs)},
                            {"n": len(outs)})
                    elif op == "sim_targets":
                        outs = _run_sim_targets(tensors, meta)
                        reply = wire.pack(
                            "ok", {f"out{i}": o
                                   for i, o in enumerate(outs)},
                            {"n": len(outs)})
                    else:
                        reply = wire.pack("error", {},
                                          {"message": f"unknown op {op}"})
                except Exception as e:  # noqa: BLE001 — report, keep serving
                    reply = wire.pack("error", {}, {"message": repr(e)})
                try:
                    wire.send_msg(conn, reply)
                except (ConnectionError, OSError):
                    return
                if op in ("cycle_step", "sim_targets"):
                    self._count_and_maybe_crash()


def main(argv=None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description="kueue_tpu oracle service")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=7461)
    parser.add_argument("--platform", default=None,
                        help="force a JAX platform (e.g. cpu)")
    parser.add_argument(
        "--fault", default=os.environ.get("KUEUE_TPU_ORACLE_FAULT", ""),
        help="fault injection, e.g. crash-after:3 (exit hard after the "
             "3rd compute reply; replay/faults.py crash matrix)")
    args = parser.parse_args(argv)
    fault_after = None
    if args.fault:
        kind, _, n = args.fault.partition(":")
        if kind != "crash-after" or not n.isdigit():
            raise SystemExit(f"unknown --fault {args.fault!r}")
        fault_after = int(n)
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    jax.config.update("jax_enable_x64", True)
    from kueue_tpu.utils.startup import (
        configure_compile_cache,
        device_stamp,
    )
    # Start the backend now: a service that cannot have its device fails
    # here, not at the first request, and says which device it holds.
    device = device_stamp()
    configure_compile_cache()
    server = OracleServer(args.host, args.port, fault_after=fault_after)
    print(f"oracle service listening on {server.address[0]}:"
          f"{server.address[1]} device={json.dumps(device)}", flush=True)
    server.serve_forever()


if __name__ == "__main__":
    main()
