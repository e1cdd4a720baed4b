#!/usr/bin/env python
"""tpu_layouts: what the chip's compiler made of a jitted function's
arrays, asked without the chip.

A source line says nothing of an array's layout on a TPU: the compiler
picks the minor axis and tiles it (u32: 8 x 128), so `u32[1024,256,2]`
with the size-2 axis minor holds 2 MB in 134 MB, and every op that
copies, scatters into or gathers from it moves the padding too. This
tool compiles a function for a *described* v5e (libtpu's compiler, no
device; nothing runs, so it gives no time) and lists, for the entry
and for every `while` body of the compiled program, each materialised
array whose tiled size exceeds its data by more than a ratio, beside
the bytes the body's ops produce a step.

Library surface:
  describe_v5e() -> SingleDeviceSharding of one described chip
  compile_for_v5e(jitted, *args, **kwargs) -> the compiled executable;
      args are ShapeDtypeStructs (or anything with .shape / .dtype)
  layout_report(hlo_text, ratio=8.0, min_bytes=MIN_BYTES) -> [Region]
  sim_targets_args(...) -> the arguments of ops/preempt.sim_targets

CLI (the sim program at a benchmark cell's shapes; ~40 s at the second
cell's, the default):
  python tools/tpu_layouts.py [--rows 1024 --resources 2 --root-nodes 201
      --running-a-root 2048 ...] [--ratio 8] [--min-bytes 1048576] [--hlo FILE]

It runs in no benchmark cell and no program path imports it.
"""

from __future__ import annotations

import argparse
import math
import os
import re
import sys
from typing import NamedTuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Under this an array is not listed as padded: every scalar is 128x its
# data, and a vector a row ([1024, n <= 128], n minor: a slot's chain
# positions, its columns) is one tile row a row, 0.5 MB whatever n.
MIN_BYTES = 1 << 20

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

# Ops whose result is no buffer of its own: views, tuples, and the
# control flow whose results alias what its computations produce.
_NOT_MATERIALISED = frozenset((
    "parameter", "get-tuple-element", "tuple", "bitcast", "constant",
    "while", "conditional", "call", "copy-done", "after-all",
    "partition-id", "replica-id", "opt-barrier",
))


class Array(NamedTuple):
    """One array of the compiled program."""
    name: str  # the instruction, or `<carry>[i]` for a loop's operand
    op: str
    shape: str  # as the compiler prints it, layout and all
    dims: tuple  # the logical shape
    minor: int | None  # the logical axis laid out minor (None: a scalar)
    data_bytes: int
    tiled_bytes: int

    @property
    def ratio(self) -> float:
        return self.tiled_bytes / max(self.data_bytes, 1)


class Region(NamedTuple):
    """The entry, or one `while` body with what it calls (conditional
    branches and calls; a nested loop's body is a region of its own)."""
    name: str
    kind: str  # "entry" | "while"
    loop: str  # the `while` instruction, as a device trace names it
    carry: tuple  # Arrays: the loop's operand tuple
    produced: tuple  # Arrays: what the region's ops materialise a step
    padded: tuple  # of carry + produced, those over the ratio

    @property
    def step_tiled_bytes(self) -> int:
        return sum(a.tiled_bytes for a in self.produced)

    @property
    def step_data_bytes(self) -> int:
        return sum(a.data_bytes for a in self.produced)


_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\](?:\{([^}]*)\})?")


def _arrays_of(type_text: str) -> list:
    """(printed, dims, minor axis, data bytes, tiled bytes) of every
    array in an instruction's result type (a tuple gives several): an
    Array's fields after its name and op."""
    out = []
    for m in _ARRAY.finditer(type_text):
        dtype, dims_s, layout = m.group(1), m.group(2), m.group(3) or ""
        if dtype not in _DTYPE_BYTES:
            continue  # token[], opaque
        dims = [int(d) for d in dims_s.split(",") if d]
        order, _, tiles = layout.partition(":")
        minor_to_major = [int(d) for d in order.split(",") if d.strip()]
        if len(minor_to_major) != len(dims):
            minor_to_major = list(range(len(dims) - 1, -1, -1))
        # The first tile pads the most-minor axes; the sub-tiles that
        # may follow it repack within a tile and add nothing.
        first = re.match(r"T\(([0-9,]+)\)", tiles)
        tile = [int(t) for t in first.group(1).split(",")] if first else []
        padded = [dims[a] for a in reversed(minor_to_major)]  # major first
        if not padded and tile:
            padded = [1]
        for i, t in enumerate(reversed(tile)):
            at = len(padded) - 1 - i
            if at >= 0:
                padded[at] = -(-padded[at] // t) * t
        size = _DTYPE_BYTES[dtype]
        out.append((m.group(0), tuple(dims),
                    minor_to_major[0] if dims else None,
                    math.prod(dims) * size, math.prod(padded) * size))
    return out


_COMPUTATION = re.compile(
    r"^(ENTRY )?%([\w.\-]+) \((.*?)\) -> .*? \{\n(.*?)^\}", re.M | re.S)
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\((.*)$", re.M)
# The computations a `call` or a `conditional` runs.
_CALLED = re.compile(
    r"\b(?:to_apply|true_computation|false_computation)=%([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")


def _parse(hlo_text: str) -> dict:
    """name -> (is_entry, [(instruction, type text, op, rest)])."""
    return {m.group(2): (bool(m.group(1)), [
        i.groups() for i in _INSTRUCTION.finditer(m.group(4))])
        for m in _COMPUTATION.finditer(hlo_text)}


def layout_report(hlo_text: str, ratio: float = 8.0,
                  min_bytes: int = MIN_BYTES) -> list:
    """The Regions of a compiled TPU program's text (`compiled.as_text()`):
    the entry first, then every `while` body in the text's order. An
    array is listed as padded where its tiled size is over ``ratio``
    times its data and at least ``min_bytes``."""
    comps = _parse(hlo_text)

    def over(arrays):
        return tuple(a for a in arrays
                     if a.tiled_bytes >= min_bytes and a.ratio > ratio)

    def produced_by(name: str, seen: set) -> list:
        """What ``name`` and the non-loop computations it calls
        materialise (a fusion's insides stay in registers and VMEM)."""
        out = []
        seen.add(name)
        for inst, type_text, op, rest in comps[name][1]:
            if op not in _NOT_MATERIALISED:
                out.extend(Array(inst, op, *a)
                           for a in _arrays_of(type_text))
            if op in ("conditional", "call"):
                for m in _CALLED.finditer(rest):
                    for callee in (m.group(1) or m.group(2)).split(","):
                        callee = callee.strip().lstrip("%")
                        if callee in comps and callee not in seen:
                            out.extend(produced_by(callee, seen))
        return out

    regions = []
    for name, (is_entry, _) in comps.items():
        if is_entry:
            made = tuple(produced_by(name, set()))
            regions.append(Region(name, "entry", "", (), made, over(made)))
    for name, (_, insts) in comps.items():
        for inst, type_text, op, rest in insts:
            if op != "while":
                continue
            body = re.search(r"\bbody=%([\w.\-]+)", rest).group(1)
            carry = tuple(Array(f"<carry>[{i}]", "while", *a)
                          for i, a in enumerate(_arrays_of(type_text)))
            made = tuple(produced_by(body, set()))
            regions.append(Region(body, "while", inst, carry, made,
                                  over(carry + made)))
    return regions


# What libtpu is told so that it describes a v5e with none attached,
# asks no metadata server and logs nowhere.
V5E_ENV = {"TPU_LOG_DIR": "disabled", "TPU_ACCELERATOR_TYPE": "v5litepod-4",
           "TPU_WORKER_HOSTNAMES": "localhost", "TPU_SKIP_MDS_QUERY": "1"}


def describe_v5e():
    """One chip of a described v5e 2x2 as a sharding. Loads libtpu (one
    process at a time may hold it): call it from a script's main or a
    test's fixture, never at import. Sets V5E_ENV where the process's
    environment does not."""
    for key, value in V5E_ENV.items():
        os.environ.setdefault(key, value)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def compile_for_v5e(jitted, *args, one_chip=None, **kwargs):
    """``jitted.lower(...).compile()`` with every array argument (in
    ``args`` and ``kwargs``; anything with .shape and .dtype) placed on
    the described chip; the other keyword arguments are the function's
    statics."""
    import jax

    one_chip = one_chip or describe_v5e()

    def place(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        return x

    return jitted.lower(*map(place, args),
                        **{k: place(v) for k, v in kwargs.items()}).compile()


def sim_targets_args(rows: int, resources: int, root_nodes: int,
                     running_a_root: int, *, queues: int = 1_000,
                     running: int = 8_192, roots: int = 5, flavors: int = 3,
                     depth: int = 4, v_cap: int = 32):
    """(args, kwargs) of ops/preempt.sim_targets as the bridge calls it
    (oracle/service._run_sim_targets), shapes only. The defaults are the
    second benchmark cell's world (fungible-3f2r-1000cq: 1,024 rows, 2
    resources, 201 nodes and 2,048 padded running workloads a root)."""
    import jax
    import numpy as np

    def a(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype))

    B, S, C, A = rows, resources, queues, running
    N, R = queues + roots, flavors * resources
    quota = a("int64", N, R)
    args = (
        a("bool", B), a("int64", B), a("float64", B),  # need, pri, ts
        a("int32", B, S), a("int64", B, S),  # slot_fr, slot_req
        a("int32", C), a("int32", C), a("bool", C), a("int64", C),
        a("bool", C),  # wcq, reclaim, bwc_forbidden, bwc_threshold, parent
        a("int32", A), a("int64", A), a("float64", A), a("float64", A),
        a("int64", A), a("bool", A), a("int64", A, R),  # the running set
        quota, quota, quota, quota, quota,  # usage .. nominal
        a("int32", N, depth), a("int32", N), a("int32", C, depth + 1),
        a("int32", roots, root_nodes), a("int32", C))
    kwargs = dict(slot_cq=a("int32", B), adm_rank=a("int64", A),
                  adm_by_root=a("int32", roots, running_a_root),
                  depth=depth, v_cap=v_cap)
    return args, kwargs


def format_report(regions: list, top: int = 12) -> str:
    lines = []
    for r in regions:
        lines.append(
            f"{r.kind} {r.loop or r.name} (body {r.name}): "
            f"{r.step_tiled_bytes / 1e6:.1f} MB produced a step for "
            f"{r.step_data_bytes / 1e6:.1f} MB of data; "
            f"{len(r.padded)} padded")
        for a in sorted(r.padded, key=lambda a: -a.tiled_bytes)[:top]:
            lines.append(f"    {a.ratio:6.1f}x {a.tiled_bytes / 1e6:9.2f} MB"
                         f"  {a.shape}  {a.op} {a.name}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, default=1_024)
    p.add_argument("--resources", type=int, default=2)
    p.add_argument("--root-nodes", type=int, default=201)
    p.add_argument("--running-a-root", type=int, default=2_048)
    p.add_argument("--queues", type=int, default=1_000)
    p.add_argument("--running", type=int, default=8_192)
    p.add_argument("--roots", type=int, default=5)
    p.add_argument("--flavors", type=int, default=3)
    p.add_argument("--ratio", type=float, default=8.0)
    p.add_argument("--min-bytes", type=int, default=MIN_BYTES)
    p.add_argument("--hlo", help="write the compiled program's text here")
    args = p.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    import jax

    jax.config.update("jax_enable_x64", True)
    # A TPU executable in the persistent cache cannot be read back
    # without a chip: keep these compiles out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    from kueue_tpu.ops import preempt as pops

    a, kw = sim_targets_args(
        args.rows, args.resources, args.root_nodes, args.running_a_root,
        queues=args.queues, running=args.running, roots=args.roots,
        flavors=args.flavors)
    compiled = compile_for_v5e(pops.sim_targets, *a, **kw)
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    mem = compiled.memory_analysis()
    print(f"temporaries {mem.temp_size_in_bytes:,} B, code "
          f"{mem.generated_code_size_in_bytes:,} B")
    regions = layout_report(text, args.ratio, args.min_bytes)
    print(format_report(regions))
    return 1 if any(r.padded for r in regions if r.kind == "while") else 0


if __name__ == "__main__":
    raise SystemExit(main())
