#!/usr/bin/env python
"""Regenerate the perf blocks in README.md / ARCHITECTURE.md from the
latest driver-captured bench artifact (BENCH_r*.json).

The round-4 verdict flagged doc numbers contradicting the shipped
artifact twice in a row (ask #8: stop the drift mechanically). This
script is the mechanism: every number between the ``<!-- perf:begin -->``
/ ``<!-- perf:end -->`` markers is generated from the artifact, stamped
with its filename + platform, and ``--check`` fails CI-style when the
committed blocks differ from what the artifact produces.

Usage:
  python tools/docs_perf.py            # rewrite blocks in place
  python tools/docs_perf.py --check    # exit 1 if any block is stale
  BENCH=BENCH_r04.json python tools/docs_perf.py   # explicit artifact
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "ARCHITECTURE.md")
BEGIN = "<!-- perf:begin (generated by tools/docs_perf.py; do not edit) -->"
END = "<!-- perf:end -->"


def latest_artifact() -> str:
    explicit = os.environ.get("BENCH")
    if explicit:
        return explicit
    arts = sorted(
        (f for f in os.listdir(ROOT)
         if re.fullmatch(r"BENCH_r\d+\.json", f)),
        key=lambda f: int(re.search(r"\d+", f).group()))
    if not arts:
        raise SystemExit("no BENCH_r*.json artifact found")
    return arts[-1]


def load(name: str) -> dict:
    """Load a bench result from either the raw bench JSON line or the
    driver's capture wrapper ({n, cmd, rc, tail, parsed}). The wrapper's
    ``tail`` keeps only the LAST ~2,000 chars of output — the round-5
    trailer design guarantees the ``platform_trailer`` (with its
    headline ``values`` recap) survives that truncation, so the blocks
    can regenerate from the trailer alone."""
    path = os.path.join(ROOT, name)
    with open(path) as f:
        text = f.read()
    data = json.loads(text)
    if "scenarios" in data or "platform_trailer" in data:
        return data
    if isinstance(data.get("parsed"), dict):
        return data["parsed"]
    tail = data.get("tail", "")
    # Truncated head: recover the platform_trailer object from the end,
    # plus any scenario objects that survived the cut (pre-round-5
    # trailers carried no headline recap).
    m = re.search(r'"platform_trailer":\s*(\{.*\})\s*\}\s*$', tail,
                  re.DOTALL)
    if m:
        try:
            out = {"platform_trailer": json.loads(m.group(1))}
        except json.JSONDecodeError:
            out = None
        if out is not None:
            scenarios = {}
            for sm in re.finditer(
                    r'"(\w+)":\s*\{"value":\s*([0-9.]+),\s*"unit":\s*'
                    r'"([^"]+)",\s*"vs_baseline":\s*([0-9.-]+)', tail):
                scenarios[sm.group(1)] = {
                    "value": float(sm.group(2)), "unit": sm.group(3),
                    "vs_baseline": float(sm.group(4))}
            if scenarios:
                out["scenarios"] = scenarios
                out["partial"] = True
            return out
    raise SystemExit(
        f"{name}: driver tail too truncated to recover the trailer")


def render(name: str, data: dict) -> str:
    trailer = data.get("platform_trailer", {})
    platform = trailer.get("platform", "unknown")
    probe = trailer.get("probe", "")
    partial = (" — driver tail truncated, only the surviving scenarios "
               "shown" if data.get("partial") else "")
    lines = [BEGIN,
             f"Measured numbers from `{name}` "
             f"(platform: **{platform}**, probe: {probe}){partial}; "
             "regenerate with `make docs-perf`.", ""]
    scenarios = data.get("scenarios", {})
    values = trailer.get("values", {})
    order = ["throughput_flat", "cycle_latency", "fair_cycle_latency",
             "hier_fair", "preempt_churn", "mixed_world", "tas",
             "tas_large", "tas_churn"]
    keys = order + sorted((set(scenarios) | set(values)) - set(order))
    lines.append("| scenario | value | vs baseline |")
    lines.append("|---|---|---|")
    for key in keys:
        sc = scenarios.get(key)
        if isinstance(sc, dict) and "value" in sc:
            lines.append(f"| {key} | {sc['value']} {sc['unit']} "
                         f"| {sc.get('vs_baseline', '')} |")
        elif isinstance(sc, dict) and "skipped" in sc:
            lines.append(f"| {key} | skipped ({sc['skipped']}) | |")
        elif isinstance(sc, dict) and "error" in sc:
            lines.append(f"| {key} | error | |")
        elif key in values:
            # Trailer-only recovery: "VALUE UNIT (vs X)".
            m = re.fullmatch(r"(.+) \(vs (.+)\)", str(values[key]))
            if m:
                lines.append(f"| {key} | {m.group(1)} | {m.group(2)} |")
            else:
                lines.append(f"| {key} | {values[key]} | |")
    lines.append("")
    lines.append(END)
    return "\n".join(lines)


def main() -> None:
    check = "--check" in sys.argv
    artifact = latest_artifact()
    block = render(artifact, load(artifact))
    pattern = re.compile(re.escape(BEGIN) + r".*?" + re.escape(END),
                         re.DOTALL)
    stale = []
    for doc in DOCS:
        path = os.path.join(ROOT, doc)
        with open(path) as f:
            text = f.read()
        if BEGIN not in text:
            continue
        new = pattern.sub(lambda _m: block, text)
        if new != text:
            stale.append(doc)
            if not check:
                with open(path, "w") as f:
                    f.write(new)
    if check and stale:
        print(f"stale perf blocks (run `make docs-perf`): {stale}")
        raise SystemExit(1)
    print(f"{'stale' if stale else 'current'}: {stale or DOCS} "
          f"(artifact: {artifact})")


if __name__ == "__main__":
    main()
