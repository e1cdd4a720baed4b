"""The adapter of the returning-cohort kind: sut.Program over a world
of worldgen_reclaim.py — the flat one-flavor kind's engine, client's
side, clocks and counters, all taken from sut.py — held to what this
kind is there to measure: cross-queue reclaim decided on the device.

It refuses a program whose device preemptor cannot run the kind: one
that scans only the first v_cap ordered candidates, valid or not, hands
a cohort of a thousand queues under `reclaimWithinCohort: Any` to its
host path at minutes a cycle. A preemptor that passes over invalid
candidates says how many: the count `n_preempt_skipped` among the
program's published counts (obs/span.py COUNT_KEYS), which this cell's
`preempt_candidates_skipped_per_cycle` reads. It is the behaviour that
is asked for, not how the scan gets there: a program that does not
count it ends the run before anything is built. And a cycle raises on
the first `preemption-overflow` host root rather than serve it from the
host.
"""

from __future__ import annotations

import sut


class Program(sut.Program):
    def __init__(self, world: dict, oracle: str = "local"):
        from kueue_tpu.obs.span import COUNT_KEYS

        if "n_preempt_skipped" not in COUNT_KEYS:
            raise SystemExit(
                "this program's device preemptor scans only the first "
                "v_cap ordered candidates (it counts no candidate passed "
                "over as invalid: no n_preempt_skipped in "
                "kueue_tpu.obs.span.COUNT_KEYS): under "
                "reclaimWithinCohort Any it would serve this cohort from "
                f"its host path; the cell {world['name']} is not run on "
                "it")
        super().__init__(world, oracle)

    def cycle(self, now: float) -> dict:
        verdicts = super().cycle(now)
        oracle = getattr(self.eng, "oracle", None)
        if oracle is not None and oracle.host_root_reasons.get(
                "preemption-overflow"):
            raise RuntimeError(
                "a head's targets overflowed the device preemptor's "
                "packed columns and its cohort was served from the host "
                f"(host_root_reasons {dict(oracle.host_root_reasons)}): "
                "this kind measures the device path only")
        return verdicts
