"""transfer + solve + readback (oracle/service.py _run_cycle_step): the
harness's span around executor.cycle_step, mean per call — the calls of
speculations that were thrown away count like the others."""

from _common import mean


def reduce(trace, spans, counters):
    return mean(d * 1e3 for c in spans["cycles"]
                for d in c["executor_calls_s"])
