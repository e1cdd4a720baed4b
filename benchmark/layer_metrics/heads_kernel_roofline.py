"""The Pallas heads kernel's share of its roofline: the bytes head
selection needs at this (W, C) (rooflines.head_selection_bytes) over
the chip's HBM bandwidth, against the kernel's traced device time per
launch. Memory-bound."""

import rooflines


def reduce(trace, spans, counters):
    if trace is None:
        return None
    names = [k for k in trace["op_s"] if "_heads_pallas" in k]
    n = sum(trace["op_n"][k] for k in names)
    if not n:
        return None
    per_launch = sum(trace["op_s"][k] for k in names) / n
    least = rooflines.least_seconds(
        rooflines.head_selection_bytes(
            counters["buckets"]["w_pad"],
            counters["cfg"]["cluster_queues"]),
        rooflines.peaks_for(counters["device_kind"]))
    return 100.0 * least / per_launch
