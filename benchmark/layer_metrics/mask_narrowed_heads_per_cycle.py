"""Heads a cycle whose flavor mask excludes at least one flavor of their
ClusterQueue's resource groups: the window's `n_mask_narrowed_heads`
(obs/span.py COUNT_KEYS, from the attr `mask_narrowed_heads` of the
`sim_nomination` span, or of `host_encode` where no nomination runs;
oracle/engine_bridge.py _encode_cycle gathers the heads' `flavor_ok`
rows through `group_flavors`) over the window's cycles. What node
selectors, taints and tolerations decide on the device; 0 in a world
without a label or a taint. A program without the count gives no cycle
the key, and nothing is reported."""

from _counts import window_count


def reduce(trace, spans, counters):
    heads = window_count(spans, "n_mask_narrowed_heads")
    if heads is None:
        return None
    return heads / len(spans["cycles"])
