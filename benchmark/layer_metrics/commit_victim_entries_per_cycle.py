"""Entries with a victim set per cycle of the window:
`n_commit_victim_entries` over the window's cycles, counted per
schedule_once() from the `victim_entries` attr of its `verdict_decode`
span (oracle/engine_bridge.py _commit_cycle: the slots whose packed
victim ids hold any id). At most that many steps of the commit's loop
take the branch that removes victims (ops/commit.py commit_grouped); the
root's ClusterQueues less it is what the branch skips."""

from _counts import window_count


def reduce(trace, spans, counters):
    entries = window_count(spans, "n_commit_victim_entries")
    if entries is None:
        return None
    return entries / len(spans["cycles"])
