"""The adapter of the selective-workloads kind: sut_flavors.Program —
the several-flavors kind's engine, client's side, clocks and counters —
over a world of worldgen_selective.py: its ResourceFlavors carry the
world's nodeLabels and nodeTaints, its workloads' pod sets the node
selector and tolerations of their class's profile. Of this kind's
modules only this one imports the program.

It is held to what the kind is there to measure: every head's flavor
mask deciding its walk **on the device**. A program that fences taints
off (a ClusterQueue that names a tainted flavor is served by its host
path: minutes a cycle at a thousand queues) does not count the heads its
masks narrow: the count `n_mask_narrowed_heads` among the program's
published counts (obs/span.py COUNT_KEYS), which this cell's
`mask_narrowed_heads_per_cycle` reads. A program without it ends the run
before anything is built; and a cycle raises on the first root or cycle
the bridge hands to the host for a flavor or a mask (`flavor-unsafe`,
`sim-flavor-mask`, `head-ineligible`, the fallback `world`) rather than
serve it from there.
"""

from __future__ import annotations

import sut_flavors

HOST_ROOTS = ("flavor-unsafe", "sim-flavor-mask", "head-ineligible")


class Program(sut_flavors.Program):
    def __init__(self, world: dict, oracle: str = "local"):
        from kueue_tpu.api.types import ResourceFlavor, Taint, Toleration
        from kueue_tpu.obs.span import COUNT_KEYS

        if "n_mask_narrowed_heads" not in COUNT_KEYS:
            raise SystemExit(
                "this program does not thread the heads' flavor masks "
                "through its sim-augmented nomination (it counts no head "
                "a mask narrows: no n_mask_narrowed_heads in "
                "kueue_tpu.obs.span.COUNT_KEYS): it would serve a world "
                "of tainted flavors from its host path; the cell "
                f"{world['name']} is not run on it")
        profiles = {p["name"]: p for p in world["profiles"]}
        self.pod_sets = [
            {"node_selector": dict(profiles[c["profile"]]["node_selector"]),
             "tolerations": tuple(
                 Toleration(t["key"], t["operator"], t["value"],
                            t["effect"])
                 for t in profiles[c["profile"]]["tolerations"])}
            for c in world["classes"]]
        super().__init__(world, oracle)
        # The node pools' labels and taints, before the first cycle:
        # nothing has been encoded or decided yet.
        for fl in world["flavor_specs"]:
            self.eng.create_resource_flavor(ResourceFlavor(
                fl["name"], dict(fl["node_labels"]), tuple(
                    Taint(t["key"], t["value"], t["effect"])
                    for t in fl["node_taints"])))

    def _workload(self, name: str, ci: int, k: int, created: float):
        from kueue_tpu.api.types import PodSet, Workload

        c = self.classes[k]
        return Workload(
            name=name, uid=f"uid-{name}", queue_name=f"lq-{ci}",
            priority=c["priority"], creation_time=created,
            pod_sets=(PodSet("main", 1, dict(c["request"]),
                             **self.pod_sets[k]),))

    def cycle(self, now: float) -> dict:
        verdicts = super().cycle(now)
        oracle = getattr(self.eng, "oracle", None)
        if oracle is not None and (
                oracle.fallback_reasons.get("world")
                or any(oracle.host_root_reasons.get(r)
                       for r in HOST_ROOTS)):
            raise RuntimeError(
                "the bridge handed a root or the cycle to the host for a "
                f"flavor or a mask (host_root_reasons "
                f"{dict(oracle.host_root_reasons)}, fallback_reasons "
                f"{dict(oracle.fallback_reasons)}): this kind measures "
                "the device path only")
        return verdicts
