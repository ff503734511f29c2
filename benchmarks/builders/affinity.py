"""Builder `affinity`: the nodes of `benchlib/cluster.py`, and pods that
carry the inter-pod terms of their template's `pod_affinity`.

A term selects one app label, `<select_app>-<group mod group_mod>`, where
the group is the pod's own Deployment (the `{group}` of its `app` label),
in the pod's namespace. `kind` is `preferred` (podAffinity, preferred),
`preferred_anti` (podAntiAffinity, preferred) or `required` (podAffinity,
required); a preferred term has its `weight`. Spread and the required
hostname anti-affinity of `anti_affinity_hostname` are
`benchlib/cluster.build_pod`'s. A builder may import
`kubernetes_tpu.api.types` and the two functions of `benchlib/cluster.py`,
and nothing else of the program.
"""

from typing import Dict

from benchlib import cluster
from kubernetes_tpu.api import types as v1

def build_node(i: int, config: Dict) -> v1.Node:
    return cluster.build_node(i, config["nodes"])


def selected_app(term: Dict, cls: Dict) -> str:
    group = int(cls["labels"]["app"].rsplit("-", 1)[1])
    return f"{term['select_app']}-{group % term['group_mod']}"


def _term(term: Dict, cls: Dict) -> v1.PodAffinityTerm:
    return v1.PodAffinityTerm(
        label_selector=v1.LabelSelector(
            match_labels={"app": selected_app(term, cls)}),
        topology_key=term["topology_key"])


def build_pod(name: str, cls: Dict) -> v1.Pod:
    pod = cluster.build_pod(name, cls)
    terms = cls.get("pod_affinity") or []
    if not terms:
        return pod
    aff = pod.spec.affinity or v1.Affinity()
    for t in terms:
        if t["kind"] == "required":
            side = aff.pod_affinity = aff.pod_affinity or v1.PodAffinity()
            side.required_during_scheduling_ignored_during_execution = (
                side.required_during_scheduling_ignored_during_execution
                or []) + [_term(t, cls)]
            continue
        if t["kind"] == "preferred":
            side = aff.pod_affinity = aff.pod_affinity or v1.PodAffinity()
        elif t["kind"] == "preferred_anti":
            side = aff.pod_anti_affinity = (aff.pod_anti_affinity
                                            or v1.PodAntiAffinity())
        else:
            raise ValueError(f"pod_affinity kind {t['kind']!r}")
        side.preferred_during_scheduling_ignored_during_execution = (
            side.preferred_during_scheduling_ignored_during_execution
            or []) + [v1.WeightedPodAffinityTerm(
                weight=int(t["weight"]), pod_affinity_term=_term(t, cls))]
    pod.spec.affinity = aff
    return pod
