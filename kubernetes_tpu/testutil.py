"""Object builders for tests and benchmarks (reference ``test/utils``,
``plugin/pkg/scheduler/testing``)."""

from __future__ import annotations

import random
from typing import Optional

from .api import (
    Affinity,
    Container,
    ContainerPort,
    LabelSelector,
    Node,
    NodeCondition,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodAffinityTerm,
    PodSpec,
    Quantity,
    ResourceRequirements,
    Service,
    Taint,
    Toleration,
    Volume,
    WeightedPodAffinityTerm,
)


def make_node(
    name: str,
    cpu: str = "4",
    memory: str = "8Gi",
    pods: int = 110,
    labels: Optional[dict] = None,
    taints: Optional[list[Taint]] = None,
    gpu: int = 0,
    storage: str = "0",
    annotations: Optional[dict] = None,
    unschedulable: bool = False,
    conditions: Optional[list[NodeCondition]] = None,
) -> Node:
    alloc = {
        "cpu": Quantity(cpu),
        "memory": Quantity(memory),
        "pods": Quantity(pods),
    }
    if gpu:
        alloc["nvidia.com/gpu"] = Quantity(gpu)
    if storage != "0":
        alloc["ephemeral-storage"] = Quantity(storage)
    return Node(
        meta=ObjectMeta(name=name, namespace="", labels=labels or {}, annotations=annotations or {}),
        spec=NodeSpec(taints=taints or [], unschedulable=unschedulable),
        status=NodeStatus(
            capacity=dict(alloc),
            allocatable=alloc,
            conditions=conditions or [NodeCondition(type="Ready", status="True")],
        ),
    )


def make_pod(
    name: str,
    cpu: str = "0",
    memory: str = "0",
    namespace: str = "default",
    labels: Optional[dict] = None,
    node_name: str = "",
    node_selector: Optional[dict] = None,
    tolerations: Optional[list[Toleration]] = None,
    host_ports: Optional[list[int]] = None,
    gpu: int = 0,
    affinity=None,
    volumes=None,
    owner_refs=None,
    containers: Optional[list[Container]] = None,
) -> Pod:
    if containers is None:
        requests = {}
        if cpu != "0":
            requests["cpu"] = Quantity(cpu)
        if memory != "0":
            requests["memory"] = Quantity(memory)
        if gpu:
            requests["nvidia.com/gpu"] = Quantity(gpu)
        ports = [ContainerPort(container_port=p, host_port=p) for p in host_ports or []]
        containers = [
            Container(
                name="c0",
                image="img",
                resources=ResourceRequirements(requests=requests),
                ports=ports,
            )
        ]
    return Pod(
        meta=ObjectMeta(
            name=name,
            namespace=namespace,
            labels=labels or {},
            owner_references=owner_refs or [],
        ),
        spec=PodSpec(
            containers=containers,
            node_name=node_name,
            node_selector=node_selector or {},
            tolerations=tolerations or [],
            affinity=affinity,
            volumes=volumes or [],
        ),
    )


ZONE = "failure-domain.beta.kubernetes.io/zone"


def make_nodes(n_nodes: int, rng: random.Random, workload: str):
    nodes = []
    for i in range(n_nodes):
        labels = {
            "kubernetes.io/hostname": f"node-{i:05d}",
            ZONE: f"zone-{i % 3}",
        }
        taints = []
        if workload == "mixed":
            if rng.random() < 0.3:
                labels["disk"] = rng.choice(["ssd", "hdd"])
            if rng.random() < 0.1:
                taints.append(Taint(key="dedicated", value="special", effect="NoSchedule"))
        nodes.append(
            make_node(
                f"node-{i:05d}",
                cpu=rng.choice(["8", "16", "32"]),
                memory=rng.choice(["16Gi", "32Gi", "64Gi"]),
                pods=110,
                labels=labels,
                taints=taints,
            )
        )
    return nodes


def make_services():
    return [
        Service(meta=ObjectMeta(name=app), selector={"app": app})
        for app in ("web", "api", "db")
    ]


def make_pods(n_pods: int, rng: random.Random, workload: str):
    """Pending-pod flood.  ``plain``: 4 homogeneous RC-style templates.
    ``mixed``: adds ~20% affinity-bearing pods (soft zone co-location +
    required hostname anti-affinity — the reference's own hot spot,
    predicates.go:982), ~10% disk-volume pods, node selectors, and
    toleration-bearing pods for the tainted capacity."""
    plain_templates = [
        dict(cpu="100m", memory="128Mi", labels={"app": "web"}),
        dict(cpu="250m", memory="256Mi", labels={"app": "api"}),
        dict(cpu="500m", memory="512Mi", labels={"app": "db"}),
        dict(cpu="1", memory="1Gi", labels={"app": "batch"}),
    ]
    if workload == "plain":
        return [
            make_pod(f"pod-{i:06d}", **plain_templates[i % len(plain_templates)])
            for i in range(n_pods)
        ]

    soft = Affinity(
        pod_affinity_preferred=[
            WeightedPodAffinityTerm(
                weight=10,
                term=PodAffinityTerm(
                    selector=LabelSelector.from_match_labels({"app": "web"}),
                    topology_key=ZONE,
                ),
            )
        ]
    )
    anti = Affinity(
        pod_anti_affinity_required=[
            PodAffinityTerm(
                selector=LabelSelector.from_match_labels({"app": "lonely"}),
                topology_key="kubernetes.io/hostname",
            )
        ]
    )
    pods = []
    for i in range(n_pods):
        r = rng.random()
        if r < 0.10:
            pods.append(
                make_pod(f"soft-{i:06d}", cpu="100m", memory="128Mi",
                         labels={"app": "web"}, affinity=soft)
            )
        elif r < 0.20:
            pods.append(
                make_pod(f"lonely-{i:06d}", cpu="100m", memory="128Mi",
                         labels={"app": "lonely"}, affinity=anti)
            )
        elif r < 0.30:
            pods.append(
                make_pod(
                    f"vol-{i:06d}", cpu="100m", memory="128Mi", labels={"app": "api"},
                    volumes=[Volume(name="v", disk_id=f"pd-{rng.randrange(2 * n_pods)}",
                                    disk_kind=rng.choice(["gce-pd", "aws-ebs"]))],
                )
            )
        elif r < 0.35:
            pods.append(
                make_pod(f"ssd-{i:06d}", cpu="250m", memory="256Mi",
                         labels={"app": "db"}, node_selector={"disk": "ssd"})
            )
        elif r < 0.40:
            pods.append(
                make_pod(
                    f"tol-{i:06d}", cpu="200m", memory="128Mi", labels={"app": "batch"},
                    tolerations=[Toleration(key="dedicated", operator="Exists")],
                )
            )
        else:
            pods.append(
                make_pod(f"pod-{i:06d}", **plain_templates[i % len(plain_templates)])
            )
    return pods
