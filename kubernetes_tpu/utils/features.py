"""Feature gates + component configuration.

Capability of the reference's ``pkg/features/kube_features.go:145`` +
``apimachinery feature.Gate``: named alpha/beta features with defaults,
flipped per component via ``--feature-gates=A=true,B=false``; and the
componentconfig pattern (``pkg/apis/componentconfig``): one declarative
config object per daemon, loadable from a YAML/JSON file, overridable by
flags.

The gate registry is process-global (as the reference's is); tests use
``FeatureGates(...)`` instances or ``override`` as a context manager."""

from __future__ import annotations

import copy
import json
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field, fields
from typing import Optional

# -- the gate registry (kube_features.go) -----------------------------------

ALPHA = "ALPHA"
BETA = "BETA"
GA = "GA"

# feature -> (default, maturity); the era's gate set, mapped to what this
# framework actually implements
KNOWN_FEATURES: dict[str, tuple[bool, str]] = {
    "PodPriority": (True, BETA),  # priority admission + preemption
    "TaintBasedEvictions": (False, ALPHA),  # NoExecute taint manager path
    "PodPreset": (True, ALPHA),
    "TPUBatchScheduling": (True, BETA),  # the batch backend itself
    "PallasKernels": (True, BETA),  # fused kernel vs XLA scan
    "DynamicKindRegistration": (True, BETA),  # CRDs
    "ExperimentalCriticalPodAnnotation": (False, ALPHA),
    "DynamicKubeletConfig": (False, ALPHA),  # kubelet config from the API
}


class FeatureGates:
    def __init__(self, overrides: Optional[dict[str, bool]] = None):
        self._mu = threading.Lock()
        self._enabled = {k: v for k, (v, _) in KNOWN_FEATURES.items()}
        if overrides:
            self.set_from_map(overrides)

    def enabled(self, feature: str) -> bool:
        with self._mu:
            if feature not in self._enabled:
                raise KeyError(f"unknown feature gate {feature!r}")
            return self._enabled[feature]

    def set_from_map(self, overrides: dict[str, bool]) -> None:
        with self._mu:
            for k, v in overrides.items():
                if k not in self._enabled:
                    raise KeyError(f"unknown feature gate {k!r}")
                self._enabled[k] = bool(v)

    def set_from_string(self, spec: str) -> None:
        """--feature-gates=A=true,B=false (flag wire format)."""
        overrides = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(f"bad feature gate {part!r} (want name=bool)")
            k, v = part.split("=", 1)
            if v.lower() not in ("true", "false"):
                raise ValueError(f"bad feature gate value {part!r}")
            overrides[k.strip()] = v.lower() == "true"
        self.set_from_map(overrides)

    @contextmanager
    def override(self, feature: str, value: bool):
        with self._mu:
            old = self._enabled[feature]
            self._enabled[feature] = value
        try:
            yield
        finally:
            with self._mu:
                self._enabled[feature] = old


DEFAULT_FEATURE_GATES = FeatureGates()  # the process-global gate


# -- componentconfig (pkg/apis/componentconfig) ------------------------------


@dataclass
class SchedulerConfiguration:
    """``KubeSchedulerConfiguration`` analogue."""

    scheduler_name: str = "default-scheduler"
    backend: str = "tpu"  # tpu | oracle
    batch_interval: float = 0.05
    policy_config_file: str = ""
    leader_elect: bool = False
    feature_gates: dict = field(default_factory=dict)


@dataclass
class ControllerManagerConfiguration:
    controllers: list = field(default_factory=lambda: ["*"])
    workers_per_controller: int = 2
    node_monitor_period: float = 5.0
    use_taint_based_evictions: bool = False
    leader_elect: bool = False
    feature_gates: dict = field(default_factory=dict)


@dataclass
class KubeletConfiguration:
    cpu: str = "8"
    memory: str = "16Gi"
    max_pods: int = 110
    tick: float = 1.0
    memory_pressure_fraction: float = 0.95
    feature_gates: dict = field(default_factory=dict)


def load_component_config(cls, path: str):
    """YAML/JSON file -> config dataclass; unknown keys are rejected (the
    reference's strict decoding), flag layering is the caller's job."""
    import yaml

    with open(path) as f:
        raw = yaml.safe_load(f) or {}
    known = {f.name for f in fields(cls)}
    unknown = set(raw) - known
    if unknown:
        raise ValueError(f"unknown config keys for {cls.__name__}: {sorted(unknown)}")
    return cls(**{k: copy.deepcopy(v) for k, v in raw.items()})
