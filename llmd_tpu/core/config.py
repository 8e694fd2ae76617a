"""Plugin-graph configuration system (EndpointPickerConfig equivalent).

Parity: reference docs/architecture/core/router/epp/configuration.md:1-129 — a single YAML
document declares plugin instances (nodes) and wires them into schedulingProfiles,
flowControl, saturationDetector, dataLayer, parser and featureGates. Validation rules
(configuration.md:52-56): all references resolve, instance names unique, extractor graph
acyclic. Defaulting tiers (configuration.md:150-166, 349-375): a `default` profile is
auto-created from all scorer/filter instances when none is declared, and a `max-score`
picker is auto-injected into any profile lacking one. Config is read once at startup.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import yaml


class ConfigError(ValueError):
    pass


@dataclass
class PluginSpec:
    name: str
    type: str
    params: dict[str, Any] = field(default_factory=dict)


@dataclass
class ProfilePluginRef:
    plugin_ref: str
    weight: float = 1.0


@dataclass
class SchedulingProfileSpec:
    name: str
    plugins: list[ProfilePluginRef] = field(default_factory=list)


@dataclass
class PriorityBandSpec:
    """Flow-control priority band (flow-control.md:242-254)."""

    priority: int
    name: str = ""
    max_bytes: int = 1 << 30
    max_requests: int = 10000
    fairness_policy: str = "round-robin"  # or "global-strict"
    ordering_policy: str = "fcfs"  # or "edf", "slo-deadline"
    ttl_s: float = 60.0


@dataclass
class FlowControlSpec:
    enabled: bool = False
    bands: list[PriorityBandSpec] = field(default_factory=list)
    saturation_detector: str = "utilization-detector"


@dataclass
class FrameworkConfig:
    plugins: list[PluginSpec] = field(default_factory=list)
    scheduling_profiles: list[SchedulingProfileSpec] = field(default_factory=list)
    profile_handler: str = "single-profile"
    flow_control: FlowControlSpec = field(default_factory=FlowControlSpec)
    parser: str = "openai-parser"
    feature_gates: dict[str, bool] = field(default_factory=dict)
    data_sources: list[PluginSpec] = field(default_factory=list)
    raw: dict[str, Any] = field(default_factory=dict)

    def plugin(self, name: str) -> PluginSpec:
        for p in self.plugins:
            if p.name == name:
                return p
        raise ConfigError(f"unknown plugin ref {name!r}")

    @classmethod
    def from_yaml(cls, text: str, known_types: Optional[set[str]] = None) -> "FrameworkConfig":
        doc = yaml.safe_load(text) or {}
        return cls.from_dict(doc, known_types)

    @classmethod
    def from_dict(cls, doc: dict[str, Any], known_types: Optional[set[str]] = None) -> "FrameworkConfig":
        cfg = cls(raw=doc)
        for p in doc.get("plugins", []) or []:
            if "type" not in p:
                raise ConfigError(f"plugin missing type: {p}")
            cfg.plugins.append(
                PluginSpec(name=p.get("name", p["type"]), type=p["type"],
                           params=p.get("params", {}) or {})
            )
        for prof in doc.get("schedulingProfiles", []) or []:
            refs = [
                ProfilePluginRef(plugin_ref=r["pluginRef"], weight=float(r.get("weight", 1.0)))
                for r in prof.get("plugins", []) or []
            ]
            cfg.scheduling_profiles.append(
                SchedulingProfileSpec(name=prof.get("name", "default"), plugins=refs)
            )
        cfg.profile_handler = doc.get("profileHandler", "single-profile")
        cfg.parser = doc.get("parser", "openai-parser")
        cfg.feature_gates = dict(doc.get("featureGates", {}) or {})
        fc = doc.get("flowControl", {}) or {}
        cfg.flow_control = FlowControlSpec(
            enabled=bool(fc.get("enabled", cfg.feature_gates.get("flowControl", False))),
            saturation_detector=fc.get("saturationDetector", "utilization-detector"),
            bands=[
                PriorityBandSpec(
                    priority=int(b["priority"]), name=b.get("name", str(b["priority"])),
                    max_bytes=int(b.get("maxBytes", 1 << 30)),
                    max_requests=int(b.get("maxRequests", 10000)),
                    fairness_policy=b.get("fairnessPolicy", "round-robin"),
                    ordering_policy=b.get("orderingPolicy", "fcfs"),
                    ttl_s=float(b.get("ttl", 60.0)),
                )
                for b in fc.get("bands", []) or []
            ],
        )
        for s in (doc.get("dataLayer") or {}).get("sources") or []:
            cfg.data_sources.append(
                PluginSpec(name=s.get("name", s["type"]), type=s["type"],
                           params=s.get("params", {}) or {})
            )
        cfg._validate(known_types)
        cfg._apply_defaults()
        return cfg

    def _validate(self, known_types: Optional[set[str]]) -> None:
        names = [p.name for p in self.plugins]
        dupes = {n for n in names if names.count(n) > 1}
        if dupes:
            raise ConfigError(f"duplicate plugin names: {sorted(dupes)}")
        if known_types is not None:
            for p in self.plugins + self.data_sources:
                if p.type not in known_types:
                    raise ConfigError(f"unknown plugin type {p.type!r} (plugin {p.name!r})")
        nameset = set(names)
        for prof in self.scheduling_profiles:
            for ref in prof.plugins:
                if ref.plugin_ref not in nameset:
                    raise ConfigError(
                        f"profile {prof.name!r} references unknown plugin {ref.plugin_ref!r}"
                    )
        profs = [p.name for p in self.scheduling_profiles]
        if len(profs) != len(set(profs)):
            raise ConfigError("duplicate scheduling profile names")
        bands = [b.priority for b in self.flow_control.bands]
        if len(bands) != len(set(bands)):
            raise ConfigError("duplicate flow-control band priorities")

    def _apply_defaults(self) -> None:
        # Auto 'default' profile over every declared plugin (configuration.md:150-166).
        if not self.scheduling_profiles:
            self.scheduling_profiles.append(
                SchedulingProfileSpec(
                    name="default",
                    plugins=[ProfilePluginRef(plugin_ref=p.name) for p in self.plugins],
                )
            )
        # Auto max-score picker injection (scheduling.md:104-108).
        picker_types = {"max-score-picker", "random-picker", "weighted-random-picker"}
        by_name = {p.name: p for p in self.plugins}
        for prof in self.scheduling_profiles:
            has_picker = any(
                by_name.get(r.plugin_ref) and by_name[r.plugin_ref].type in picker_types
                for r in prof.plugins
            )
            if not has_picker:
                if "max-score-picker" not in by_name:
                    spec = PluginSpec(name="max-score-picker", type="max-score-picker")
                    self.plugins.append(spec)
                    by_name[spec.name] = spec
                prof.plugins.append(ProfilePluginRef(plugin_ref="max-score-picker"))
