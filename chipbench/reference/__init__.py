"""The plain expanded-space reference: one module per rule, found by
the rule name in a configuration file (``workload.rule``)."""
from __future__ import annotations

import importlib


def rule_for(cfg: dict):
    """The reference rule of a configuration, built from its file."""
    name = cfg["workload"]["rule"].replace("-", "_")
    return importlib.import_module(f"chipbench.reference.{name}").from_config(
        cfg)
