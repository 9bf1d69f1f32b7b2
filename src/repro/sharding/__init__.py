"""Mesh axis rules, HLO cost analysis, and roofline estimates."""
from repro.sharding.rules import (  # noqa: F401
    axis_rules,
    constrain,
    current_mesh,
    fsdp_constrain,
    fsdp_shardings,
    logical_spec,
    make_mesh,
    param_shardings,
    tp_constrain,
)
