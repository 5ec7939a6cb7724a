from repro.sharding.rules import (
    FAMILY_RULES,
    batch_pspec,
    batch_shardings,
    cache_pspecs,
    evenly,
    opt_state_pspec,
    param_pspecs,
    param_pspecs_even,
    param_shardings,
    pspec_for_axes,
    replicated,
    rules_for,
)

__all__ = [
    "FAMILY_RULES",
    "batch_pspec",
    "batch_shardings",
    "cache_pspecs",
    "evenly",
    "opt_state_pspec",
    "param_pspecs",
    "param_pspecs_even",
    "param_shardings",
    "pspec_for_axes",
    "replicated",
    "rules_for",
]
