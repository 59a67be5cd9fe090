"""Perf observatory: analytic graph cost model, device capability
DB, roofline attribution, HBM memory planner
(docs/observability.md, docs/memory.md).

    from incubator_mxnet_tpu import perf
    report = perf.symbol_cost(sym, {"data": (32, 784)})
    rows = report.table(perf.caps_for_kind("v5e"))
    plan = perf.plan_memory(sym, {"data": (32, 784)})
"""
from .cost_model import (CostReport, DEFAULT_COST, ZERO_COST,
                         coverage_gaps, covered_ops, jit_cost,
                         symbol_cost, xla_cost)
from .device_db import (DEVICE_DB, DeviceCaps, caps_for,
                        caps_for_kind, hbm_capacity, headroom,
                        peak_flops, roofline)
from .memory_planner import (MemoryPlan, PreflightResult,
                             jaxpr_liveness, max_leaf_bytes,
                             next_divisor, plan_memory, preflight,
                             sharded_tree_bytes, symbol_liveness,
                             tree_bytes, xla_live_bytes)

__all__ = [
    "CostReport", "DEFAULT_COST", "ZERO_COST", "coverage_gaps",
    "covered_ops", "jit_cost", "symbol_cost", "xla_cost",
    "DEVICE_DB", "DeviceCaps", "caps_for", "caps_for_kind",
    "hbm_capacity", "headroom", "peak_flops", "roofline",
    "MemoryPlan", "PreflightResult", "jaxpr_liveness",
    "max_leaf_bytes", "next_divisor", "plan_memory", "preflight",
    "sharded_tree_bytes", "symbol_liveness", "tree_bytes",
    "xla_live_bytes",
]
