"""The reductions that the per-layer metric files under ``metrics/``
share. Each takes the run's context and the end-to-end rate the metric
belongs with, and returns None where it finds nothing to read: the
harness then leaves the metric out of the line.

The context (``run.Context``) carries the cell's rate, the reduced trace
(``trace.Reduced``), the units of work completed inside the traced
window and what one unit needs (model FLOPs, the CIM calls' least
time).
"""
from __future__ import annotations

import re

#: The fused CIM deploy kernels among the trace's Pallas kernels. Their
#: ``pallas_call`` carries no name of its own yet; the trace shows the
#: jitted wrapper's (``cim_matmul_pallas.3``).
CIM_KERNEL = re.compile(r"^cim_")


def _traced(ctx, rate):
    return ctx.rate == rate and ctx.trace is not None and ctx.units > 0


def idle_share(ctx, rate):
    """Per cent of the traced window in which no operation ran on the
    device."""
    if not _traced(ctx, rate):
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def cim_kernel_seconds(trace):
    return sum(trace.ops[name] for name in trace.pallas
               if CIM_KERNEL.search(name))


def cim_roofline(ctx, rate):
    """Per cent: the least time the CIM calls of the traced units need at
    the chip's peaks, over the device time of the CIM kernel events."""
    if not _traced(ctx, rate):
        return None
    kernel_s = cim_kernel_seconds(ctx.trace)
    if kernel_s <= 0:
        return None
    return 100.0 * ctx.least["seconds"] * ctx.units / kernel_s


def mfu(ctx, rate):
    """Per cent of the chip's int8 peak: the model FLOPs of the traced
    units over the traced window."""
    if not _traced(ctx, rate):
        return None
    return (100.0 * ctx.unit_flops * ctx.units / ctx.trace.window_s
            / ctx.peaks["int8_ops"])

