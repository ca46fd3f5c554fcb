"""A group of kernels' share of its roofline, as ``roofline`` computes
it, where some of the kernels cannot be found by their program scope:
the device time is that of whatever runs under a program scope that
``scope`` finds, and of every operation whose own name ``names`` finds.
The TPU compiler turns a ragged (grouped) dot into kernels of its own
(``ragged-dot-*``) and drops the ``op_name`` they were traced under, so
the trace files them under no scope at all.  Nothing to read where the
trace holds neither."""
import re

from lib.tracered import self_times


def read(ctx, cost, scope, names):
    need = ctx["costs"].get(cost)
    tr = ctx["trace"]
    by_scope, by_name = re.compile(scope), re.compile(names)
    ns = sum(t for ops in tr.devices.values() for op, t in self_times(ops)
             if by_scope.search(op.scope) or by_name.search(op.name))
    ms = ns / len(tr.devices) / 1e6 / tr.steps
    if need is None or ms <= 0:
        return None
    chips = ctx["device"]["count"]
    least = max(need["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                need["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]) / chips
    return 100.0 * least / (ms / 1e3)
