"""A group of kernels' share of its roofline: the least time the chip
could take for the operations and bytes the algorithm needs (the larger
of operations over peak FLOP/s and bytes over peak bytes/s, a chip's
share of both), over the device time the trace gives those kernels:
operations of ``kinds`` outside the scope ``not_scope`` finds, or
whatever runs under a program scope that ``scope`` finds.  Nothing to read where the trace holds no such
kernel."""


def read(ctx, cost, kinds=(), scope=None, not_scope=None):
    need = ctx["costs"].get(cost)
    ms = ctx["trace"].kind_ms_per_step(set(kinds), scope, not_scope)
    if need is None or ms <= 0:
        return None
    chips = ctx["device"]["count"]
    least = max(need["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                need["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]) / chips
    return 100.0 * least / (ms / 1e3)
