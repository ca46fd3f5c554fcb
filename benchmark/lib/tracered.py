"""From a profiler trace to numbers: device busy and idle time, time by
kind of operation, exposed collective time, and what the host was doing
in the idle gaps.

The reduction works on a plain structure (:class:`Trace`) that a check
can build by hand; :func:`load` fills it from the ``.xplane.pb`` the JAX
profiler writes.  Every PR computes the same number in the same way from
here, and none that claims a gain can change it.
"""
import collections
import glob
import os
import re

Op = collections.namedtuple("Op", "start dur name kind scope")  # ns
Op.__new__.__defaults__ = ("",)
Span = collections.namedtuple("Span", "start dur name")        # ns

# kinds of device operation
CONV, DOT, FLASH, COLLECTIVE, OTHER = \
    "convolution", "dot", "flash", "collective", "other"
NO_SPAN = "_no_host_span_"


def merge(intervals):
    """Sorted, disjoint [start, end) from any (start, end)."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged):
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Length of merged ``a`` not covered by merged ``b``."""
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def self_times(ops):
    """[(op, self ns)]: an operation that encloses others (a loop and
    its body) keeps only the time its children do not cover.  Two that
    merely overlap, as on two lines of one device, are left whole."""
    out, stack = [], []
    for op in sorted(ops, key=lambda o: (o.start, -o.dur)):
        end = op.start + op.dur
        while stack and stack[-1][0].start + stack[-1][0].dur < end:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= op.dur
        stack.append([op, op.dur])
    out.extend(tuple(x) for x in stack)
    return [(op, max(0, ns)) for op, ns in out]


class Trace:
    """Device operations and host spans on one clock, cut to a window."""

    def __init__(self, devices, spans, window, steps):
        lo, hi = window
        self.window = window
        self.steps = steps
        self.spans = list(spans)
        self.devices = {}
        for dev, ops in devices.items():
            kept = []
            for op in ops:
                s, e = max(op.start, lo), min(op.start + op.dur, hi)
                if e > s:
                    kept.append(op._replace(start=s, dur=e - s))
            self.devices[dev] = kept

    # -- busy and idle -------------------------------------------------
    def busy_intervals(self, dev):
        return merge((o.start, o.start + o.dur) for o in self.devices[dev])

    def busy_ns(self, dev):
        return length(self.busy_intervals(dev))

    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self):
        """Seconds an operation ran, averaged over the devices."""
        return sum(self.busy_ns(d) for d in self.devices) \
            / len(self.devices) / 1e9

    def fullest(self):
        return max(self.devices, key=self.busy_ns)

    def idle_share(self):
        """1 - busy over the window, on the busiest device."""
        return 1.0 - self.busy_ns(self.fullest()) / 1e9 / self.window_s()

    def device_ms_per_step(self):
        return self.busy_ns(self.fullest()) / 1e6 / self.steps

    # -- by kind -------------------------------------------------------
    def kind_ms_per_step(self, kinds=(), scope=None, not_scope=None,
                         invert=False):
        """Self time per step in operations of ``kinds`` that are not
        under a program scope ``not_scope`` finds, or under one that
        ``scope`` finds (regular expressions); with ``invert`` in every
        other operation.  Averaged over the devices."""
        pat = re.compile(scope) if scope else None
        nope = re.compile(not_scope) if not_scope else None
        total = 0
        for ops in self.devices.values():
            for op, ns in self_times(ops):
                hit = (op.kind in kinds
                       and not (nope and nope.search(op.scope))) \
                    or bool(pat and pat.search(op.scope))
                if hit != invert:
                    total += ns
        return total / len(self.devices) / 1e6 / self.steps

    def exposed_collective_ms_per_step(self):
        """Collective time during which no other operation runs on that
        device, on the worst device.  None where there is no collective."""
        worst = None
        for ops in self.devices.values():
            coll = merge((o.start, o.start + o.dur) for o in ops
                         if o.kind == COLLECTIVE)
            if not coll:
                continue
            comp = merge((o.start, o.start + o.dur) for o in ops
                         if o.kind != COLLECTIVE)
            ms = subtract(coll, comp) / 1e6 / self.steps
            worst = ms if worst is None else max(worst, ms)
        return worst

    # -- breakdown -----------------------------------------------------
    def top_ops(self, n=10):
        """[[name, seconds a step]] of the operations that took most
        self time on the busiest device."""
        tot = collections.defaultdict(int)
        for op, ns in self_times(self.devices[self.fullest()]):
            tot[op.name] += ns
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9 / self.steps] for name, ns in rows]

    def idle_gaps(self, n=10):
        """[[host span, idle seconds]]: every gap between operations on
        the busiest device, given to the innermost host span that covers
        its middle, summed by span."""
        busy = self.busy_intervals(self.fullest())
        edges = [self.window[0]] + [x for s, e in busy for x in (s, e)] \
            + [self.window[1]]
        tot = collections.defaultdict(int)
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) // 2
            inside = [sp for sp in self.spans
                      if sp.start <= mid < sp.start + sp.dur]
            name = min(inside, key=lambda sp: sp.dur).name if inside \
                else NO_SPAN
            tot[name] += e - s
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in rows]

    def breakdown(self):
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


# ----------------------------------------------------------------------
# from the profiler's file
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_WRAPPERS = re.compile(r"^(transpose|jvp|checkpoint|remat|vmap|rematted_"
                       r"computation|custom_vjp_call|custom_jvp_call)"
                       r"\((.*)\)$")
_CALL = re.compile(r"^[\w.\-]+\(.*\)$")


def scope_of(tf_op):
    """(scope, 'fwd'|'bwd', primitive) from the ``op_name`` an operation
    was traced under, e.g. ``jit(step)/transpose(jvp(pooling0))/
    scatter-add:`` gives (pooling0, bwd, scatter-add).  The scope is the
    program's outermost ``named_scope`` (the executor's, one a node)."""
    parts = tf_op.rstrip(":").split("/")
    prim = parts[-1] if len(parts) > 1 else ""
    back = any("transpose(" in p for p in parts[:-1])
    scope = ""
    for p in parts[:-1]:
        while True:
            m = _WRAPPERS.match(p)
            if not m:
                break
            p = m.group(2)
        if p and not _CALL.match(p):
            scope = p           # the outermost: the program's own scope;
            break               # what follows is a loop's or einsum's
    return scope, "bwd" if back else "fwd", prim


def kind_of(meta):
    cat = meta.get("hlo_category", "")
    name = meta.get("name", "")
    if any(c in cat for c in COLLECTIVES):
        return COLLECTIVE
    if "convolution" in cat:
        # the TPU's compiler lowers a dot to a convolution too
        return DOT if re.search(r"\bdot\(|dot_general", name) else CONV
    if "custom-call" in cat and "tpu_custom_call" in name:
        return FLASH
    return OTHER


def op_name(meta, parsed=None):
    """``<scope>_<fwd|bwd>_<what>``: what is the traced primitive where
    the compiler kept it whole or fused it its own way, else the
    compiler's category (sort, convolution, fusion, copy...)."""
    scope, way, prim = parsed or scope_of(meta.get("tf_op", ""))
    if scope:
        cat = meta.get("hlo_category", "")
        what = prim if cat in ("", "custom fusion", "custom-call") \
            else cat.replace(" fusion", "").replace(" ", "-")
        return "%s_%s_%s" % (scope, way, what)
    base = meta.get("display_name") or meta.get("name", "?")
    return re.sub(r"[.\d]+$", "", base.lstrip("%")) or base


def load(trace_dir, steps):
    """The :class:`Trace` of the window a run traced into ``trace_dir``."""
    from . import xplane
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError("%d trace files under %s" % (len(paths),
                                                        trace_dir))
    devices, spans, window = {}, [], None
    for plane in xplane.planes(paths[0]):
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            ops = []
            for line, rows in plane.lines:
                # "Async XLA Ops" spans a transfer from its start to its
                # done; only a collective's counts as the device at work
                if line not in ("XLA Ops", "Async XLA Ops"):
                    continue
                for mid, start, dur in rows:
                    meta = plane.meta.get(mid, {})
                    kind = kind_of(meta)
                    if line != "XLA Ops" and kind != COLLECTIVE:
                        continue
                    parsed = scope_of(meta.get("tf_op", ""))
                    ops.append(Op(start, dur, op_name(meta, parsed), kind,
                                  parsed[0]))
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:CPU"):
            for _, rows in plane.lines:
                for mid, start, dur in rows:
                    name = plane.meta.get(mid, {}).get("name", "")
                    if name == WINDOW_SPAN:
                        window = (start, start + dur)
                    elif name.startswith(SPAN_PREFIX):
                        spans.append(Span(start, dur, name))
    if not devices or not any(devices.values()):
        raise RuntimeError("the trace holds no operation on a TPU")
    if window is None:
        raise RuntimeError("the trace holds no %s span" % WINDOW_SPAN)
    return Trace(devices, spans, window, steps)
