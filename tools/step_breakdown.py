#!/usr/bin/env python
"""Per-instruction roofline breakdown of the fused train step.

Answers the round-3 accounting question — XLA's aggregate cost model
said the step moves more bytes/s than the measured HBM peak, which
cannot be literally true — by walking the OPTIMIZED HLO entry
computation instruction by instruction:

  * HBM traffic per instruction = operand bytes + output bytes
    (fusion internals never touch HBM; parameters/constants/GTEs are
    free; this is the same accounting the streaming calibration in
    tools/roofline.py shows the cost model gets exactly right).
  * MXU flops per convolution/dot parsed from its dims.
  * roofline time estimate per instruction =
    max(bytes / hbm_peak, flops / mxu_peak).

The sum of per-instruction estimates vs the measured step time says how
coherent the accounting is; the sorted table says where the time goes
(and therefore what an optimization must attack).  Writes
``STEP_BREAKDOWN.json`` at the repo root.

Round-6 additions:

* **Symbol-layer attribution**: the executor stamps every traced
  primitive with its symbol node name (``jax.named_scope`` in
  ``executor.py::_eval_node``; XLA keeps it in the instruction metadata
  as ``op_name="jit(step)/.../jvp(<node>)/<prim>"``, with
  ``transpose(jvp(<node>))`` marking backward).  Each top row carries a
  ``layer`` field (majority vote over a fusion's inner instructions)
  and the artifact gains a ``layers`` table aggregating HBM bytes per
  symbol layer — "conv2 backward fusion: 2.6 GB" instead of
  "fusion.9".
* **Machine-readable byte budget**: ``--check`` recaptures the step for
  the current platform, diffs ``cost_model_gb_per_step`` against the
  checked-in ``STEP_BYTE_BUDGET.json`` and exits non-zero on a >3%
  regression (the nightly CI gate); ``--write-budget`` ratchets the
  budget down after an intentional byte win.  ``--artifact-dir`` drops
  the layer-attributed breakdown there for CI upload.

Round-7 addition: **input-overlap attribution**
(:func:`overlap_attribution`, CLI ``--overlap``) — the host->device
feed side of the same accounting.  The streaming pipeline's bound is
``max(decode, h2d, compute)`` per batch, not their sum; bench.py
computes these fields live (``stream_bound_img_per_sec``,
``stream_overlap_efficiency``) from this one formula so the bench line
and the tool can never disagree.
"""
import json
import os
import re
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET_PATH = os.path.join(ROOT, "STEP_BYTE_BUDGET.json")
BUDGET_TOLERANCE_PCT = 3.0

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(shape_str):
    """Total bytes of an HLO shape string (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*((?:\([^=]*?\)|[\w\[\],{}:()*]+?))\s+"
    r"([\w\-]+)\((.*)$")


def parse_computations(hlo_text):
    """All computations: {comp_name: [(name, shape_str, opcode, rest)]};
    the ENTRY computation is stored under the key "ENTRY"."""
    comps = {}
    cur = None
    for ln in hlo_text.splitlines():
        # computation header: column-0 line ending in "{" with no "=",
        # e.g. "%fused_computation.3 (p0: bf16[...]) -> bf16[...] {"
        # or   "ENTRY %main.1234 (Arg_0.1: f32[...]) -> (...) {"
        if ln and not ln[0].isspace() and ln.rstrip().endswith("{") \
                and "=" not in ln.split("(")[0]:
            first = ln.split()[0]
            if first == "ENTRY":
                cur = "ENTRY"
            else:
                cur = first.lstrip("%")
            comps[cur] = []
            continue
        if cur is None:
            continue
        if ln.startswith("}"):
            cur = None
            continue
        im = _INSTR_RE.match(ln)
        if im:
            comps[cur].append((im.group(1).lstrip("%"), im.group(2),
                               im.group(3), im.group(4)))
    return comps


# ----------------------------------------------------------------------
# symbol-layer attribution (name-scope correlation)
_OP_NAME_RE = re.compile(r'op_name="([^"]+)"')
_SCOPE_RE = re.compile(r"^(transpose\()?(?:jvp\()?([A-Za-z0-9_.\-]+)\)*$")


def layer_from_op_name(op_name):
    """Extract ``(symbol_layer, is_backward)`` from an XLA ``op_name``
    metadata path.  The executor's per-node ``jax.named_scope`` leaves
    the symbol node name as a path component — plain (``conv0``) or
    autodiff-wrapped (``jvp(conv0)`` forward, ``transpose(jvp(conv0))``
    backward); wrapper components (``jit(...)``) and the trailing
    primitive name are skipped.  Deepest scope wins."""
    layer, bwd = None, False
    parts = op_name.split("/")
    for part in parts[:-1]:
        if "(" in part and not part.startswith(("transpose(", "jvp(")):
            continue                       # jit(...)/pjit(...)/rematted
        m = _SCOPE_RE.match(part)
        if m and m.group(2):
            layer = m.group(2)
            bwd = bwd or bool(m.group(1))
    if layer is None:
        return None, "transpose(" in op_name
    return layer, bwd


def _vote_layers(comp_name, comps, votes, seen):
    """Accumulate layer votes over a computation body, recursing
    through nested fusion/call wrappers (the CPU backend wraps fused
    bodies in metadata-less ``parallel_*`` call shells)."""
    if comp_name in seen or comp_name not in comps:
        return
    seen.add(comp_name)
    for _, _, opcode, rest in comps[comp_name]:
        m = _OP_NAME_RE.search(rest)
        if m:
            layer, bwd = layer_from_op_name(m.group(1))
            if layer is not None:
                key = (layer, bwd)
                votes[key] = votes.get(key, 0) + 1
        if opcode in ("fusion", "call"):
            cm = re.search(r"(?:calls|to_apply)=%?([\w.\-]+)", rest)
            if cm:
                _vote_layers(cm.group(1), comps, votes, seen)


def _row_layer(opcode, rest, comps):
    """Layer label for one entry-computation instruction."""
    pick = None
    if opcode in ("fusion", "call"):
        cm = re.search(r"(?:calls|to_apply)=%?([\w.\-]+)", rest)
        if cm:
            votes = {}
            _vote_layers(cm.group(1), comps, votes, set())
            if votes:
                pick = max(votes.items(), key=lambda kv: kv[1])[0]
    if pick is None:
        m = _OP_NAME_RE.search(rest)
        if m:
            layer, bwd = layer_from_op_name(m.group(1))
            pick = (layer, bwd) if layer is not None else None
    if pick is None:
        return None
    layer, bwd = pick
    return layer + (" (bwd)" if bwd else "")


def layer_table(rows):
    """Aggregate HBM bytes / roofline time per symbol layer."""
    agg = {}
    for r in rows:
        key = r.get("layer") or "(unattributed)"
        e = agg.setdefault(key, {"gbytes": 0.0, "roofline_ms": 0.0,
                                 "n_instructions": 0})
        e["gbytes"] += r["gbytes"]
        e["roofline_ms"] += r["roofline_ms"]
        e["n_instructions"] += 1
    for e in agg.values():
        e["gbytes"] = round(e["gbytes"], 4)
        e["roofline_ms"] = round(e["roofline_ms"], 4)
    return dict(sorted(agg.items(), key=lambda kv: -kv[1]["gbytes"]))


def _operand_dims(rest, idx, shapes):
    """Dims list of the idx-th operand of an instruction.  Operands are
    either %name references (resolved via ``shapes``) or inline-typed;
    handle both by scanning the operand segment."""
    seg = rest.split("), ")[0]
    # inline-typed operands: "f32[2,3]{...} %p" pairs
    inline = _SHAPE_RE.findall(seg)
    refs = re.findall(r"%([\w.\-]+)", seg)
    if len(inline) > idx and len(inline) >= len(refs):
        return inline[idx][1].split(",") if inline[idx][1] else []
    if len(refs) > idx and refs[idx] in shapes:
        m = _SHAPE_RE.search(shapes[refs[idx]])
        if m:
            return m.group(2).split(",") if m.group(2) else []
    return None


def _win_vec(rest, key, ndim, default):
    m = re.search(key + r"=([\dx_]+)", rest)
    if not m:
        return [default] * ndim
    return [int(x.split("_")[0]) for x in m.group(1).split("x")]


def _win_pad(rest, ndim):
    m = re.search(r"pad=([\d_x\-]+)", rest)
    if not m:
        return [0] * ndim
    return [int(x.split("_")[0]) for x in m.group(1).split("x")]


def conv_flops(shape_str, rest, shapes=None):
    """Exact MAC count for any convolution form (forward, grad-input,
    grad-weight): 2 * prod_d(valid (output, tap) pairs in dim d)
    * out_batch * out_feature * contracted_feature.  Counting only
    IN-BOUNDS taps matters: grad-weight convs are written with
    pad ~= window-1, so most taps fall in padding and the naive
    out*window*cin formula overcounts by orders of magnitude."""
    shapes = shapes or {}
    m = _SHAPE_RE.search(shape_str)
    dl = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", rest)
    if not m or not dl:
        return 0.0
    out_dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    lhs_l, k_l, out_l = dl.group(1), dl.group(2), dl.group(3)
    lhs_dims = _operand_dims(rest, 0, shapes)
    k_dims = _operand_dims(rest, 1, shapes)
    if not lhs_dims or not k_dims or len(out_dims) != len(out_l):
        return 0.0
    lhs_dims = [int(d) for d in lhs_dims]
    k_dims = [int(d) for d in k_dims]
    nsp = len(out_l) - 2
    stride = _win_vec(rest, "stride", nsp, 1)
    pad = _win_pad(rest, nsp)
    lhs_dil = _win_vec(rest, "lhs_dilate", nsp, 1)
    rhs_dil = _win_vec(rest, "rhs_dilate", nsp, 1)
    win = _win_vec(rest, r"window={size", nsp, 1)
    pairs = 1.0
    for d in range(nsp):
        O = out_dims[out_l.index(str(d))]
        I = lhs_dims[lhs_l.index(str(d))]
        I_eff = (I - 1) * lhs_dil[d] + 1
        cnt = 0
        for o in range(O):
            base = o * stride[d] - pad[d]
            for k in range(win[d]):
                pos = base + k * rhs_dil[d]
                if 0 <= pos < I_eff and pos % lhs_dil[d] == 0:
                    cnt += 1
        pairs *= cnt
    out_b = out_dims[out_l.index("b")]
    out_f = out_dims[out_l.index("f")]
    contracted = k_dims[k_l.index("i")]      # per-group by construction
    return 2.0 * pairs * out_b * out_f * contracted


_SHAPE_SPACE_RE = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")


def hbm_shape_bytes(shape_str):
    """Bytes of the shapes in ``shape_str`` that live in default memory
    (HBM) — shapes annotated with a scoped space ``S(n)`` (the
    VMEM/SMEM staging halves of async copy/slice pairs) don't count as
    HBM traffic."""
    total = 0
    for dtype, dims, layout in _SHAPE_SPACE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        if layout and "S(" in layout:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def dot_flops(shape_str, rest, shapes=None):
    shapes = shapes or {}
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return 0.0
    out_elems = 1
    for d in (m.group(2).split(",") if m.group(2) else []):
        out_elems *= int(d)
    cm = re.search(r"rhs_contracting_dims={([\d,]+)}", rest)
    k = 1
    rdims = _operand_dims(rest, 1, shapes)
    if cm and rdims:
        for ci in cm.group(1).split(","):
            if int(ci) < len(rdims):
                k *= int(rdims[int(ci)])
    return 2.0 * out_elems * k


_NO_TRAFFIC = {"parameter", "constant", "get-tuple-element", "bitcast",
               "tuple", "after-all", "partition-id", "replica-id",
               "bitcast-convert",
               # the -start half of an async pair carries the traffic;
               # counting -done too would double every copy/async op
               "copy-done", "async-done", "all-reduce-done",
               "all-gather-done", "collective-permute-done", "send-done",
               "recv-done"}


def analyze(hlo_text, hbm_gbps, mxu_tflops):
    """Per-instruction byte/flop/roofline-time table.  Conv/dot flops
    nested inside fusions are attributed to the fusion instruction via
    its ``calls=`` computation."""
    comps = parse_computations(hlo_text)
    instrs = comps.get("ENTRY", [])
    # flops per non-entry computation (fusion bodies)
    comp_flops = {}
    for cname, cinstrs in comps.items():
        if cname == "ENTRY":
            continue
        local_shapes = {n: s for n, s, _, _ in cinstrs}
        total = 0.0
        for _, shape, opcode, rest in cinstrs:
            if opcode == "convolution":
                total += conv_flops(shape, rest, local_shapes)
            elif opcode == "dot":
                total += dot_flops(shape, rest, local_shapes)
        comp_flops[cname] = total
    shapes = {name: shape for name, shape, _, _ in instrs}
    rows = []
    for name, shape, opcode, rest in instrs:
        if opcode in _NO_TRAFFIC:
            continue
        if opcode.endswith("-start"):
            # async copy/slice pair: the start's tuple shape lists both
            # halves with memory-space annotations; count the HBM-side
            # shapes once and skip the operand scan (the operand IS one
            # of the tuple halves)
            out_b, oper_b = hbm_shape_bytes(shape), 0
        else:
            out_b = shape_bytes(shape)
            # operand traffic: %operand names referenced in the call;
            # their defining shapes (parameters live in HBM too)
            oper_b = 0
            for ref in re.findall(r"%([\w.\-]+)",
                                  rest.split(" calls=")[0]
                                  .split(" to_apply=")[0]):
                if ref in shapes:
                    oper_b += shape_bytes(shapes[ref])
            # fallback: inline-typed operands (param-less HLO styles)
            if oper_b == 0:
                oper_b = shape_bytes(rest)
        flops = 0.0
        if opcode == "convolution":
            flops = conv_flops(shape, rest, shapes)
        elif opcode == "dot":
            flops = dot_flops(shape, rest, shapes)
        elif opcode in ("fusion", "call"):
            cm = re.search(r"(?:calls|to_apply)=%?([\w.\-]+)", rest)
            if cm:
                flops = comp_flops.get(cm.group(1), 0.0)
        byte_ms = (out_b + oper_b) / (hbm_gbps * 1e9) * 1e3
        flop_ms = flops / (mxu_tflops * 1e12) * 1e3
        rows.append({"name": name, "op": opcode,
                     "gbytes": round((out_b + oper_b) / 1e9, 4),
                     "gflops": round(flops / 1e9, 2),
                     "roofline_ms": round(max(byte_ms, flop_ms), 4),
                     "bound": "mxu" if flop_ms > byte_ms else "hbm",
                     "layer": _row_layer(opcode, rest, comps)})
    rows.sort(key=lambda r: -r["roofline_ms"])
    return rows


# ----------------------------------------------------------------------
# the importable byte cost model (the autotuner's training surrogate
# and bench.py's accounting share THIS code path — the CLI used to be
# the only entry point, so the tuner would have had to shell out)
def step_cost(trainer, batch_vals, lr=0.1):
    """Compile the fused step for concrete batch values and return
    XLA's aggregate cost-model accounting::

        {"bytes", "flops", "gb_per_step", "tflop_per_step", "compiled"}

    Pure trace+compile — nothing executes.  ``compiled`` is the
    compiled step (``.as_text()`` feeds :func:`analyze`)."""
    from tools.stepcost import compile_step, cost_analysis
    comp = compile_step(trainer, batch_vals, lr=lr)
    ca = cost_analysis(comp)
    return {"bytes": ca["bytes"], "flops": ca["flops"],
            "gb_per_step": ca["bytes"] / 1e9,
            "tflop_per_step": ca["flops"] / 1e12,
            "compiled": comp}


# the knobs cost_model understands; a typo'd key is a loud error with
# a did-you-mean (the envknobs/faults discipline — a surrogate that
# silently ignored "grad_acum" would "tune" nothing)
_COST_CONFIG_DEFAULTS = {
    "model": "mlp", "batch": 16, "image": 64, "num_classes": None,
    "devices": 1, "compute_dtype": None, "dtype_policy": None,
    "remat": None, "zero": None, "grad_accum": None, "grad_dtype": None,
}


def build_cost_trainer(config=None, **overrides):
    """Build the fused Trainer + concrete batch for a cost/surrogate
    config — the ONE workload constructor :func:`cost_model` (XLA byte
    accounting) and the ``--live`` liveness view share, so the two
    never describe different programs.  Returns ``(trainer,
    batch_vals, cfg)``."""
    cfg = dict(_COST_CONFIG_DEFAULTS)
    given = dict(config or {}, **overrides)
    unknown = sorted(set(given) - set(cfg))
    if unknown:
        import difflib
        close = difflib.get_close_matches(unknown[0], sorted(cfg), n=1)
        raise ValueError(
            "unknown cost_model config key(s) %s%s — known: %s"
            % (unknown, (" (did you mean %r?)" % close[0]) if close
               else "", "/".join(sorted(cfg))))
    cfg.update(given)

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.parallel.trainer import Trainer

    batch = int(cfg["batch"])
    if cfg["model"] == "mlp":
        # THE tune workload — the same symbol serve_bench builds (and
        # the one the emitted plan is keyed to), not a lookalike: a
        # private copy here would fork the digest (and the program-
        # cache keyspace) from the timed trials
        from tools.serve_bench import build_model
        if cfg["num_classes"] not in (None, 16):
            raise ValueError("the mlp tune workload has a fixed "
                             "16-class head (num_classes=%r)"
                             % (cfg["num_classes"],))
        ncls = 16
        sym = build_model("mlp", 0)[0]
        data_shape = (batch, 64)
    elif cfg["model"] == "resnet-50":
        from mxnet_tpu import models
        ncls = int(cfg["num_classes"] or 1000)
        sym = models.get_symbol("resnet-50", num_classes=ncls,
                                layout="NHWC")
        image = int(cfg["image"])
        data_shape = (batch, image, image, 3)
    else:
        raise ValueError("unknown cost_model model %r (mlp|resnet-50)"
                         % (cfg["model"],))

    mesh = None
    n = int(cfg["devices"])
    if n > 1:
        devices = jax.devices()
        if len(devices) < n:
            raise RuntimeError(
                "cost_model config wants a %d-way data mesh but only "
                "%d local devices exist" % (n, len(devices)))
        mesh = parallel.make_mesh({"data": n}, devices[:n])

    t = Trainer(sym, mx.optimizer.create(
        "sgd", learning_rate=0.1, momentum=0.9,
        rescale_grad=1.0 / batch),
        mesh=mesh, compute_dtype=cfg["compute_dtype"],
        dtype_policy=cfg["dtype_policy"], remat=cfg["remat"],
        zero=cfg["zero"], grad_accum=cfg["grad_accum"],
        grad_dtype=cfg["grad_dtype"])
    t.bind(data_shapes={"data": data_shape},
           label_shapes={"softmax_label": (batch,)})
    mx.random.seed(3)
    t.init_params(mx.init.Xavier())
    rng = np.random.RandomState(0)
    batch_vals = {
        "data": jnp.asarray(rng.normal(0, 1, data_shape)
                            .astype(np.float32)),
        "softmax_label": jnp.asarray(
            rng.randint(0, ncls, (batch,)).astype(np.float32))}
    return t, batch_vals, cfg


def cost_model(config=None, **overrides):
    """``cost_model(config) -> {"gb_per_step", ...}`` — the importable
    training-side surrogate: build the fused Trainer for ``config``,
    compile (never execute) its step, and return the XLA cost-model
    bytes/flops.  Config knobs: ``model`` (``mlp`` — CPU-tier seconds —
    or ``resnet-50``), ``batch``, ``image`` (resnet), ``num_classes``,
    ``devices`` (data-mesh degree over the local devices; >1 enables
    the zero/grad_dtype corners), and the trainer knobs
    ``compute_dtype``/``dtype_policy``/``remat``/``zero``/
    ``grad_accum``/``grad_dtype``.

    A repeated config against a warm ``MXTPU_PROGRAM_CACHE`` re-uses
    the persisted executable, so the dominant cost — tracing — is paid
    once per distinct config, ever (docs/how_to/compiled_programs.md).
    """
    t, batch_vals, cfg = build_cost_trainer(config, **overrides)
    sc = step_cost(t, batch_vals)
    # static liveness peak (trace-only, no compile): the memory-
    # feasibility axis of the surrogate — bytes MOVED (gb_per_step)
    # says how fast a config is, bytes RESIDENT says whether it runs
    # at all (tools/mem_lint.py; autotune prunes on it)
    try:
        peak = t.predicted_peak_bytes()
    except Exception:  # noqa: BLE001 — the surrogate must not die
        peak = 0       # on an analyzer gap; 0 = "unknown, don't prune"
    return {"gb_per_step": round(sc["gb_per_step"], 6),
            "tflop_per_step": round(sc["tflop_per_step"], 6),
            "bytes": sc["bytes"], "flops": sc["flops"],
            "opt_state_bytes_per_chip": t.opt_state_bytes_per_chip(),
            "grad_comm_gb_per_step": round(
                t.grad_comm_bytes_per_step() / 1e9, 6),
            "predicted_peak_bytes": peak,
            "config": {k: v for k, v in cfg.items()}}


# the byte-attack history, kept with the artifact so a regeneration
# never drops the record the numbers rest on
_ATTACK_HISTORY = {
    "round5_attack": {
        "convert_reduce f32 BN-stat chains (r4 top: 3x0.92 + "
        "0.82 GB)":
            "ATTACKED: BatchNorm computes sum(x-c)/sum((x-c)^2) in "
            "ONE f32-accumulated pass over the bf16 activation, "
            "centered on the running mean (was jnp.var's two-pass "
            "(x-mean)^2). Result: cost-model 80.68 -> 71.03 "
            "GB/step, measured step 108.2 -> 96.6 ms, headline "
            "2486 -> 2781 img/s (~37% MFU); the convert_reduce "
            "fusions left the top table.",
        "select_and_scatter.9 (0.925 GB, MaxPool backward)":
            "analyzed, declined: 1.3% of step bytes (~1.3 ms). An "
            "equality-mask backward avoids the re-read but "
            "distributes gradient to ALL tied maxima where "
            "select-and-scatter picks the first — a semantics "
            "change for ~1 ms.  (Superseded in round 6 by the "
            "argmax-index backward, which keeps the first-tie rule.)",
        "zero-flop 1.64 GB fusions (r4 .64/.65, now .37/.38)":
            "identified via HLO dump: the stage-2/3 residual-join "
            "backward chains — bf16 activations re-read for "
            "BN/ReLU backward plus the gradient-stream adds at "
            "each residual merge (7 big operands each). "
            "Irreducible without rematerialization, and every "
            "remat policy measured SLOWER on this byte-bound step "
            "(REMAT_SWEEP.json).",
    },
    "round6_attack": {
        "zero-flop fusion.8/.9/.10 + 0.82 GB family (residual-join "
        "backward chains, ~8.6 GB)":
            "ATTACKED via backward reformulation (op/bytediet.py): "
            "BatchNorm backward is the closed form dx = x*A + dy*S + B "
            "(per-channel f32 scalars, f32-accumulated reductions) "
            "instead of autodiff's activation-sized stat-broadcast "
            "temporaries; ReLU backward re-derives its mask from the "
            "already-resident output (where(y>0, dy, 0)) instead of a "
            "saved input, deduping the residual pair.  Cost-model "
            "bytes fell 21.5% on the CPU-backend A/B at the bench "
            "shape (4.58 -> 3.60 GB/step, MXTPU_DTYPE_POLICY "
            "bytediet-vs-legacy); chip recapture pending.",
        "select_and_scatter.9 (0.925 GB, MaxPool backward)":
            "ATTACKED: forward computes value+argmax in one variadic "
            "reduce_window pass (first index wins ties — "
            "select_and_scatter's own tie rule), backward is a "
            "scatter-add of the cotangent at the saved int32 indices; "
            "no full-size activation re-read in backward.  TAKEN OUT "
            "AGAIN (PR 28): never run on a chip until PR 21, where XLA "
            "sorted the 51.4M indices and scattered them serially, 573 "
            "of the step's 698 ms; the plain reduce_window's "
            "select_and_scatter is back.",
    },
}


def capture(batch=256, image=224, measure=True, steps=40, ctx=None):
    """Compile the fused ResNet-50 train step, walk its optimized HLO,
    and (optionally) measure the real step.  Returns the breakdown
    dict (the schema of ``STEP_BREAKDOWN.json``)."""
    os.environ.setdefault("MXTPU_MODULE_FUSED", "always")
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import io, models

    sym = models.get_symbol("resnet-50", num_classes=1000, layout="NHWC")
    mod = mx.mod.Module(context=ctx if ctx is not None else mx.tpu(),
                        symbol=sym, compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", (batch, image, image, 3))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9,
                                         "rescale_grad": 1.0 / batch})
    t = mod._trainer

    from tools.stepcost import timed_module_steps
    rng = np.random.RandomState(0)
    batch_vals = {
        "data": jnp.asarray(rng.normal(
            0, 1, (batch, image, image, 3)).astype(np.float32)),
        "softmax_label": jnp.asarray(
            rng.randint(0, 1000, (batch,)).astype(np.float32))}
    sc = step_cost(t, batch_vals)
    hlo = sc["compiled"].as_text()

    roof = json.load(open(os.path.join(ROOT, "ROOFLINE.json")))
    rows = analyze(hlo, roof["hbm_gbps"], roof["bf16_matmul_tflops"])

    measured_ms = None
    if measure:
        # measure the real step for the coherence check
        data_batch = io.DataBatch(
            data=[mx.nd.NDArray(batch_vals["data"])],
            label=[mx.nd.NDArray(batch_vals["softmax_label"])], pad=0)
        metric = mx.metric.create("acc")
        elapsed, _ = timed_module_steps(mod, metric, data_batch, steps)
        measured_ms = elapsed / steps * 1e3

    total_gb = sum(r["gbytes"] for r in rows)
    total_roofline_ms = sum(r["roofline_ms"] for r in rows)
    result = {
        "model": "resnet-50 NHWC bf16 batch %d image %d fused train step"
                 % (batch, image),
        "dtype_policy": t.dtype_policy or "bytediet",
        "measured_step_ms": round(measured_ms, 2) if measured_ms else None,
        "sum_instruction_roofline_ms": round(total_roofline_ms, 2),
        "coherence_measured_over_roofline": round(
            measured_ms / total_roofline_ms, 3)
        if (measured_ms and total_roofline_ms) else None,
        "hlo_walk_gb_per_step": round(total_gb, 2),
        "cost_model_gb_per_step": round(sc["gb_per_step"], 2),
        "cost_model_tflop_per_step": round(sc["tflop_per_step"], 3),
        "n_instructions": len(rows),
        "top": rows[:25],
        "layers": layer_table(rows),
        "bound_split_ms": {
            "hbm": round(sum(r["roofline_ms"] for r in rows
                             if r["bound"] == "hbm"), 2),
            "mxu": round(sum(r["roofline_ms"] for r in rows
                             if r["bound"] == "mxu"), 2)},
    }
    result.update(_ATTACK_HISTORY)
    return result


# ----------------------------------------------------------------------
# input-pipeline overlap attribution (the stream half of the step
# accounting: the byte budget covers on-chip HBM traffic, this covers
# the host->device feed that must hide UNDER the step)
def overlap_attribution(decode_s, h2d_s, compute_s, measured_s=None):
    """Model of the overlapped streaming input pipeline (decode ring ->
    chunked uploader -> on-device augment -> fused step): a perfectly
    overlapped pipeline runs each batch in ``max`` of its stage times,
    a fully serialized one in their ``sum``.

    Returns per-batch seconds plus, when ``measured_s`` is given:

    * ``overlap_efficiency`` = bound / measured — 1.0 means every
      non-binding stage is fully hidden under the binding one; the
      serialized pipeline reads bound/sum.
    * ``exposed_s_per_batch`` — wall NOT hidden under the binding
      stage (what an optimization must attack next).
    * ``hidden_s_per_batch`` — overlap actually achieved vs the
      serialized baseline.
    """
    stages = {"decode": float(decode_s), "h2d": float(h2d_s),
              "compute": float(compute_s)}
    bound_s = max(stages.values())
    serial_s = sum(stages.values())
    out = {"decode_s_per_batch": round(stages["decode"], 4),
           "h2d_s_per_batch": round(stages["h2d"], 4),
           "compute_s_per_batch": round(stages["compute"], 4),
           "bound_s_per_batch": round(bound_s, 4),
           "serial_s_per_batch": round(serial_s, 4),
           "binding_stage": max(stages, key=stages.get)}
    if measured_s:
        measured_s = float(measured_s)
        out["measured_s_per_batch"] = round(measured_s, 4)
        out["overlap_efficiency"] = round(bound_s / measured_s, 3)
        out["exposed_s_per_batch"] = round(measured_s - bound_s, 4)
        out["hidden_s_per_batch"] = round(
            max(0.0, serial_s - measured_s), 4)
    return out


def _parse_overlap_arg(spec):
    """``decode=0.26,h2d=0.71,compute=0.09[,measured=0.77]`` -> kwargs."""
    vals = {}
    for item in spec.split(","):
        key, eq, v = item.partition("=")
        if not eq:
            raise ValueError("bad overlap item %r (want key=seconds)"
                             % item)
        vals[key.strip()] = float(v)
    missing = {"decode", "h2d", "compute"} - set(vals)
    if missing:
        raise ValueError("overlap spec missing %s" % sorted(missing))
    return overlap_attribution(vals["decode"], vals["h2d"],
                               vals["compute"], vals.get("measured"))


# ----------------------------------------------------------------------
# liveness view (the RESIDENT-bytes half of the step accounting: the
# roofline table above says where the bytes MOVE, this says where they
# SIT at the predicted peak — tools/mem_lint.py, same walker)
def _parse_live_arg(spec):
    """``model=mlp,batch=64,devices=2,remat=dots`` -> cost config."""
    cfg = {}
    for item in filter(None, (spec or "").split(",")):
        key, eq, v = item.partition("=")
        if not eq:
            raise ValueError("bad live item %r (want key=value)" % item)
        try:
            v = int(v)
        except ValueError:
            pass
        cfg[key.strip()] = v
    return cfg


def run_live(spec):
    """Build the trainer for the spec'd cost config (the SAME
    constructor the surrogate compiles) and print the buffer-liveness
    top-10 peak contributors from the static timeline."""
    t, _, cfg = build_cost_trainer(_parse_live_arg(spec))
    tl = t.mem_timeline()
    knobs = {k: v for k, v in cfg.items()
             if v not in (None,) and k != "num_classes"}
    print("liveness[%s]: predicted peak %.6f GB/chip at %s "
          "(%d program points)"
          % (" ".join("%s=%s" % kv for kv in sorted(knobs.items())),
             tl.peak_bytes_per_chip / 1e9, tl.peak_point, tl.n_points))
    print(tl.format_top(10))
    return 0


# ----------------------------------------------------------------------
# machine-readable byte budget (the CI regression gate)
def byte_budget_entry(result):
    """The budget record for one captured breakdown."""
    return {"model": result["model"],
            "cost_model_gb_per_step": result["cost_model_gb_per_step"]}


def load_budget(path=None):
    path = path or BUDGET_PATH
    if not os.path.exists(path):
        return None
    return json.load(open(path))


def check_byte_budget(measured_gb, entry, tolerance_pct=None):
    """Diff a measured ``cost_model_gb_per_step`` against a budget
    entry.  Returns ``(ok, delta_pct)`` — ``ok`` is False when the
    measurement exceeds the budget by more than the tolerance."""
    tol = BUDGET_TOLERANCE_PCT if tolerance_pct is None else tolerance_pct
    budget = float(entry["cost_model_gb_per_step"])
    delta_pct = (float(measured_gb) - budget) / budget * 100.0
    return delta_pct <= tol, round(delta_pct, 2)


def _platform():
    import jax
    return jax.devices()[0].platform


def run_check(artifact_dir=None, write_budget=False):
    """Capture the step for the current platform, attribute layers,
    drop the breakdown in ``artifact_dir``, and gate on the checked-in
    byte budget.  Returns a process exit code."""
    plat = _platform()
    if plat == "tpu":
        result = capture()                      # full shape, measured
    else:
        # the bench's CPU shape: compile + cost model only (executing
        # 40 batch-256 steps is a chip workload)
        import mxnet_tpu as mx
        result = capture(batch=16, image=64, measure=False, ctx=mx.cpu())
    measured = result["cost_model_gb_per_step"]

    if artifact_dir:
        os.makedirs(artifact_dir, exist_ok=True)
        art = os.path.join(artifact_dir, "STEP_BREAKDOWN_%s.json" % plat)
        with open(art, "w") as f:
            json.dump(result, f, indent=1)
        print("byte-budget: breakdown artifact -> %s" % art)

    budget = load_budget()
    entry = (budget or {}).get(plat)
    if entry is None:
        print("byte-budget: no %r entry in %s — nothing to gate against"
              % (plat, BUDGET_PATH))
        return 0
    if entry.get("model") != result["model"]:
        # a budget recorded at a different capture shape (e.g. a full
        # batch-256 --write-budget run on a CPU-only host) would
        # make every diff meaningless — ~95% slack that no regression
        # can ever trip.  Refuse to compare; --write-budget re-records
        # the entry at THIS platform's capture shape.
        print("byte-budget[%s]: budget entry model %r does not match "
              "the captured %r — stale or wrong-shape budget; re-ratchet "
              "with --check --write-budget"
              % (plat, entry.get("model"), result["model"]))
        if write_budget:
            budget[plat] = byte_budget_entry(result)
            with open(BUDGET_PATH, "w") as f:
                json.dump(budget, f, indent=1)
            print("byte-budget[%s]: budget rewritten to %.2f GB/step"
                  % (plat, measured))
            return 0
        return 1
    tol = (budget or {}).get("tolerance_pct", BUDGET_TOLERANCE_PCT)
    ok, delta_pct = check_byte_budget(measured, entry, tol)
    print("byte-budget[%s]: measured %.2f GB/step vs budget %.2f "
          "(%+.2f%%, tolerance %.1f%%): %s"
          % (plat, measured, entry["cost_model_gb_per_step"], delta_pct,
             tol, "OK" if ok else "REGRESSION"))
    if ok and delta_pct < -tol:
        print("byte-budget[%s]: budget is slack by %.2f%% — ratchet it "
              "down with --write-budget" % (plat, -delta_pct))
    if write_budget:
        # record unconditionally: an intentional IN-tolerance increase
        # must ratchet too, or the slack it leaves gets silently spent
        # by the next unrelated drift
        budget = budget or {"tolerance_pct": BUDGET_TOLERANCE_PCT}
        budget[plat] = byte_budget_entry(result)
        with open(BUDGET_PATH, "w") as f:
            json.dump(budget, f, indent=1)
        print("byte-budget[%s]: budget rewritten to %.2f GB/step"
              % (plat, measured))
        return 0
    return 0 if ok else 1


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="capture for the current platform and gate "
                         "cost_model_gb_per_step against %s"
                         % os.path.basename(BUDGET_PATH))
    ap.add_argument("--write-budget", action="store_true",
                    help="record the capture into the budget file "
                         "(ratchet after an intentional change)")
    ap.add_argument("--artifact-dir", default=None,
                    help="drop the layer-attributed breakdown JSON here")
    ap.add_argument("--overlap", default=None, metavar="SPEC",
                    help="attribute input-pipeline overlap from stage "
                         "seconds, e.g. decode=0.26,h2d=0.71,"
                         "compute=0.09,measured=0.77 (bench.py computes "
                         "the same fields live as stream_*)")
    ap.add_argument("--live", default=None, nargs="?", const="",
                    metavar="SPEC",
                    help="print the static buffer-liveness top-10 peak "
                         "contributors for a cost config (trace-only, "
                         "no compile), e.g. --live model=mlp,batch=64,"
                         "devices=2,remat=dots; default: the mlp tune "
                         "workload (tools/mem_lint.py shares the model)")
    args = ap.parse_args(argv)

    if args.overlap:
        print(json.dumps(_parse_overlap_arg(args.overlap)))
        return 0

    from mxnet_tpu import program
    program.place_compile_cache()

    if args.live is not None:
        return run_live(args.live)

    if args.check:
        return run_check(artifact_dir=args.artifact_dir,
                         write_budget=args.write_budget)

    result = capture()
    out = os.path.join(ROOT, "STEP_BREAKDOWN.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    if args.write_budget:
        if _platform() == "tpu":
            budget = load_budget() or \
                {"tolerance_pct": BUDGET_TOLERANCE_PCT}
            budget["tpu"] = byte_budget_entry(result)
            with open(BUDGET_PATH, "w") as f:
                json.dump(budget, f, indent=1)
        else:
            # this bare capture ran the FULL batch-256 shape on the
            # CPU; recording it into the "cpu" budget slot would leave
            # the nightly gate (which captures the small CPU shape)
            # ~95% slack.  The model-mismatch guard in run_check would
            # catch it, but don't write it at all.
            print("byte-budget: not recording a full-shape CPU "
                  "capture; use --check --write-budget on this host",
                  file=sys.stderr)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("top", "layers")}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
