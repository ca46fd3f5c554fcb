"""Composed large-model parallelism workloads.

Everything the parallel layers can do, exercised together on
transformer-shaped programs (ROADMAP item 3 — the framework judged on
more than ResNet-50):

* **transformer-large** — a decoder LM trained end to end with
  pipeline parallelism (interleaved schedule over ``pipe``), an MoE
  FFN in every stage (sort-based sparse dispatch, top-2 gating),
  gradient accumulation (an outer ``lax.scan``), momentum SGD with
  ZeRO-style optimizer state sharded over the pipe axis, and
  kill-and-resume through :class:`~mxnet_tpu.resilience.CheckpointManager`.
* **ringattn-long-context** — a causal LM whose attention runs as ring
  attention over a ``seq`` mesh axis (causal block skip + fused K/V
  permute), for the long-context tokens/sec headline.

The configs here are sized for the virtual 8-device CPU mesh the tests
and CI run on; the shapes (not the sizes) are what the real chips see.
``tools/mem_lint.py`` traces both at these sizes for its peak-HBM
ratchet; ``tests/test_parallel_workloads.py`` asserts value, gradient
and resume parity.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from .mesh import make_mesh, zero_spec
from .moe import moe_apply
from .pipeline import pipeline_apply
from .ring_attention import attention_reference, ring_attention_sharded

__all__ = ["TransformerConfig", "transformer_large", "ringattn_long_context",
           "transformer_init", "transformer_forward", "transformer_loss",
           "make_train_step", "momentum_shardings", "synth_tokens",
           "tokens_per_step", "ringattn_init", "ringattn_forward",
           "save_composed", "load_composed"]


class TransformerConfig:
    """Plain knob bag for the composed workloads (attribute access,
    stable ``key()`` for program-cache identity)."""

    _DEFAULTS = dict(
        vocab=512, seq=64, d_model=128, n_heads=4, d_hidden=256,
        n_layers=8, n_experts=4, capacity_factor=1.25, top_k=2,
        moe_dispatch=None,          # None -> MXTPU_MOE_DISPATCH
        n_micro=4, microbatch=2, grad_accum=2,
        pipe=4, seq_shards=8, schedule=None,  # None -> MXTPU_PIPE_SCHEDULE
        zero=True, lr=0.02, momentum=0.9, seed=0,
    )

    def __init__(self, **kw):
        bad = set(kw) - set(self._DEFAULTS)
        if bad:
            raise ValueError("unknown config fields: %s" % sorted(bad))
        for k, dflt in self._DEFAULTS.items():
            setattr(self, k, kw.get(k, dflt))

    def key(self):
        """JSON-able identity dict (CompiledProgram cache key part)."""
        return {k: getattr(self, k) for k in sorted(self._DEFAULTS)}


def transformer_large(**overrides):
    """The pipeline×MoE×grad_accum×zero bench config (CPU-mesh sized:
    4 pipe devices × 2 stages/device = 8 layers, top-2 sparse MoE)."""
    cfg = dict(vocab=512, seq=64, d_model=128, n_heads=4, d_hidden=256,
               n_layers=8, n_experts=4, top_k=2, n_micro=4, microbatch=2,
               grad_accum=2, pipe=4, zero=True)
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def ringattn_long_context(**overrides):
    """The long-context causal ring-attention config (8 seq shards)."""
    cfg = dict(vocab=512, seq=2048, d_model=128, n_heads=4, d_hidden=256,
               n_layers=2, n_micro=1, microbatch=1, grad_accum=1,
               seq_shards=8, zero=False)
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def tokens_per_step(cfg):
    """Tokens consumed by ONE optimizer step (the tok/sec numerator)."""
    return cfg.grad_accum * cfg.n_micro * cfg.microbatch * cfg.seq


def _rmsnorm(x, g):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g


def _init_stack(key, n, shape, scale, dtype=jnp.float32):
    return (jax.random.normal(key, (n,) + shape) * scale).astype(dtype)


# ======================================================================
# transformer-large: pipeline × MoE × grad_accum × zero
def transformer_init(key, cfg, dtype=jnp.float32):
    """Parameter pytree: replicated embed/pos/head + stacked
    ``(n_layers, ...)`` stage leaves (sharded over ``pipe`` by
    ``pipeline_apply``)."""
    d, S, E, h = cfg.d_model, cfg.n_layers, cfg.n_experts, cfg.d_hidden
    ks = jax.random.split(key, 10)
    s = d ** -0.5
    return {
        "embed": _init_stack(ks[0], cfg.vocab, (d,), 0.02, dtype),
        "pos": _init_stack(ks[1], cfg.seq, (d,), 0.02, dtype),
        "head": (jax.random.normal(ks[2], (d, cfg.vocab)) * s
                 ).astype(dtype),
        "stages": {
            "ln1": jnp.ones((S, d), dtype),
            "wq": _init_stack(ks[3], S, (d, d), s, dtype),
            "wk": _init_stack(ks[4], S, (d, d), s, dtype),
            "wv": _init_stack(ks[5], S, (d, d), s, dtype),
            "wo": _init_stack(ks[6], S, (d, d), s, dtype),
            "ln2": jnp.ones((S, d), dtype),
            "gate": _init_stack(ks[7], S, (d, E), s, dtype),
            "w1": _init_stack(ks[8], S, (E, d, h), s, dtype),
            "w2": _init_stack(ks[9], S, (E, h, d), h ** -0.5, dtype),
        },
    }


def _stage_fn(cfg, p, x):
    """One pipeline stage: pre-norm causal self-attention + MoE FFN,
    both residual.  ``x``: (mb, seq, d).  Collective-free (the
    pipeline engine cond-skips it on fill/drain ticks); the local
    attention sees the full ``seq`` of its microbatch."""
    mb, t, d = x.shape
    hd = d // cfg.n_heads
    hx = _rmsnorm(x, p["ln1"])
    q = (hx @ p["wq"]).reshape(mb, t, cfg.n_heads, hd)
    k = (hx @ p["wk"]).reshape(mb, t, cfg.n_heads, hd)
    v = (hx @ p["wv"]).reshape(mb, t, cfg.n_heads, hd)
    attn = attention_reference(q, k, v, causal=True)
    x = x + attn.reshape(mb, t, d) @ p["wo"]
    hx = _rmsnorm(x, p["ln2"])
    moe_p = {"gate": p["gate"], "w1": p["w1"], "w2": p["w2"]}
    out, _keep = moe_apply(moe_p, hx.reshape(mb * t, d),
                           capacity_factor=cfg.capacity_factor,
                           top_k=cfg.top_k, dispatch=cfg.moe_dispatch)
    return x + out.reshape(mb, t, d)


def transformer_forward(params, tokens, cfg, mesh, axis="pipe"):
    """``tokens``: (n_micro, mb, seq) int32 -> logits
    (n_micro, mb, seq, vocab).  Embed/head run replicated outside the
    pipeline; the stage stack runs under ``pipeline_apply``."""
    x = params["embed"][tokens] + params["pos"][None, None]
    y = pipeline_apply(partial(_stage_fn, cfg), params["stages"], x,
                       mesh, axis=axis, schedule=cfg.schedule)
    return y @ params["head"]


def transformer_loss(params, tokens, cfg, mesh, axis="pipe"):
    """Mean next-token cross-entropy over one (n_micro, mb, seq) batch."""
    logits = transformer_forward(params, tokens, cfg, mesh, axis=axis)
    lp = jax.nn.log_softmax(logits[..., :-1, :].astype(jnp.float32))
    tgt = tokens[..., 1:]
    nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)
    return jnp.mean(nll)


def momentum_shardings(params, mesh, axis="pipe"):
    """NamedShardings for the momentum pytree under ZeRO-style state
    sharding: stage leaves keep their pipe partitioning (their state is
    naturally sharded with the weight); replicated leaves (embed, pos,
    head) fold ``axis`` into their first divisible dim via
    :func:`~mxnet_tpu.parallel.mesh.zero_spec`."""
    n = mesh.shape[axis]

    def leaf_spec(base):
        def f(leaf):
            return NamedSharding(
                mesh, zero_spec(base, leaf.shape, n, axis=axis))
        return f

    return {
        "embed": leaf_spec(PartitionSpec())(params["embed"]),
        "pos": leaf_spec(PartitionSpec())(params["pos"]),
        "head": leaf_spec(PartitionSpec())(params["head"]),
        "stages": jax.tree.map(leaf_spec(PartitionSpec(axis)),
                               params["stages"]),
    }


def make_train_step(cfg, mesh, axis="pipe", params_template=None):
    """The fused optimizer step: grad-accumulation scan over
    ``(grad_accum, n_micro, mb, seq)`` token groups, momentum SGD, and
    (``cfg.zero``) opt-state sharding constraints.  Pure — jit or wrap
    in a CompiledProgram; deterministic given (params, mom, tokens).
    ``params_template`` (any pytree of the right structure/shapes) is
    required when ``cfg.zero`` to plan the momentum shardings."""
    mom_shardings = None
    if cfg.zero:
        if params_template is None:
            raise ValueError("cfg.zero needs params_template to plan "
                             "momentum shardings")
        mom_shardings = momentum_shardings(params_template, mesh,
                                           axis=axis)

    def train_step(params, mom, tokens):
        G = tokens.shape[0]

        def acc(g, batch):
            gi = jax.grad(transformer_loss)(params, batch, cfg, mesh,
                                            axis=axis)
            return jax.tree.map(jnp.add, g, gi), None

        g0 = jax.tree.map(jnp.zeros_like, params)
        grads, _ = lax.scan(acc, g0, tokens)
        grads = jax.tree.map(lambda g: g / G, grads)
        new_mom = jax.tree.map(lambda m, g: cfg.momentum * m + g,
                               mom, grads)
        if cfg.zero and mom_shardings is not None:
            new_mom = jax.tree.map(lax.with_sharding_constraint,
                                   new_mom, mom_shardings)
        new_params = jax.tree.map(lambda p, m: p - cfg.lr * m,
                                  params, new_mom)
        return new_params, new_mom

    return train_step


def synth_tokens(cfg, step):
    """Deterministic synthetic batch for optimizer step ``step``:
    ``(grad_accum, n_micro, mb, seq)`` int32 — resume parity depends on
    the data being a pure function of the step index."""
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
    return jax.random.randint(
        key, (cfg.grad_accum, cfg.n_micro, cfg.microbatch, cfg.seq),
        0, cfg.vocab, dtype=jnp.int32)


# ======================================================================
# checkpoint adapters (CheckpointManager speaks module/symbol; the
# composed workload is a bare pytree — flatten to named arrays)
def _flat_names(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out[name] = leaf
    return out


class _PytreeModule:
    """Just enough module surface for CheckpointManager.save: a
    one-variable symbol for the provenance file, params exposed as
    named arrays, no optimizer states (momentum rides aux_params)."""

    optimizer_initialized = False

    def __init__(self):
        from .. import symbol as _sym
        self.symbol = _sym.Variable("data")

    def get_params(self):
        return {}, {}


def save_composed(mgr, params, mom, step):
    """Checkpoint the composed run: params as arg_params, momentum and
    the step counter as aux_params, through ``mgr``'s CRC-manifested
    commit path.  Returns the Checkpoint."""
    from .. import ndarray as nd
    arg = {k: nd.array(np.asarray(v))
           for k, v in _flat_names(params).items()}
    aux = {"mom/" + k: nd.array(np.asarray(v))
           for k, v in _flat_names(mom).items()}
    aux["step"] = nd.array(np.array([step], np.int32))
    return mgr.save(_PytreeModule(), int(step), arg_params=arg,
                    aux_params=aux)


def load_composed(ck, params_template, mom_template):
    """Inverse of :func:`save_composed`: rebuild (params, mom, step)
    shaped like the templates from checkpoint ``ck``."""
    _sym, arg, aux = ck.load_params()

    def rebuild(template, table, prefix=""):
        names = _flat_names(template)
        leaves = {}
        for name, leaf in names.items():
            nd_leaf = table[prefix + name]
            leaves[name] = jnp.asarray(nd_leaf.asnumpy(),
                                       dtype=leaf.dtype)
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        ordered = ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path) for path, _ in flat]
        return jax.tree_util.tree_unflatten(
            treedef, [leaves[n] for n in ordered])

    params = rebuild(params_template, arg)
    mom = rebuild(mom_template, aux, prefix="mom/")
    step = int(aux["step"].asnumpy()[0])
    return params, mom, step


# ======================================================================
# ringattn-long-context: causal LM over a seq-sharded mesh
def ringattn_init(key, cfg, dtype=jnp.float32):
    """Replicated params for the long-context LM: embed/pos/head plus
    ``n_layers`` stacked blocks (ring attention + dense FFN)."""
    d, L, h = cfg.d_model, cfg.n_layers, cfg.d_hidden
    ks = jax.random.split(key, 8)
    s = d ** -0.5
    return {
        "embed": _init_stack(ks[0], cfg.vocab, (d,), 0.02, dtype),
        "pos": _init_stack(ks[1], cfg.seq, (d,), 0.02, dtype),
        "head": (jax.random.normal(ks[2], (d, cfg.vocab)) * s
                 ).astype(dtype),
        "blocks": {
            "ln1": jnp.ones((L, d), dtype),
            "wq": _init_stack(ks[3], L, (d, d), s, dtype),
            "wk": _init_stack(ks[4], L, (d, d), s, dtype),
            "wv": _init_stack(ks[5], L, (d, d), s, dtype),
            "wo": _init_stack(ks[6], L, (d, d), s, dtype),
            "ln2": jnp.ones((L, d), dtype),
            "w1": _init_stack(ks[7], L, (d, h), s, dtype),
            "w2": _init_stack(jax.random.fold_in(ks[7], 1), L, (h, d),
                              h ** -0.5, dtype),
        },
    }


def ringattn_forward(params, tokens, cfg, mesh, axis="seq",
                     skip_masked=None):
    """``tokens``: (batch, seq) int32 over the GLOBAL sequence ->
    logits (batch, seq, vocab); attention is exact causal ring
    attention sharded over ``mesh[axis]``, everything else is
    pointwise over seq (GSPMD keeps it sharded)."""
    b, t = tokens.shape
    d, H = cfg.d_model, cfg.n_heads
    hd = d // H
    x = params["embed"][tokens] + params["pos"][None, :t]
    x = lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(None, axis, None)))
    for li in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[li], params["blocks"])
        hx = _rmsnorm(x, p["ln1"])
        q = (hx @ p["wq"]).reshape(b, t, H, hd)
        k = (hx @ p["wk"]).reshape(b, t, H, hd)
        v = (hx @ p["wv"]).reshape(b, t, H, hd)
        attn = ring_attention_sharded(q, k, v, mesh, axis=axis,
                                      causal=True,
                                      skip_masked=skip_masked)
        x = x + attn.reshape(b, t, d) @ p["wo"]
        hx = _rmsnorm(x, p["ln2"])
        x = x + jax.nn.relu(hx @ p["w1"]) @ p["w2"]
    return x @ params["head"]
