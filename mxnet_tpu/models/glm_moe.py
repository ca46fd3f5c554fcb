"""Decoder-only mixture-of-experts language model of the GLM-4.7-Flash
family (``glm4_moe_lite``): latent attention, sigmoid-routed experts
with a shared expert, a multi-token-prediction module.

Pre-norm blocks x + Attn(RMSNorm(x)), x + FFN(RMSNorm(x)).  The first
``first_k_dense`` blocks have a dense gated feed-forward, the others an
expert layer: a router over all ``n_routed_experts``, the
``experts_held`` of them this chip holds (``MoEExperts`` computes their
part and drops no entry) and one shared expert.  Attention is latent
(MLA): queries through a ``q_lora_rank`` bottleneck, keys and values
expanded from a ``kv_lora_rank`` latent, a rotary part of
``qk_rope_head_dim`` dims whose key is one vector a position shared by
all heads; expanded, it is ordinary causal attention at head dimension
``qk_nope_head_dim + qk_rope_head_dim`` and runs the flash kernel
(``v_head_dim`` may differ from it: the attention op pads the narrower
operands with zero columns and cuts the result, see ``op/attention.py``).
``_linear``, ``_gated_ffn``, ``_expert_layer`` and ``_block`` (which
takes its mixer) also build ``models/bailing_hybrid.py`` and
``models/lfm2_moe.py`` (which has no shared expert) and
``models/laguna.py``, the first two ``models/loop_lm.py``.

The multi-token-prediction module (arXiv:2412.19437 sec. 2.2, depth 1)
joins the trunk's last hidden state at position i with the embedding of
token i+1, runs one more expert block and predicts token i+2 through the
trunk's own embedding and head: the embedding and the head are each one
parameter used twice.  Output 0 is the next-token softmax, output 1 the
module's, whose gradient is scaled by ``mtp_lambda``.

Every size is a keyword; the defaults are a toy.
"""
from .. import name as _name
from .. import symbol as sym

__all__ = ["get_symbol"]


def _linear(x, width, name, weight=None):
    kw = {} if weight is None else {"weight": weight}
    return sym.FullyConnected(x, num_hidden=width, no_bias=True, name=name,
                              **kw)


def _gated_ffn(x, width, hidden, prefix, weights=(None, None, None)):
    """(silu(x W_gate) * x W_up) W_down, three plain products;
    ``weights`` hands it W_gate, W_up and W_down where another node
    uses them too."""
    w_gate, w_up, w_down = weights
    gate = sym.Activation(_linear(x, width, prefix + "gate", w_gate),
                          act_type="silu", name=prefix + "act")
    return _linear(gate * _linear(x, width, prefix + "up", w_up), hidden,
                   prefix + "down", w_down)


def _attention(x, cfg):
    """Latent attention over (B*T, d) rows; returns (B*T, d)."""
    t, h = cfg["seq_len"], cfg["num_heads"]
    nope, rope, vdim = cfg["qk_nope"], cfg["qk_rope"], cfg["v_head"]
    eps, theta = cfg["eps"], cfg["rope_theta"]
    cq = sym.RMSNorm(_linear(x, cfg["q_lora_rank"], "attn_qa"), eps=eps,
                     name="attn_qa_norm")
    q = sym.Reshape(_linear(cq, h * (nope + rope), "attn_qb"),
                    shape=(-1, t, h, nope + rope), name="attn_q")
    q = sym.RotaryEmbedding(q, base=theta, offset=nope, dim=rope,
                            name="attn_q_rope")
    kva = _linear(x, cfg["kv_lora_rank"] + rope, "attn_kva")
    ckv = sym.slice_axis(kva, axis=1, begin=0, end=cfg["kv_lora_rank"],
                         name="attn_ckv")
    ckv = sym.RMSNorm(ckv, eps=eps, name="attn_kva_norm")
    kv = sym.Reshape(_linear(ckv, h * (nope + vdim), "attn_kvb"),
                     shape=(-1, t, h, nope + vdim), name="attn_kv")
    k_nope = sym.slice_axis(kv, axis=3, begin=0, end=nope,
                            name="attn_k_nope")
    v = sym.slice_axis(kv, axis=3, begin=nope, end=nope + vdim,
                       name="attn_v")
    # the rotary key: one vector a position, shared by all heads
    k_rope = sym.slice_axis(kva, axis=1, begin=cfg["kv_lora_rank"],
                            end=cfg["kv_lora_rank"] + rope,
                            name="attn_k_pe")
    k_rope = sym.RotaryEmbedding(
        sym.Reshape(k_rope, shape=(-1, t, 1, rope), name="attn_k_pe4"),
        base=theta, name="attn_k_rope")
    k_rope = sym.broadcast_axis(k_rope, axis=2, size=h, name="attn_k_peh")
    k = sym.Concat(k_nope, k_rope, dim=3, name="attn_k")
    out = sym._contrib_DotProductAttention(
        q, k, v, causal=True, scale=float(nope + rope) ** -0.5,
        name="attn_attn")
    out = sym.Reshape(out, shape=(-1, h * vdim), name="attn_out")
    return _linear(out, cfg["hidden"], "attn_o")


def _expert_layer(x, cfg):
    """Shared expert plus this chip's share of the routed experts; with
    ``cfg["n_shared"]`` 0 the routed share alone."""
    router = sym.MoERouter(x, num_experts=cfg["n_experts"],
                           top_k=cfg["top_k"], scale=cfg["scaling"],
                           n_group=cfg.get("n_group", 1),
                           topk_group=cfg.get("topk_group", 1),
                           name="moe_router")
    routed = sym.MoEExperts(x, router[0], router[1],
                            num_experts=cfg["n_experts"],
                            experts_held=cfg["held"],
                            first_expert=cfg["first_expert"],
                            num_hidden=cfg["moe_width"],
                            name="moe_experts")
    if not cfg.get("n_shared", 1):
        return routed
    return _gated_ffn(x, cfg["moe_width"], cfg["hidden"],
                      "moe_shared_") + routed


def _block(x, cfg, prefix, dense, mixer=None):
    """One pre-norm block; every node's name starts with ``prefix``.
    ``mixer(x, cfg)`` is the block's first half, latent attention
    unless another is given."""
    with _name.Prefix(prefix):
        x = x + (mixer or _attention)(
            sym.RMSNorm(x, eps=cfg["eps"], name="norm1"), cfg)
        h = sym.RMSNorm(x, eps=cfg["eps"], name="norm2")
        if dense:
            return x + _gated_ffn(h, cfg["dense_width"], cfg["hidden"],
                                  "mlp_")
        return x + _expert_layer(h, cfg)


def get_symbol(num_classes=512, vocab_size=None, seq_len=32, hidden_size=64,
               num_layers=2, first_k_dense=1, num_heads=2, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
               v_head_dim=32, intermediate_size=160,
               moe_intermediate_size=48, n_routed_experts=16,
               experts_held=4, first_expert=0, num_experts_per_tok=4,
               routed_scaling_factor=1.8, rope_theta=1e6, rms_norm_eps=1e-5,
               mtp_layers=1, mtp_lambda=0.3, **kwargs):
    """data (B, T) token ids, softmax_label (B, T) the next tokens ->
    [softmax over the vocabulary at every position, the prediction
    module's softmax of the token after next].  ``mtp_layers`` 0 leaves
    the module out and returns the one output."""
    vocab = vocab_size or num_classes
    if mtp_layers not in (0, 1):
        raise ValueError("mtp_layers is 0 or 1, got %r" % (mtp_layers,))
    cfg = dict(seq_len=seq_len, hidden=hidden_size, num_heads=num_heads,
               q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
               qk_nope=qk_nope_head_dim, qk_rope=qk_rope_head_dim,
               v_head=v_head_dim, dense_width=intermediate_size,
               moe_width=moe_intermediate_size, n_experts=n_routed_experts,
               held=experts_held, first_expert=first_expert,
               top_k=num_experts_per_tok, scaling=routed_scaling_factor,
               rope_theta=rope_theta, eps=rms_norm_eps)
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed_w = sym.Variable("tok_embed_weight")
    head_w = sym.Variable("head_weight")

    def embed(ids, name):
        e = sym.Embedding(ids, weight=embed_w, input_dim=vocab,
                          output_dim=hidden_size, name=name)
        return sym.Reshape(e, shape=(-1, hidden_size), name=name + "_rows")

    x = embed(data, "tok_embed")
    for i in range(num_layers):
        x = _block(x, cfg, "l%d_" % i, dense=i < first_k_dense)
    logits = _linear(sym.RMSNorm(x, eps=rms_norm_eps, name="norm"), vocab,
                     "head", weight=head_w)
    main = sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,),
                                                 name="label_rows"),
                             name="softmax")
    if not mtp_layers:
        return main

    # position i: the trunk's state (before its final norm) with the
    # embedding of token i+1, which is the label at i; the target is
    # token i+2, the label at i+1, and a row's last position has none
    with _name.Prefix("mtp_"):
        joined = sym.Concat(
            sym.RMSNorm(x, eps=rms_norm_eps, name="hnorm"),
            sym.RMSNorm(embed(label, "embed"), eps=rms_norm_eps,
                        name="enorm"),
            dim=1, name="joined")
        h = _linear(joined, hidden_size, "eh_proj")
    h = _block(h, cfg, "mtp_", dense=False)
    with _name.Prefix("mtp_"):
        logits2 = _linear(sym.RMSNorm(h, eps=rms_norm_eps, name="norm"),
                          vocab, "head", weight=head_w)
        after = sym.slice_axis(label, axis=1, begin=1, end=seq_len,
                               name="label_next")
        none = sym.slice_axis(label, axis=1, begin=0, end=1,
                              name="label_none") * 0 - 1
        target = sym.Reshape(sym.Concat(after, none, dim=1,
                                        name="label_shifted"),
                             shape=(-1,), name="label_rows")
        mtp = sym.SoftmaxOutput(logits2, target, grad_scale=mtp_lambda,
                                use_ignore=True, ignore_label=-1,
                                name="softmax")
    return sym.Group([main, mtp])
