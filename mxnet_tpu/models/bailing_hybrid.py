"""Decoder-only hybrid language model of the Ling-3.0-flash family
(``bailing_hybrid``): blocks that differ in their mixer by a
``layer_types`` pattern, sigmoid-routed experts chosen within groups.

Pre-norm blocks x + Mixer(RMSNorm(x)), x + FFN(RMSNorm(x)).  A ``kda``
block's mixer is Kimi delta attention (arXiv:2510.26692): q, k and v
through a depthwise causal convolution of ``conv_kernel`` taps and SiLU,
q and k L2-normed a head, a log decay a channel ``lower_bound *
sigmoid(exp(A_log) * (x W_f + dt_bias))`` (the source's
``kda_safe_gate``: the only gate written here, so every rule node
declares ``lower_bound``), a write strength ``sigmoid(x W_beta)`` a
head, the gated delta rule with its state along the sequence
(``_contrib_GatedDeltaRule``, node ``l<i>_kda_core``), and
``(RMSNorm(o) * sigmoid(x W_g)) W_o``.  An ``mla`` block's mixer is
latent attention with no query bottleneck: keys and values expanded from
a ``kv_lora_rank`` latent, a rotary part whose key is one vector a
position shared by the heads (interleaved pairing), ``qk_nope + qk_rope``
wide queries and keys against ``v_head_dim`` wide values
(``l<i>_attn_attn``), and a gate of one scalar a head on the result.
The first ``first_k_dense`` blocks have a dense gated feed-forward, the
others ``glm_moe``'s expert layer with the router's choice limited to
``topk_group`` of ``n_group`` groups.

A chip holds ``heads_held`` of a layer's heads (every per-head leaf has
that many), ``experts_held`` of its experts from ``first_expert`` and
the rows of the vocabulary it is given; the router, the shared expert
and the latent projection are whole.  Every size is a keyword; the
defaults are a toy.
"""
from .. import name as _name
from .. import symbol as sym
from .glm_moe import _block, _linear

__all__ = ["get_symbol"]


def _leaf(name, shape):
    """A parameter no op infers the shape of, named under the block's
    prefix like the nodes around it."""
    return sym.Variable(_name.current().get(name, None), shape=shape)


def _kda(x, cfg):
    """Kimi delta attention over (B*T, d) rows; returns (B*T, d)."""
    t, h, dk = cfg["seq_len"], cfg["heads_held"], cfg["head_dim"]

    def branch(n):
        # projection, causal taps along time, SiLU; [b, t, h, dk]
        u = sym.Reshape(_linear(x, h * dk, "kda_" + n), shape=(-1, t, h * dk),
                        name="kda_%s_seq" % n)
        u = sym._contrib_ShortConv(u, kernel=cfg["conv_kernel"],
                                   name="kda_%s_conv" % n)
        return sym.Activation(u, act_type="silu", name="kda_%s_act" % n)

    def unit(u, n):
        # a head's L2 norm, on rows of one head each
        u = sym.L2Normalization(sym.Reshape(u, shape=(-1, dk),
                                            name="kda_%s_heads" % n),
                                eps=1e-6, name="kda_%s_l2" % n)
        return sym.Reshape(u, shape=(-1, t, h, dk), name="kda_%s_unit" % n)

    q, k = unit(branch("q"), "q"), unit(branch("k"), "k")
    v = sym.Reshape(branch("v"), shape=(-1, t, h, dk), name="kda_v_heads")
    # the log decay in float32: it is summed along a chunk
    f = sym.Cast(sym.Reshape(_linear(x, h * dk, "kda_f"),
                             shape=(-1, t, h, dk), name="kda_f_heads"),
                 dtype="float32", name="kda_f_f32")
    # (so are its rate and bias: a bfloat16 exponent is 0.4% off)
    rate = sym.Reshape(sym.exp(sym.Cast(_leaf("kda_A_log", (h,)),
                                        dtype="float32", name="kda_A_f32"),
                               name="kda_rate"),
                       shape=(1, 1, h, 1), name="kda_rate4")
    dt = sym.Reshape(sym.Cast(_leaf("kda_dt_bias", (h * dk,)),
                              dtype="float32", name="kda_dt_f32"),
                     shape=(1, 1, h, dk), name="kda_dt4")
    g = sym.Activation(sym.broadcast_mul(sym.broadcast_add(f, dt), rate),
                       act_type="sigmoid", name="kda_gate") \
        * cfg["kda_lower_bound"]
    beta = sym.Activation(sym.Reshape(_linear(x, h, "kda_beta"),
                                      shape=(-1, t, h), name="kda_beta_seq"),
                          act_type="sigmoid", name="kda_beta_act")
    # the gate is bounded by construction (``kda_safe_gate``), and the
    # node says so: the rule then takes its product form
    o = sym._contrib_GatedDeltaRule(q, k, v, g, beta, scale=dk ** -0.5,
                                    chunk=cfg["chunk"],
                                    lower_bound=cfg["kda_lower_bound"],
                                    name="kda_core")
    o = sym.RMSNorm(o, eps=cfg["eps"], name="kda_o_norm")
    gate = sym.Activation(sym.Reshape(_linear(x, h * dk, "kda_g"),
                                      shape=(-1, t, h, dk),
                                      name="kda_g_heads"),
                          act_type="sigmoid", name="kda_g_act")
    out = sym.Reshape(o * gate, shape=(-1, h * dk), name="kda_out")
    return _linear(out, cfg["hidden"], "kda_o")


def _mla(x, cfg):
    """Latent attention with no query bottleneck and a gate a head."""
    t, h = cfg["seq_len"], cfg["heads_held"]
    nope, rope, vdim = cfg["qk_nope"], cfg["qk_rope"], cfg["v_head"]
    eps, theta, rank = cfg["eps"], cfg["rope_theta"], cfg["kv_lora_rank"]
    q = sym.Reshape(_linear(x, h * (nope + rope), "attn_q"),
                    shape=(-1, t, h, nope + rope), name="attn_q_heads")
    q = sym.RotaryEmbedding(q, base=theta, offset=nope, dim=rope,
                            interleaved=True, name="attn_q_rope")
    kva = _linear(x, rank + rope, "attn_kva")
    ckv = sym.RMSNorm(sym.slice_axis(kva, axis=1, begin=0, end=rank,
                                     name="attn_ckv"),
                      eps=eps, name="attn_kva_norm")
    kv = sym.Reshape(_linear(ckv, h * (nope + vdim), "attn_kvb"),
                     shape=(-1, t, h, nope + vdim), name="attn_kv")
    k_nope = sym.slice_axis(kv, axis=3, begin=0, end=nope,
                            name="attn_k_nope")
    v = sym.slice_axis(kv, axis=3, begin=nope, end=nope + vdim,
                       name="attn_v")
    k_rope = sym.slice_axis(kva, axis=1, begin=rank, end=rank + rope,
                            name="attn_k_pe")
    k_rope = sym.RotaryEmbedding(
        sym.Reshape(k_rope, shape=(-1, t, 1, rope), name="attn_k_pe4"),
        base=theta, interleaved=True, name="attn_k_rope")
    k_rope = sym.broadcast_axis(k_rope, axis=2, size=h, name="attn_k_peh")
    k = sym.Concat(k_nope, k_rope, dim=3, name="attn_k")
    out = sym._contrib_DotProductAttention(
        q, k, v, causal=True, scale=float(nope + rope) ** -0.5,
        name="attn_attn")
    gate = sym.Activation(sym.Reshape(_linear(x, h, "attn_gate"),
                                      shape=(-1, t, h, 1),
                                      name="attn_gate_heads"),
                          act_type="sigmoid", name="attn_gate_act")
    out = sym.Reshape(sym.broadcast_mul(out, gate), shape=(-1, h * vdim),
                      name="attn_out")
    return _linear(out, cfg["hidden"], "attn_o")


_MIXERS = {"kda": _kda, "mla": _mla}


def get_symbol(num_classes=512, vocab_size=None, seq_len=64, hidden_size=64,
               layer_types=("kda", "kda", "mla"), first_k_dense=1,
               heads_held=2, head_dim=16, conv_kernel=4,
               kda_lower_bound=-5.0, chunk=64, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate_size=160, moe_intermediate_size=48,
               num_experts=32, experts_held=4, first_expert=0,
               num_experts_per_tok=4, n_group=4, topk_group=2,
               routed_scaling_factor=2.5, rope_theta=6e6, rms_norm_eps=1e-6,
               **kwargs):
    """data (B, T) token ids, softmax_label (B, T) the next tokens ->
    the softmax over the held rows of the vocabulary at every position.
    ``layer_types`` names each block's mixer, ``kda`` or ``mla`` (a
    sequence, or the names joined by commas)."""
    vocab = vocab_size or num_classes
    if isinstance(layer_types, str):
        layer_types = layer_types.split(",")
    unknown = sorted(set(layer_types) - set(_MIXERS))
    if unknown:
        raise ValueError("layer_types names %s; a mixer is one of %s"
                         % (unknown, sorted(_MIXERS)))
    cfg = dict(seq_len=seq_len, hidden=hidden_size, heads_held=heads_held,
               head_dim=head_dim, conv_kernel=conv_kernel,
               kda_lower_bound=kda_lower_bound, chunk=chunk,
               kv_lora_rank=kv_lora_rank, qk_nope=qk_nope_head_dim,
               qk_rope=qk_rope_head_dim, v_head=v_head_dim,
               dense_width=intermediate_size,
               moe_width=moe_intermediate_size, n_experts=num_experts,
               held=experts_held, first_expert=first_expert,
               top_k=num_experts_per_tok, n_group=n_group,
               topk_group=topk_group, scaling=routed_scaling_factor,
               rope_theta=rope_theta, eps=rms_norm_eps)
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data, input_dim=vocab, output_dim=hidden_size,
                      name="tok_embed")
    x = sym.Reshape(x, shape=(-1, hidden_size), name="tok_embed_rows")
    for i, kind in enumerate(layer_types):
        x = _block(x, cfg, "l%d_" % i, dense=i < first_k_dense,
                   mixer=_MIXERS[kind])
    logits = _linear(sym.RMSNorm(x, eps=rms_norm_eps, name="norm"), vocab,
                     "head")
    return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,),
                                                 name="label_rows"),
                             name="softmax")
