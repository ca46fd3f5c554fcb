"""Decoder-only hybrid language model of the LFM2-MoE family
(``lfm2_moe``): blocks that mix by a gated short convolution or by
grouped-query attention, by a ``layer_types`` pattern, and sigmoid-routed
experts with no shared expert.

Pre-norm blocks x + Mixer(RMSNorm(x)), x + FFN(RMSNorm(x)).  A ``conv``
block's mixer is [B | C | x~] = u W_in (d -> 3d, split in that order),
y = B * x~, a depthwise causal convolution of ``conv_kernel`` taps along
time with no bias (``_contrib_ShortConv``, node ``l<i>_sconv_taps``) and
(C * z) W_out; there is no activation.  An ``attention`` block's mixer
is causal softmax attention of ``num_heads`` query heads over
``num_kv_heads`` key/value heads (query head j reads key/value head
j // (num_heads / num_kv_heads)), head dimension hidden / num_heads, q
and k each through an RMSNorm over a head's dims with a gamma of their
own and rotate-half rotary embedding on all of them, then W_o.  The first
``num_dense_layers`` blocks have a dense gated feed-forward, the others
``glm_moe``'s expert layer without its shared expert.

A chip holds ``experts_held`` of a layer's experts from ``first_expert``
and the rows of the vocabulary it is given; the mixers, the router and
the dense layer are whole.  Every size is a keyword; the defaults are a
toy.
"""
from .. import symbol as sym
from .glm_moe import _block, _linear

__all__ = ["get_symbol"]


def _sconv(x, cfg):
    """The gated short convolution over (B*T, d) rows; returns (B*T, d)."""
    t, d = cfg["seq_len"], cfg["hidden"]
    bcx = _linear(x, 3 * d, "sconv_in")
    b, c, xt = (sym.slice_axis(bcx, axis=1, begin=i * d, end=(i + 1) * d,
                               name="sconv_" + n)
                for i, n in enumerate(("b", "c", "x")))
    y = sym.Reshape(sym.elemwise_mul(b, xt, name="sconv_bx"),
                    shape=(-1, t, d), name="sconv_bx_seq")
    z = sym._contrib_ShortConv(y, kernel=cfg["conv_kernel"],
                               name="sconv_taps")
    z = sym.Reshape(z, shape=(-1, d), name="sconv_z_rows")
    return _linear(sym.elemwise_mul(c, z, name="sconv_cz"), d, "sconv_out")


def _gqa(x, cfg):
    """Grouped-query attention over (B*T, d) rows; returns (B*T, d)."""
    t, h, h_kv, dh = (cfg["seq_len"], cfg["num_heads"], cfg["num_kv_heads"],
                      cfg["head_dim"])
    eps, theta = cfg["eps"], cfg["rope_theta"]

    def heads(n, count, normed):
        u = sym.Reshape(_linear(x, count * dh, "attn_" + n),
                        shape=(-1, t, count, dh), name="attn_%s_heads" % n)
        if not normed:
            return u
        u = sym.RMSNorm(u, eps=eps, name="attn_%s_norm" % n)
        return sym.RotaryEmbedding(u, base=theta, name="attn_%s_rope" % n)

    out = sym._contrib_DotProductAttention(
        heads("q", h, True), heads("k", h_kv, True), heads("v", h_kv, False),
        causal=True, scale=float(dh) ** -0.5, name="attn_attn")
    out = sym.Reshape(out, shape=(-1, h * dh), name="attn_out")
    return _linear(out, cfg["hidden"], "attn_o")


_MIXERS = {"conv": _sconv, "attention": _gqa}


def get_symbol(num_classes=512, vocab_size=None, seq_len=64, hidden_size=64,
               layer_types=("conv", "attention", "conv"), num_dense_layers=1,
               num_heads=4, num_kv_heads=2, conv_kernel=3,
               intermediate_size=160, moe_intermediate_size=48,
               num_experts=16, experts_held=4, first_expert=0,
               num_experts_per_tok=4, routed_scaling_factor=1.0,
               rope_theta=1e6, rms_norm_eps=1e-5, **kwargs):
    """data (B, T) token ids, softmax_label (B, T) the next tokens ->
    the softmax over the held rows of the vocabulary at every position.
    ``layer_types`` names each block's mixer, ``conv`` or ``attention``
    (a sequence, or the names joined by commas)."""
    vocab = vocab_size or num_classes
    if isinstance(layer_types, str):
        layer_types = layer_types.split(",")
    unknown = sorted(set(layer_types) - set(_MIXERS))
    if unknown:
        raise ValueError("layer_types names %s; a mixer is one of %s"
                         % (unknown, sorted(_MIXERS)))
    if hidden_size % num_heads or num_heads % num_kv_heads:
        raise ValueError("%d query heads over hidden size %d and %d "
                         "key/value heads" % (num_heads, hidden_size,
                                              num_kv_heads))
    cfg = dict(seq_len=seq_len, hidden=hidden_size, num_heads=num_heads,
               num_kv_heads=num_kv_heads, head_dim=hidden_size // num_heads,
               conv_kernel=conv_kernel, dense_width=intermediate_size,
               moe_width=moe_intermediate_size, n_experts=num_experts,
               held=experts_held, first_expert=first_expert,
               top_k=num_experts_per_tok, scaling=routed_scaling_factor,
               n_shared=0, rope_theta=rope_theta, eps=rms_norm_eps)
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data, input_dim=vocab, output_dim=hidden_size,
                      name="tok_embed")
    x = sym.Reshape(x, shape=(-1, hidden_size), name="tok_embed_rows")
    for i, kind in enumerate(layer_types):
        x = _block(x, cfg, "l%d_" % i, dense=i < num_dense_layers,
                   mixer=_MIXERS[kind])
    logits = _linear(sym.RMSNorm(x, eps=rms_norm_eps, name="norm"), vocab,
                     "head")
    return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,),
                                                 name="label_rows"),
                             name="softmax")
