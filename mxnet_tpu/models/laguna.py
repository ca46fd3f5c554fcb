"""Decoder-only mixture-of-experts language model of the Laguna family
(``laguna``): window and full attention in one stack, a gate a head on
the attention's result, sigmoid-routed experts with a shared expert.

Pre-norm blocks x + Attn(RMSNorm(x)), x + FFN(RMSNorm(x)).  With u the
block's normed input and ``heads_per_layer[i]`` = H query heads of n =
``head_dim`` (which need not be hidden / H):

* q = u W_q (H heads), k = u W_k and v = u W_v (``num_kv_heads`` heads
  each); query head j reads key/value head j // (H / num_kv_heads).
* Rotary embedding, rotate-half, by the layer's kind
  (``rope_parameters[kind]``, the keys of the family's config): on the
  first ``partial_rotary_factor`` x n dims of every q and k head, the
  rest passed through; ``rope_type`` ``yarn`` takes YaRN's frequencies
  and scales cos and sin by ``attention_factor`` (``RotaryEmbedding``).
* softmax(q k^T / sqrt(n)) v, causal; a ``sliding_attention`` layer
  also keeps only the keys t - ``sliding_window`` < s <= t of query t
  (node ``l<i>_attn_window``), a ``full_attention`` layer all of them
  (node ``l<i>_attn_attn``).
* o_j <- sigmoid(u W_g)_j o_j, W_g of H outputs: one scalar a head and
  position (node ``l<i>_attn_gate``); then x <- x + o W_o.
* The first ``num_dense_layers`` blocks have a dense gated feed-forward
  of ``intermediate_size``, the others ``glm_moe``'s expert layer: a
  sigmoid router over all ``num_experts``, the top
  ``num_experts_per_tok`` renormalized and times
  ``routed_scaling_factor``, the ``experts_held`` of them this chip
  holds from ``first_expert``, and one shared expert, all of width
  ``moe_intermediate_size`` (``shared_expert_intermediate_size``, where
  given, is that width too).
* A final RMSNorm and an untied head over the rows of the vocabulary
  the chip holds.

The router, attention, its gates, the shared expert and the dense layer
are whole on the chip.  Every size is a keyword; the defaults are a toy.
"""
from .. import symbol as sym
from .glm_moe import _block, _linear

__all__ = ["get_symbol"]

KINDS = ("full_attention", "sliding_attention")

_TOY_ROPE = {
    "full_attention": {"rope_theta": 500000.0, "rope_type": "yarn",
                       "factor": 8.0, "original_max_position_embeddings": 16,
                       "beta_fast": 32.0, "beta_slow": 1.0,
                       "attention_factor": 1.2, "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_theta": 10000.0, "rope_type": "default",
                          "partial_rotary_factor": 1.0}}


def _rotary(u, rope, n, name):
    dim = int(n * rope.get("partial_rotary_factor", 1.0))
    kw = {}
    if rope.get("rope_type", "default") == "yarn":
        kw = dict(rope_type="yarn", factor=float(rope["factor"]),
                  original_max_position=int(
                      rope["original_max_position_embeddings"]),
                  beta_fast=float(rope.get("beta_fast", 32.0)),
                  beta_slow=float(rope.get("beta_slow", 1.0)),
                  attention_factor=float(rope.get("attention_factor", 0.0)))
    return sym.RotaryEmbedding(u, base=float(rope["rope_theta"]),
                               dim=0 if dim == n else dim, name=name, **kw)


def _attention(u, cfg, kind, h):
    """Gated grouped-query attention of ``h`` query heads over (B*T, d)
    rows, full or in a window by ``kind``; returns (B*T, d)."""
    t, h_kv, n = cfg["seq_len"], cfg["num_kv_heads"], cfg["head_dim"]
    rope = cfg["rope"][kind]

    def heads(name, count):
        return sym.Reshape(_linear(u, count * n, "attn_" + name),
                           shape=(-1, t, count, n),
                           name="attn_%s_heads" % name)

    q = _rotary(heads("q", h), rope, n, "attn_q_rope")
    k = _rotary(heads("k", h_kv), rope, n, "attn_k_rope")
    sliding = kind == "sliding_attention"
    out = sym._contrib_DotProductAttention(
        q, k, heads("v", h_kv), causal=True, scale=float(n) ** -0.5,
        name="attn_window" if sliding else "attn_attn",
        **({"window": cfg["window"]} if sliding else {}))
    gate = sym.Activation(sym.Reshape(_linear(u, h, "attn_gate"),
                                      shape=(-1, t, h, 1),
                                      name="attn_gate_heads"),
                          act_type="sigmoid", name="attn_gate_act")
    out = sym.Reshape(sym.broadcast_mul(out, gate), shape=(-1, h * n),
                      name="attn_out")
    return _linear(out, cfg["hidden"], "attn_o")


def _as_list(x, cast=str):
    return [cast(v) for v in (x.split(",") if isinstance(x, str) else x)]


def get_symbol(num_classes=512, vocab_size=None, seq_len=64, hidden_size=64,
               layer_types=("full_attention", "sliding_attention",
                            "sliding_attention"),
               heads_per_layer=(4, 8, 8), num_kv_heads=2, head_dim=16,
               sliding_window=16, rope_parameters=None, num_dense_layers=1,
               intermediate_size=160, moe_intermediate_size=32,
               shared_expert_intermediate_size=None, num_experts=16,
               experts_held=4, first_expert=0, num_experts_per_tok=4,
               routed_scaling_factor=2.5, rms_norm_eps=1e-6, **kwargs):
    """data (B, T) token ids, softmax_label (B, T) the next tokens ->
    the softmax over the held rows of the vocabulary at every position.
    ``layer_types`` names each block's attention, ``full_attention`` or
    ``sliding_attention``, and ``heads_per_layer`` its query heads (each
    a sequence, or the entries joined by commas)."""
    vocab = vocab_size or num_classes
    layer_types = _as_list(layer_types)
    heads = _as_list(heads_per_layer, int)
    unknown = sorted(set(layer_types) - set(KINDS))
    if unknown:
        raise ValueError("layer_types names %s; a layer is one of %s"
                         % (unknown, list(KINDS)))
    if len(heads) != len(layer_types) or any(h % num_kv_heads
                                             for h in heads):
        raise ValueError("heads_per_layer %s over %d layers and %d "
                         "key/value heads" % (heads, len(layer_types),
                                              num_kv_heads))
    if shared_expert_intermediate_size not in (None, moe_intermediate_size):
        raise ValueError("the shared expert is as wide as a routed one "
                         "(%d), not %d" % (moe_intermediate_size,
                                           shared_expert_intermediate_size))
    cfg = dict(seq_len=seq_len, hidden=hidden_size,
               num_kv_heads=num_kv_heads, head_dim=head_dim,
               window=sliding_window, rope=rope_parameters or _TOY_ROPE,
               dense_width=intermediate_size,
               moe_width=moe_intermediate_size, n_experts=num_experts,
               held=experts_held, first_expert=first_expert,
               top_k=num_experts_per_tok, scaling=routed_scaling_factor,
               eps=rms_norm_eps)
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data, input_dim=vocab, output_dim=hidden_size,
                      name="tok_embed")
    x = sym.Reshape(x, shape=(-1, hidden_size), name="tok_embed_rows")
    for i, (kind, h) in enumerate(zip(layer_types, heads)):
        x = _block(x, cfg, "l%d_" % i, dense=i < num_dense_layers,
                   mixer=lambda u, c, kind=kind, h=h: _attention(u, c,
                                                                 kind, h))
    logits = _linear(sym.RMSNorm(x, eps=rms_norm_eps, name="norm"), vocab,
                     "head")
    return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,),
                                                 name="label_rows"),
                             name="softmax")
