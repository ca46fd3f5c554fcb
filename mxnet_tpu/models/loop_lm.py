"""Looped decoder-only language model: one stack of layers run
``loop_steps`` times on its own output, on one set of weights, with an
output head and a learned exit gate after every pass (the looped
language model of arXiv:2510.25741).

Rows are positions, every projection without bias, U = ``loop_steps``,
N = ``num_layers``:

* h^0 = E[x].  Block l, the same weights in every pass ("sandwich"
  normalization: a norm before and after each half):
  a = RMSNorm_1(h); q, k, v = a W_q, a W_k, a W_v as ``num_heads`` heads
  of ``head_dim``; rotary embedding (rotate-half) over all of q's and
  k's head; o = softmax_causal(q k^T / sqrt(head_dim)) v;
  h <- h + RMSNorm_2(o W_o);
  m = RMSNorm_3(h); h <- h + RMSNorm_4((silu(m W_g) * m W_u) W_d).
* Pass t = 1..U: h^t = RMSNorm_f(Block_N(... Block_1(h^{t-1}))): the
  final norm is inside the loop and the normed state is what the next
  pass starts from; z^t = h^t W_head^T; l_t = -log softmax(z^t)[y] a
  position; lambda_t = sigmoid(h^t . w_exit / sqrt(d) + b_exit) a
  position, for t < U, in float32 whatever the compute type.  (The
  1 / sqrt(d) is a parametrization, not another unit: a step of SGD at
  rate r on w_exit moves the logit by r times its gradient, and by
  r d times that without it.  Seed w_exit small, so that the gates
  start near one half: at a logit of deviation 1 the first steps'
  shift of h, which every position shares, swung them to 0 or 1
  within three steps, PERF.md PR 37.)
* Exit distribution a position: S_0 = 1, S_t = prod_{j<=t} (1 -
  lambda_j), p_t = lambda_t S_{t-1} for t < U, p_U = S_{U-1}.
* Loss: the mean over positions of sum_t p_t l_t - beta H(p), H(p) =
  -sum_t p_t log p_t (an expected loss under the exit distribution with
  an entropy term against a uniform prior).  The gradient flows through
  p into the gate and through every h^t into the shared weights.

Every parameter is one ``Variable`` handed to the U nodes that use it
(``l<i>_..._weight`` / ``_gamma``, ``norm_gamma``, ``head_weight``,
``exit_weight``, ``exit_bias``; ``tok_embed_weight`` once); node names
say which pass they belong to: ``u<t>_l<i>_...`` for a block's nodes
(``u<t>_l<i>_attn_attn`` the attention), ``u<t>_norm``, ``u<t>_exit_...``
for a pass's head, row loss and gate, ``exit_loss...`` for the
combination.  With ``segments`` the nodes of pass t carry
``executor.SEGMENT_ATTR`` "u<t>", and the executor evaluates every run
of them as one recomputation segment: a pass's blocks and final norm
(the last pass's with its head), and, further down the walk, which
reaches them from the loss, a pass's gate, and its head and row loss.
Between the forward and the backward pass the U carried states live,
and the last pass's logits, which output 0 reads; no other pass's
logits outlive its own row loss.

Outputs: the last pass's softmax with its gradient blocked, named
``softmax_output`` (what a metric is shown), then ``MakeLoss`` of the
*sum* over positions of sum_t p_t l_t - beta H(p): an optimizer's
``rescale_grad`` of 1 / positions makes it the mean.

Every size is a keyword; the defaults are a toy.  Built from
``glm_moe.py``'s ``_linear`` and ``_gated_ffn``.
"""
import contextlib

from .. import attribute as _attribute
from .. import name as _name
from .. import symbol as sym
from ..executor import SEGMENT_ATTR
from .glm_moe import _gated_ffn, _linear

__all__ = ["get_symbol"]

_TINY = 1e-30         # log(p + _TINY): p log p is 0 at p = 0, not NaN


def _weights(num_layers):
    """The stack's Variables, made once: block -> {name: Variable}."""
    names = ("norm1_gamma", "attn_q_weight", "attn_k_weight",
             "attn_v_weight", "attn_o_weight", "norm2_gamma", "norm3_gamma",
             "mlp_gate_weight", "mlp_up_weight", "mlp_down_weight",
             "norm4_gamma")
    return [{n: sym.Variable("l%d_%s" % (i, n)) for n in names}
            for i in range(num_layers)]


def _block(x, w, cfg, prefix):
    """One sandwich-norm block on (B*T, d) rows with the weights ``w``."""
    t, h, hd, eps = cfg["seq_len"], cfg["heads"], cfg["head_dim"], cfg["eps"]
    with _name.Prefix(prefix):
        a = sym.RMSNorm(x, gamma=w["norm1_gamma"], eps=eps, name="norm1")
        q, k, v = (sym.Reshape(_linear(a, h * hd, "attn_" + n,
                                       w["attn_%s_weight" % n]),
                               shape=(-1, t, h, hd), name="attn_%s4" % n)
                   for n in "qkv")
        q = sym.RotaryEmbedding(q, base=cfg["rope_theta"],
                                name="attn_q_rope")
        k = sym.RotaryEmbedding(k, base=cfg["rope_theta"],
                                name="attn_k_rope")
        o = sym._contrib_DotProductAttention(
            q, k, v, causal=True, scale=float(hd) ** -0.5, name="attn_attn")
        o = _linear(sym.Reshape(o, shape=(-1, h * hd), name="attn_out"),
                    cfg["hidden"], "attn_o", w["attn_o_weight"])
        x = x + sym.RMSNorm(o, gamma=w["norm2_gamma"], eps=eps, name="norm2")
        m = sym.RMSNorm(x, gamma=w["norm3_gamma"], eps=eps, name="norm3")
        f = _gated_ffn(m, cfg["width"], cfg["hidden"], "mlp_",
                       (w["mlp_gate_weight"], w["mlp_up_weight"],
                        w["mlp_down_weight"]))
        return x + sym.RMSNorm(f, gamma=w["norm4_gamma"], eps=eps,
                               name="norm4")


def get_symbol(num_classes=512, vocab_size=None, seq_len=32, hidden_size=64,
               num_layers=2, num_heads=2, head_dim=32, intermediate_size=160,
               loop_steps=4, exit_beta=0.1, rope_theta=1e6,
               rms_norm_eps=1e-6, segments=True, **kwargs):
    """data (B, T) token ids, softmax_label (B, T) the next tokens ->
    [the last pass's softmax over the vocabulary at every position
    (gradient blocked), the sum over positions of the expected loss
    under the exit distribution less ``exit_beta`` times its entropy].
    ``segments`` marks the nodes of every pass for recomputation."""
    vocab = vocab_size or num_classes
    steps = int(loop_steps)
    if steps < 1:
        raise ValueError("loop_steps is 1 or more, got %r" % (loop_steps,))
    cfg = dict(seq_len=seq_len, hidden=hidden_size, heads=num_heads,
               head_dim=head_dim, width=intermediate_size,
               rope_theta=rope_theta, eps=rms_norm_eps)
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    stack = _weights(num_layers)
    norm_g, head_w = sym.Variable("norm_gamma"), sym.Variable("head_weight")
    # (shapes given: none is inferred backward through a Cast)
    exit_w = sym.Variable("exit_weight", shape=(1, hidden_size))
    exit_b = sym.Variable("exit_bias", shape=(1,))

    x = sym.Embedding(data, input_dim=vocab, output_dim=hidden_size,
                      name="tok_embed")
    x = sym.Reshape(x, shape=(-1, hidden_size), name="tok_embed_rows")
    row_losses, gates = [], []

    def segment(name):
        return _attribute.AttrScope(**{SEGMENT_ATTR: name}) if segments \
            else contextlib.nullcontext()

    for t in range(1, steps + 1):
        with segment("u%d" % t):
            for i, w in enumerate(stack):
                x = _block(x, w, cfg, "u%d_l%d_" % (t, i))
            with _name.Prefix("u%d_" % t):
                x = sym.RMSNorm(x, gamma=norm_g, eps=rms_norm_eps,
                                name="norm")
                logits = _linear(x, vocab, "exit_head", head_w)
                row_losses.append(sym.Reshape(
                    sym._contrib_RowCrossEntropy(
                        logits, sym.Reshape(label, shape=(-1,),
                                            name="exit_label"),
                        name="exit_rowloss"),
                    shape=(-1, 1), name="exit_rowloss_col"))
                if t < steps:
                    # in float32 whatever the compute type: the loss's
                    # weights hang on this one number a position
                    gate = sym.FullyConnected(
                        sym.Cast(x, dtype="float32", name="exit_gate_in")
                        * float(hidden_size) ** -0.5,
                        weight=sym.Cast(exit_w, dtype="float32",
                                        name="exit_weight32"),
                        bias=sym.Cast(exit_b, dtype="float32",
                                      name="exit_bias32"),
                        num_hidden=1, name="exit_gate")
                    gates.append(sym.Activation(gate, act_type="sigmoid",
                                                name="exit_lambda"))
    # what a metric is shown: no gradient passes it, so it is in no
    # segment, whose backward pass would run it again for nothing
    probs = sym.BlockGrad(sym.softmax(logits, name="u%d_exit_prob" % steps),
                          name="softmax")

    with _name.Prefix("exit_loss_"):
        if gates:
            dist = sym._contrib_ExitDistribution(
                sym.Concat(*gates, dim=1, name="gates"), name="dist")
            losses = sym.Concat(*row_losses, dim=1, name="rows")
            # sum_t p_t l_t - beta H(p) = sum_t p_t (l_t + beta log p_t)
            rows = dist * (losses + exit_beta * sym.log(dist + _TINY,
                                                        name="logp"))
        else:
            rows = row_losses[0]
        total = sym.sum(rows, name="sum")
    return sym.Group([probs, sym.MakeLoss(total, name="exit_loss")])
