"""The reference's side of ``correct``: the first steps of training in
plain float32, and the numbers that are compared.

Nothing here imports the program.  ``ref`` is a module of
``benchmark/reference/``; the optimizer is momentum SGD written out.
``cast`` turns the same code into the lower-precision control and
``fault`` plants one of the faults a training cell can have.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3
# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone and is left out of the change
DEAD_GRADIENT = 1e-3
# the weights of convolutions and matrix products have this many
# elements and more; the leaves of normalizations and biases have fewer
# (GPT-2 medium's widest bias, the head's, apart)
BIG_LEAF = 4096


def _grid(x, mantissa, min_exp, top):
    """x rounded to a float8 grid under one scale for the whole tensor,
    the way fp8 training recipes do it.  The rounding is written out in
    float32 arithmetic (``mantissa`` explicit bits, normal exponents
    from ``min_exp``, largest value ``top``), so that it is the same on
    every backend and can never overflow into a NaN or an infinity."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    y = x / s
    _, e = jnp.frexp(y)                       # |y| = m * 2**e, m in [.5, 1)
    quantum = jnp.ldexp(jnp.float32(1), jnp.maximum(e - 1, min_exp)
                        - mantissa)           # an exact power of two
    return jnp.clip(jnp.round(y / quantum) * quantum, -top, top) * s


def e4m3(x):
    return _grid(x, 3, -6, 448.0)


def e5m2(x):
    return _grid(x, 2, -14, 57344.0)


@jax.custom_vjp
def fp8(x):
    """The control's precision, the nearest below bfloat16, as float8
    training computes a product: the references apply this to both
    operands, so what enters a product is rounded to float8 e4m3 on the
    way forward and the cotangent that comes back to each operand to
    float8 e5m2; accumulation and the product's result in float32."""
    return e4m3(x)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, g: (e5m2(g),))


CASTS = {None: None, "fp8": fp8}


def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for n, v in tree.items()}


def _grad_blocks(ref, cfg, cast, params, aux, data, label, row_block):
    """Loss, new auxiliary state and gradient of the mean loss over the
    batch; in blocks of rows where rows do not depend on one another."""
    vg = jax.value_and_grad(
        lambda p, d, l: ref.loss(cfg, p, aux, d, l, cast), has_aux=True)
    n = data.shape[0]
    if not row_block or row_block >= n:
        return vg(params, data, label)
    k = n // row_block
    data = data.reshape((k, row_block) + data.shape[1:])
    label = label.reshape((k, row_block) + label.shape[1:])

    def body(carry, xs):
        (loss, new_aux), g = vg(params, *xs)
        tot, acc = carry
        return (tot + loss / k,
                jax.tree.map(lambda a, b: a + b / k, acc, g)), new_aux

    zero = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), new_aux = jax.lax.scan(body, (jnp.float32(0), zero),
                                          (data, label))
    return (loss, jax.tree.map(lambda a: a[-1], new_aux)), grads


def make_step(ref, cfg, opt, cast=None, row_block=None):
    """One jitted step of momentum SGD on the reference's loss."""
    lr, mu, wd = opt["learning_rate"], opt["momentum"], opt.get("wd", 0.0)

    def step(params, aux, mom, data, label):
        (loss, new_aux), grads = _grad_blocks(
            ref, cfg, cast, params, aux, data, label, row_block)
        new_p, new_m = {}, {}
        for n in params:
            g = grads[n]
            if wd and n.endswith(("_weight", "_gamma")):
                g = g + wd * params[n]
            new_m[n] = mu * mom[n] - lr * g
            new_p[n] = params[n] + new_m[n]
        return new_p, new_aux, new_m, loss, leaf_norms(grads)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def alter(fault, data, label):
    """The batch as a faulty program would see it."""
    if fault == "half_batch":          # half left out, mean over the rest
        n = data.shape[0]
        return data[: n // 2], label[: n // 2]
    return data, label


def run(ref, cfg, opt, init_fn, key, batches, cast=None, fault=None,
        row_block=None):
    """Three steps from the seed.  ``batches`` yields (data, label) of
    int32 labels.  Returns host numbers: the three losses, the first
    gradient's norm and the change's norm, leaf by leaf."""
    if fault == "half_batch":
        row_block = None if row_block is None else max(1, row_block // 2)
    step = make_step(ref, cfg, opt, CASTS[cast], row_block)
    params, aux = init_fn(key)
    sizes = {n: int(v.size) for n, v in params.items()}
    mom = jax.tree.map(jnp.zeros_like, params)
    losses, grad = [], None
    for i in range(STEPS):
        data, label = alter(fault, *batches(i))
        if fault == "state_unchanged":
            # the step computes and returns its state as it got it
            keep = jax.tree.map(jnp.copy, (params, aux, mom))
            _, _, _, loss, g = step(params, aux, mom, data, label)
            params, aux, mom = keep
        else:
            params, aux, mom, loss, g = step(params, aux, mom, data, label)
        losses.append(loss)
        if i == 0:
            grad = g
    change = change_norms(params, init_fn, key)
    out = jax.device_get((losses, grad, change))
    del params, aux, mom
    return {"loss": [float(x) for x in out[0]],
            "grad": {n: float(v) for n, v in out[1].items()},
            "change": {n: float(v) for n, v in out[2].items()},
            "size": sizes}


@functools.partial(jax.jit, static_argnums=(1,))
def change_norms(params, init_fn, key):
    """Norm of params - init(key), leaf by leaf; the initial values are
    made again from the seed, not kept."""
    start, _ = init_fn(key)
    return leaf_norms({n: params[n] - start[n] for n in params})


# ----------------------------------------------------------------------
def compare(got, want):
    """The numbers ``correct`` rests on: ``got`` against the reference's
    ``want``, both as :func:`run` returns them.

    A gap is between norms, not the norm of a difference, and is
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger.  ``grad_norm_gap`` and
    ``change_norm_gap`` are the worst leaf's and ``*_median`` the median
    leaf's; ``*_big`` and ``*_big_median`` are the same over the leaves
    of ``BIG_LEAF`` elements and more."""
    out = {}
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"])):
        out["loss_gap_step%d" % (i + 1)] = abs(a - b) / abs(b)
    g_med = float(np.median(list(want["grad"].values())))
    live = [n for n, v in want["grad"].items() if v >= DEAD_GRADIENT * g_med]
    for what, leaves in (("grad", list(want["grad"])), ("change", live)):
        ref = want[what]
        med = float(np.median(list(ref.values())))
        gaps = {n: abs(got[what][n] - ref[n]) / max(ref[n], med)
                for n in leaves}
        big = [g for n, g in gaps.items() if want["size"][n] >= BIG_LEAF]
        worst = max(gaps, key=gaps.get)
        out[what + "_norm_gap"], out[what + "_worst_leaf"] = gaps[worst], worst
        out[what + "_norm_gap_median"] = float(np.median(list(gaps.values())))
        out[what + "_norm_gap_big"] = max(big)
        out[what + "_norm_gap_big_median"] = float(np.median(big))
    out["leaves_left_out"] = sorted(set(want["grad"]) - set(live))
    return out
