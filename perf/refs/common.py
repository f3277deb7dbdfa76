"""What every plain reference shares: seeds, the optimizers' arithmetic as
the configuration states it, the per-leaf norms that `correct` compares, and
the lower-precision arithmetic of the control.

Plain ``jax.numpy`` in float32 with matmuls at ``highest`` precision.  Nothing
here imports the program (``mxnet_tpu``) or takes anything the program made.
"""
import functools
import math

import jax
import jax.numpy as jnp

#: the control's arithmetic for a configuration that states bfloat16 compute:
#: the nearest step below it
CONTROL = "fp8"


def key_from_seed(seed):
    """A PRNG key from any whole number up to a little over 2**31 and beyond:
    the low 31 bits seed the key, the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def init_from_spec(spec, seed):
    """Every leaf of ``spec`` ([(name, shape, init, served dtype)]; ``init``
    is "ones", "zeros", ("const", value) or a normal deviation) from the
    seed, on the device, in one jitted call, in the type it is served in."""

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, init, dtype) in enumerate(spec):
            if isinstance(init, tuple):
                a = jnp.full(shape, init[1], jnp.float32)
            elif init == "ones":
                a = jnp.ones(shape, jnp.float32)
            elif init == "zeros":
                a = jnp.zeros(shape, jnp.float32)
            else:
                a = init * jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32)
            out[name] = a.astype(dtype)
        return out

    return make(key_from_seed(seed))


def _cast(x, dtype, scaled_to=None):
    """``x`` rounded to ``dtype`` and back: a plain cast, as the program's
    own bfloat16 is, or (``scaled_to`` the format's largest number) under a
    per-tensor scale."""
    if scaled_to is None:
        return x.astype(dtype).astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / scaled_to
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


#: precision -> (operand dtype, gradient dtype, the two formats' largest
#: numbers where each tensor is scaled to them, stored-result dtype)
_LOW = {
    "bfloat16": (jnp.bfloat16, jnp.bfloat16, None, None, jnp.bfloat16),
    "fp8": (jnp.float8_e4m3fn, jnp.float8_e5m2, None, None,
            jnp.float8_e4m3fn),
    "fp8_scaled": (jnp.float8_e4m3fn, jnp.float8_e5m2, 448.0, 57344.0, None),
}


def operand(x, precision):
    """An operand of a matrix multiplication or convolution, in the arithmetic
    that ``precision`` names.  ``float32``: as it is.  Otherwise rounded to the
    precision's operand type (its own gradient passes straight through):
    ``fp8`` is the control, a plain cast to e4m3 one step below the bfloat16
    the configurations state, as the program's bfloat16 is a plain cast (see
    ``result`` for what it stores);
    ``fp8_scaled`` the same under a per-tensor scale; ``bfloat16`` a witness
    of the precision the configurations state."""
    if precision == "float32":
        return x
    dtype, _, top, _, _ = _LOW[precision]
    return x + jax.lax.stop_gradient(_cast(x, dtype, top) - x)


@functools.lru_cache(maxsize=None)
def _low_result(precision):
    _, gdtype, _, gtop, stored = _LOW[precision]

    @jax.custom_vjp
    def f(y):
        return y if stored is None else _cast(y, stored)

    def fwd(y):
        return f(y), None

    def bwd(_, g):
        return (_cast(g, gdtype, gtop),)

    f.defvjp(fwd, bwd)
    return f


def result(y, precision):
    """The result of a matrix multiplication or convolution.  Accumulation is
    float32 in every precision; the result is then rounded as stored
    activations are (``bfloat16`` as the program stores them, ``fp8`` to e4m3
    as a program bound in fp8 would; ``fp8_scaled`` keeps them float32).  The
    gradient that comes back through it, which is an operand of both backward
    products, is rounded to the precision's gradient type (e5m2 for fp8, the
    usual recipe)."""
    return y if precision == "float32" else _low_result(precision)(y)


def leaf_norms(tree):
    """{name: l2 norm in float32} of a dict of arrays."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


# ------------------------------------------------------------- optimizers
# The arithmetic MXNet's optimizers state (python/mxnet/optimizer.py): the
# gradient the optimizer gets is ``g * rescale_grad + wd * w``.
def effective_grad(w, g, hp):
    return g * hp.get("rescale_grad", 1.0) + hp.get("wd", 0.0) * w


def init_state(name, params):
    if name == "sgd":
        return {"mom": {k: jnp.zeros_like(v) for k, v in params.items()}}
    if name == "adam":
        return {"m": {k: jnp.zeros_like(v) for k, v in params.items()},
                "v": {k: jnp.zeros_like(v) for k, v in params.items()}}
    raise ValueError("no reference arithmetic for optimizer %r" % (name,))


def apply_update(name, hp, params, grads, state, t):
    """One update of every leaf, step count ``t`` from 1.  Returns
    (params, state, effective gradient per leaf)."""
    lr = hp["learning_rate"]
    geff = {k: effective_grad(params[k], grads[k], hp) for k in params}
    if name == "sgd":
        mu = hp.get("momentum", 0.0)
        mom = {k: mu * state["mom"][k] - lr * geff[k] for k in params}
        return ({k: params[k] + mom[k] for k in params}, {"mom": mom}, geff)
    if name == "adam":
        b1, b2 = hp.get("beta1", 0.9), hp.get("beta2", 0.999)
        eps = hp.get("epsilon", 1e-8)
        lr_t = lr * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        m = {k: b1 * state["m"][k] + (1 - b1) * geff[k] for k in params}
        v = {k: b2 * state["v"][k] + (1 - b2) * jnp.square(geff[k])
             for k in params}
        new = {k: params[k] - lr_t * m[k] / (jnp.sqrt(v[k]) + eps)
               for k in params}
        return new, {"m": m, "v": v}, geff
    raise ValueError("no reference arithmetic for optimizer %r" % (name,))


def first_grad_from_state(name, hp, state_leaf_norm):
    """The norm of the first effective gradient, worked out from the norm of
    the optimizer's state after one step (momentum, or Adam's mean)."""
    if name == "sgd":
        return state_leaf_norm / hp["learning_rate"]
    if name == "adam":
        return state_leaf_norm / (1.0 - hp.get("beta1", 0.9))
    raise ValueError("no reference arithmetic for optimizer %r" % (name,))
