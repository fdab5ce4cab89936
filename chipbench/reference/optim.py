"""Plain optimizer formulas (float32), as MXNet defines them.

``Trainer.step(batch)`` differentiates the SUM of the per-sample losses
and rescales the gradient by 1/batch; weight decay enters the gradient.
"""
import jax.numpy as jnp


def init_state(name, w):
    if name == "sgd":
        return jnp.zeros_like(w)
    if name == "adam":
        return (jnp.zeros_like(w), jnp.zeros_like(w))
    raise KeyError(f"no plain formula for optimizer {name!r}")


def update(opt, w, g, state, t):
    """One step of ``opt`` (the configuration's optimizer group) on one
    leaf; ``g`` is already rescaled by 1/batch; ``t`` counts from 1."""
    lr, wd = opt["learning_rate"], opt.get("wd", 0.0)
    g = g + wd * w
    if opt["name"] == "sgd":
        mom = opt.get("momentum", 0.0) * state - lr * g
        return w + mom, mom
    if opt["name"] == "adam":
        b1, b2 = opt.get("beta1", 0.9), opt.get("beta2", 0.999)
        eps = opt.get("epsilon", 1e-8)
        m, v = state
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        lr_t = lr * (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)
        return w - lr_t * m / (jnp.sqrt(v) + eps), (m, v)
    raise KeyError(f"no plain formula for optimizer {opt['name']!r}")


def first_gradient(opt, w0, state1):
    """The first step's gradient as the optimizer received it (rescaled,
    before weight decay), recovered from its state after that step."""
    wd = opt.get("wd", 0.0)
    if opt["name"] == "sgd":
        return -state1 / opt["learning_rate"] - wd * w0
    if opt["name"] == "adam":
        return state1[0] / (1 - opt.get("beta1", 0.9)) - wd * w0
    raise KeyError(f"no plain formula for optimizer {opt['name']!r}")
