"""Drive a plain reference through its first training steps where its
state no longer fits twice: `train_ref.train_steps` keeps the weights it
was given, the weights it trains and both of Adam's moments side by side
(4 x 4 bytes a parameter, and gradients on top).  Here the seeded weights
are made when they are needed and made again for the comparison at the
end, the gradient step and the optimizer are two programs, the
optimizer updates one leaf at a time into donated buffers, and its state
waits on the host while a gradient step runs.  The numbers
are `train_ref.train_steps`' numbers."""
import functools

import jax
import jax.numpy as jnp

from . import optim


def train_steps(ref, cfg, make_weights, batches, steps, precision="float32"):
    """``steps`` optimizer steps from ``make_weights()`` on
    ``batches[k]``.  Returns (mean loss per step, {leaf: norm of the
    first gradient}, {leaf: norm of the parameters' change after the last
    step}) as host numbers."""
    opt = cfg["optimizer"]
    weights = make_weights()
    train = {n: w for n, w in weights.items() if ref.trainable(n)}
    frozen = {n: w for n, w in weights.items() if n not in train}
    del weights
    state = {}      # host copies: the moments wait there between steps

    @jax.jit
    def loss_and_grads(train, frozen, batch):
        def total(tr):
            per = ref.per_sample_loss(cfg, {**tr, **frozen}, batch,
                                      precision)
            return jnp.sum(per), per
        (_, per), g = jax.value_and_grad(total, has_aux=True)(train)
        return jnp.mean(per), g

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def update(w, s, g, t, n):
        gi = g / n
        new_w, new_s = optim.update(opt, w, gi, s, t)
        return new_w, new_s, jnp.sqrt(jnp.sum(jnp.square(gi)))

    losses, first = [], None
    for k in range(steps):
        loss, g = loss_and_grads(train, frozen, batches[k])
        losses.append(float(loss))
        n = jnp.float32(batches[k][0].shape[0])
        norms = {}
        for name in list(train):
            s = (jax.device_put(state[name]) if name in state
                 else optim.init_state(opt["name"], train[name]))
            train[name], s, norms[name] = update(
                train[name], s, g.pop(name), jnp.float32(k + 1), n)
            # off the device while the next step's activations need it
            state[name] = jax.device_get(s)
        if k == 0:
            first = {name: float(v) for name, v in norms.items()}
    del state, g
    w0 = make_weights()
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    dw = {name: float(norm(train[name], w0[name])) for name in train}
    return losses, first, dw
