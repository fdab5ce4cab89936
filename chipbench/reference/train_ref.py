"""Drive a plain reference through its first training steps."""
import jax
import jax.numpy as jnp

from . import optim


def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for n, v in tree.items()}


def train_steps(ref, cfg, weights, batches, steps, precision="float32"):
    """``steps`` optimizer steps from ``weights`` on ``batches[k]``.
    Returns (mean loss per step, {leaf: norm of the first gradient},
    {leaf: norm of the parameters' change after the last step}) as host
    numbers."""
    opt = cfg["optimizer"]
    train = {n: w for n, w in weights.items() if ref.trainable(n)}
    frozen = {n: w for n, w in weights.items() if n not in train}
    state = {n: optim.init_state(opt["name"], w) for n, w in train.items()}

    @jax.jit
    def step(train, state, batch, t):
        def total(tr):
            per = ref.per_sample_loss(cfg, {**tr, **frozen}, batch,
                                      precision)
            return jnp.sum(per), per
        (_, per), g = jax.value_and_grad(total, has_aux=True)(train)
        n = per.shape[0]
        new_w, new_s, gn = {}, {}, {}
        for name, w in train.items():
            gi = g[name] / n
            gn[name] = jnp.sqrt(jnp.sum(jnp.square(gi)))
            new_w[name], new_s[name] = optim.update(opt, w, gi, state[name],
                                                    t)
        return jnp.mean(per), gn, new_w, new_s

    losses, first = [], None
    cur = train
    for k in range(steps):
        loss, gn, cur, state = step(cur, state, batches[k],
                                    jnp.float32(k + 1))
        losses.append(float(loss))
        if k == 0:
            first = {n: float(v) for n, v in gn.items()}
    dw = jax.jit(lambda a, b: leaf_norms({n: a[n] - b[n] for n in a}))(
        cur, train)
    return losses, first, {n: float(v) for n, v in dw.items()}
