"""A looped decoder (model type ``ouro``) on the Gluon path, at a small size
on the CPU: the whole model against the benchmark's plain reference, the
stack run several times on shared weights as one rolled loop, the exit
gate's distribution and objective, the head by blocks of positions, the
norm-free query / key preparation, and the whole step with its gauges and
scopes."""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.contrib.nn import GatedMLP
from mxnet_tpu.gluon.model_zoo import decoder
from mxnet_tpu.gluon.model_zoo.ouro import ExitLoss
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.ops import pallas_qk_prep as qp
from mxnet_tpu.telemetry import instruments as ti

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's plain reference, builder and weights."""
    sys.path.insert(0, BENCH)
    try:
        import weights as wmod
        from models import ouro as model
        from reference import ouro as ref
        yield ref, wmod, model
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def toy(bench):
    ref, wmod, _ = bench
    with open(os.path.join(BENCH, "configs", "toy_ouro.json")) as f:
        cfg = json.load(f)
    weights = wmod.make_weights(ref.param_specs(cfg), 7, "float32")
    batch = wmod.make_batches(ref.input_specs(cfg, 2), 7, 1)[0]
    return cfg, weights, batch


def _net(bench, cfg, weights, remat=False, dtype="float32", **changed):
    return bench[2].build(mx, dict(cfg, remat=remat, dtype=dtype, **changed),
                          weights, mx.cpu())


def _close(got, want, atol=2e-4, msg=""):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    onp.testing.assert_allclose(onp.asarray(got) / scale,
                                onp.asarray(want) / scale, atol=atol,
                                err_msg=msg)


def _pure_loss(net, batch, keep=lambda n: not n.endswith("running_exit_mass")):
    """(train, total): ``total(train)`` = (sum of the per-sequence losses,
    the losses) of the net as a pure function of its trained leaves."""
    fn, params = net.as_pure_function(training=True)
    train = {n: v for n, v in params.items() if keep(n)}
    frozen = {n: v for n, v in params.items() if n not in train}

    def total(tr):
        per, _ = fn({**tr, **frozen}, jax.random.PRNGKey(0), *batch)
        return jnp.sum(per), per

    return train, total


# -- the whole model against the plain reference ----------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_every_gradient_match_the_plain_reference(bench, toy,
                                                           remat):
    ref = bench[0]
    cfg, weights, batch = toy
    train, total = _pure_loss(_net(bench, cfg, weights, remat), batch)
    (_, per), grads = jax.value_and_grad(total, has_aux=True)(train)
    ref_train = {n: w for n, w in weights.items() if ref.trainable(n)}
    frozen = {n: w for n, w in weights.items() if n not in ref_train}

    def ref_total(tr):
        per = ref.per_sample_loss(cfg, {**tr, **frozen}, batch)
        return jnp.sum(per), per

    (_, ref_per), ref_grads = jax.value_and_grad(
        ref_total, has_aux=True)(ref_train)
    onp.testing.assert_allclose(per, ref_per, rtol=2e-5)
    assert set(grads) == set(ref_grads)
    assert len(grads) == 2 * 11 + 5     # 2 layers, embedding, norm, head, gate
    for name, g in ref_grads.items():
        assert float(jnp.abs(g).max()) > 0, name
        _close(grads[name], g, msg=name)


def test_the_reference_imports_nothing_of_the_program(bench):
    with open(bench[0].__file__) as f:
        text = f.read()
    assert "mxnet_tpu" not in text and "lax.scan" not in text


def test_amp_keeps_the_gate_and_the_norms_float32(bench, toy):
    cfg, weights, batch = toy
    net = _net(bench, cfg, weights, dtype="bfloat16")
    kinds = {n: str(p.data().dtype) for n, p in net.collect_params().items()}
    for name, kind in kinds.items():
        keeps = any(k in name for k in ("gamma", "exit_gate", "running"))
        assert kind == ("float32" if keeps else "bfloat16"), name
    assert kinds["exit_loss.exit_gate_weight"] == "float32"
    assert net.exit_loss.running_exit_mass.grad_req == "null"
    layer = net.model.layers[0]
    assert isinstance(layer.mlp, GatedMLP)
    assert layer.self_attn.q_norm is None               # no per-head norm
    full = _net(bench, cfg, weights)(NDArray(batch[0])).asnumpy()
    low = net(NDArray(batch[0])).asnumpy()
    assert low.dtype == onp.float32 and onp.allclose(low, full, rtol=0.02)


# -- the looped stack: shared weights, one rolled loop ----------------------

class _Residual(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        self.mlp = GatedMLP(8, 12)

    def forward(self, x):
        return x + self.mlp(x)


class _Stack(gluon.HybridBlock):
    """``copies`` stacks of two layers: one stack run ``steps`` times on
    its own output (looped), or each of ``copies`` stacks run once, one
    after the other (untied), the norm after every pass either way."""

    def __init__(self, copies, steps, remat):
        super().__init__()
        self._steps, self._remat = steps, remat
        self.stacks = gluon.nn.HybridSequential()
        for _ in range(copies):
            stack = gluon.nn.HybridSequential()
            stack.add(_Residual(), _Residual())
            self.stacks.add(stack)
        self.norm = decoder.RMSNorm(8)

    def forward(self, x):
        if len(self.stacks) == 1:
            return decoder.run_looped(self.stacks[0], self._remat, x,
                                      steps=self._steps, after=self.norm)
        exits = []
        for stack in self.stacks:
            x = self.norm(decoder.run_layers(stack, self._remat, x))
            exits.append(x)
        return mx.np.stack(exits)


def _stack_grads(net, x, w):
    fn, params = net.as_pure_function(training=True)

    def total(p):
        out, _ = fn(p, jax.random.PRNGKey(0), x)
        return jnp.sum(out * w)

    return jax.value_and_grad(total)(params)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_shared_weights_gradient_is_the_sum_over_untied_copies(remat):
    rs = onp.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 5, 8).astype("f"))
    w = jnp.asarray(rs.randn(3, 2, 5, 8).astype("f"))
    looped, untied = _Stack(1, 3, remat), _Stack(3, 1, remat)
    looped.initialize(), untied.initialize()
    shared = {n: p.data() for n, p in looped.collect_params().items()}
    for name, p in untied.collect_params().items():
        head, _, rest = name.partition(".")[2].partition(".")
        p.set_data(shared["stacks.0." + rest if name.startswith("stacks")
                          else name])
    for net in (looped, untied):
        net.hybridize()
    loss, grads = _stack_grads(looped, x, w)
    want_loss, copies = _stack_grads(untied, x, w)
    onp.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for name, g in grads.items():
        if name == "norm.gamma":
            want = copies[name]
        else:
            rest = name.partition(".")[2].partition(".")[2]
            want = sum(copies[f"stacks.{c}.{rest}"] for c in range(3))
            assert float(jnp.abs(copies[f"stacks.2.{rest}"]).max()) > 0
        _close(g, want, atol=1e-5, msg=name)


def test_one_loop_step_is_the_plain_stack(bench, toy):
    """R = 1: the stack once and the final norm — what `run_layers` and
    the norm give outside any loop."""
    cfg, weights, batch = toy
    net = _net(bench, cfg, weights, total_ut_steps=1)
    tokens = NDArray(batch[0])
    positions = NDArray(jnp.arange(cfg["seq"], dtype=jnp.int32))
    exits = net.model(tokens, positions).asnumpy()
    assert exits.shape == (1, 2, cfg["seq"], cfg["hidden_size"])
    m = net.model
    plain = m.norm(decoder.run_layers(m.layers, False,
                                      m.embed_tokens(tokens), positions))
    onp.testing.assert_allclose(exits[0], plain.asnumpy(), atol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _segments(jaxpr):
    return [e for e in _eqns(jaxpr)
            if e.primitive.name in ("remat2", "checkpoint")]


@pytest.mark.parametrize("steps", [1, 4])
def test_the_traced_step_holds_one_scan_over_the_loop_steps(bench, toy,
                                                            steps):
    """One `lax.scan` of length R whose body holds the N layers once, each
    a checkpoint segment: R = 1 and R = 4 trace the same N layer bodies."""
    cfg, weights, batch = toy
    layers = cfg["num_hidden_layers"]
    for g in (ti.looped_stack_copies, ti.ut_steps):
        g.clear()
    train, total = _pure_loss(
        _net(bench, cfg, weights, remat=True, total_ut_steps=steps), batch)
    jaxpr = jax.make_jaxpr(lambda tr: total(tr)[0])(train).jaxpr
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [steps]
    assert len(_segments(scans[0].params["jaxpr"].jaxpr)) == layers
    assert len(_segments(jaxpr)) == layers
    assert (ti.looped_stack_copies.value, ti.ut_steps.value) == (1, steps)


# -- the exit gate: distribution and objective ------------------------------

def _exit_loss(weight, bias, beta=0.1, steps=4, units=6):
    block = ExitLoss(units, steps, beta)
    block.initialize()
    block.exit_gate_weight.set_data(NDArray(jnp.asarray(weight, jnp.float32)))
    block.exit_gate_bias.set_data(NDArray(jnp.asarray([bias], jnp.float32)))
    return block


def _exits_and_ce(steps=4, batch=2, seq=9, units=6, seed=0):
    rs = onp.random.RandomState(seed)
    return (jnp.asarray(rs.randn(steps, batch, seq, units).astype("f")),
            jnp.asarray(rs.rand(steps, batch, seq).astype("f") * 5))


def test_a_closed_gate_leaves_the_last_exits_plain_cross_entropy():
    """lambda = 0 at every exit: p = (0, 0, 0, 1), no entropy, and the
    loss is the mean of the last exit's cross-entropy over the S - 1
    positions that have a next token."""
    h, ce = _exits_and_ce()
    loss = _exit_loss(onp.zeros(6), -1e4)(NDArray(h), NDArray(ce)).asnumpy()
    onp.testing.assert_allclose(loss, onp.asarray(ce[-1, :, :-1]).mean(1),
                                rtol=1e-6)
    assert onp.isfinite(loss).all()


def test_the_entropy_term_against_a_count_by_hand():
    """A gate at 0: lambda = 1/2, p = (1/2, 1/4, 1/8, 1/8) everywhere."""
    h, ce = _exits_and_ce()
    p = onp.array([0.5, 0.25, 0.125, 0.125])
    entropy = -(p * onp.log(p)).sum()
    assert entropy == pytest.approx(1.75 * math.log(2))
    expected = (p[:, None, None] * onp.asarray(ce)).sum(0)[:, :-1].mean(1)
    for beta in (0.0, 0.1, 0.7):
        loss = _exit_loss(onp.zeros(6), 0.0, beta)(NDArray(h), NDArray(ce))
        onp.testing.assert_allclose(loss.asnumpy(), expected - beta * entropy,
                                    rtol=1e-6)


def test_the_exit_distribution_sums_to_one_and_reaches_the_gauge():
    h, ce = _exits_and_ce(seed=3)
    rs = onp.random.RandomState(4)
    block = _exit_loss(rs.randn(6), 0.3)
    ti._staged_exit_mass.clear()
    ti.exit_mass.clear()
    assert ti.flush_exit_mass() is None
    with mx.autograd.record():
        block(NDArray(h), NDArray(ce))
    mass = ti.flush_exit_mass()
    assert len(mass) == 4 and sum(mass) == pytest.approx(1.0, abs=1e-6)
    # by hand, from the gate's sigmoid
    lam = 1 / (1 + onp.exp(-(onp.asarray(h) @ block.exit_gate_weight.data(
    ).asnumpy() + 0.3)))
    stay = onp.cumprod(1 - lam[:-1], axis=0)
    p = onp.concatenate([lam[:1], lam[1:-1] * stay[:-1], stay[-1:]])
    onp.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    onp.testing.assert_allclose(mass, p.mean(axis=(1, 2)), rtol=1e-5)
    assert [ti.exit_mass.labels(str(t)).value for t in (1, 2, 3, 4)] == mass
    onp.testing.assert_allclose(
        block.running_exit_mass.data().asnumpy(), mass, rtol=1e-6)
    ti._staged_exit_mass.clear()
    ti.exit_mass.clear()


# -- the head by blocks of positions ----------------------------------------

@pytest.mark.parametrize("lead", [(), (3,)], ids=["one-exit", "exits"])
def test_the_blocked_head_is_the_one_block_head(monkeypatch, lead):
    rs = onp.random.RandomState(0)
    h = jnp.asarray(rs.randn(*lead, 2, 32, 8).astype("f"))
    w = jnp.asarray(rs.randn(16, 8).astype("f"))
    target = jnp.asarray(rs.randint(0, 16, (2, 32)))
    weight = jnp.asarray(rs.rand(2, 32).astype("f"))

    def ce(h):
        return decoder.token_loss(NDArray(h), NDArray(w),
                                  NDArray(target[:, :24]), "ce",
                                  positions=24)._data

    def summed(h):
        return decoder.head_loss(
            NDArray(h), NDArray(w), NDArray(target[:, :24]),
            NDArray(weight[:, :24]), "loss", positions=24)._data

    fns = [ce] if lead else [ce, summed]
    whole = [(f(h), jax.grad(lambda h: f(h).sum())(h)) for f in fns]
    assert whole[0][0].shape == lead + (2, 24)
    assert decoder._block_length(2, 24, 16) == 24
    # a block's logits at most 6 positions x 2 (x 3 exits) rows x 16 x 4 B
    rows = 2 * math.prod(lead)
    monkeypatch.setattr(decoder, "_WHOLE_LOGITS_BYTES", 0)
    monkeypatch.setattr(decoder, "_BLOCK_LOGITS_BYTES", 6 * rows * 16 * 4)
    assert decoder._block_length(rows, 24, 16) == 6
    for f, (out, grad) in zip(fns, whole):
        jaxpr = jax.make_jaxpr(f)(h).jaxpr
        scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
        assert [e.params["length"] for e in scans] == [4]
        assert len(_segments(jaxpr)) == 1
        onp.testing.assert_allclose(f(h), out, rtol=1e-5, atol=1e-6)
        onp.testing.assert_allclose(jax.grad(lambda h: f(h).sum())(h), grad,
                                    rtol=1e-5, atol=1e-6)


def test_the_block_length_is_read_off_the_shapes():
    # the two accepted decoder cells' heads stay whole ...
    assert decoder._block_length(2, 4096, 18992) == 4096
    assert decoder._block_length(2, 8192, 16032) == 8192
    # ... four exits of 8,192 positions over 49,152 rows do not: 6.4 GB of
    # float32 logits go by 32 blocks of 256 positions (201 MB each)
    assert decoder._block_length(4, 8192, 49152) == 256
    assert 4 * 4 * 256 * 49152 <= decoder._BLOCK_LOGITS_BYTES
    assert decoder._block_length(4, 30, 1 << 30) == 15     # odd: stops


# -- query / key preparation without a per-head norm ------------------------

@pytest.mark.parametrize("heads,seq,dtype", [
    (2, 24, jnp.float32), (4, 40, jnp.bfloat16)], ids=["f32", "bf16"])
def test_norm_free_preparation_is_rotary_and_transpose(monkeypatch, heads,
                                                       seq, dtype):
    """`rms_norm_rotary(x, None, ...)` through the interpreted kernels:
    `rotary_embedding` + transpose, forward, and the cotangent turned
    back, for a queries' and a keys' call of one layer."""
    monkeypatch.setattr(qp, "_kernel_mode", lambda: True)
    monkeypatch.setattr(ti, "_qk_prep_sites", [0, 0])
    rs = onp.random.RandomState(0)
    pos = jnp.arange(seq, dtype=jnp.int32) + 3
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    for n in (heads, 1):                    # queries, then fewer key heads
        x = jnp.asarray(rs.randn(2, seq, n * 128).astype("f")).astype(dtype)
        w = jnp.asarray(rs.randn(2, n, seq, 128).astype("f"))

        def plain(x):
            turned = ops_nn.rotary_embedding(
                x.reshape((2, seq, n, 128)), pos.reshape((seq, 1)), 1e6)
            return turned.transpose((0, 2, 1, 3))

        def fused(x):
            return qp.rms_norm_rotary(x, None, pos, 1e6, n)

        out = fused(x)
        assert out.shape == (2, n, seq, 128) and out.dtype == dtype
        onp.testing.assert_allclose(out.astype("f"), plain(x).astype("f"),
                                    atol=tol)
        got = jax.grad(lambda x: (fused(x).astype("f") * w).sum())(x)
        want = jax.grad(lambda x: (plain(x).astype("f") * w).sum())(x)
        assert got.shape == x.shape and got.dtype == dtype
        onp.testing.assert_allclose(got.astype("f"), want.astype("f"),
                                    atol=tol * 4)
    assert ti.qk_prep_kernel_share.value == 1.0     # both sites counted
    with pytest.raises(ValueError):
        qp.rms_norm_rotary(x, None, pos[:-1], 1e6, 1)


def test_off_the_chip_the_norm_free_op_is_its_composition(monkeypatch):
    monkeypatch.setattr(ti, "_qk_prep_sites", [0, 0])
    x = jnp.asarray(onp.random.RandomState(1).randn(2, 8, 4 * 8).astype("f"))
    pos = jnp.arange(8, dtype=jnp.int32)
    out = mx.npx.rms_norm_rotary(NDArray(x), None, NDArray(pos), 1e4, 4)
    want = ops_nn.rotary_embedding(x.reshape((2, 8, 4, 8)),
                                   pos.reshape((8, 1)), 1e4)
    onp.testing.assert_array_equal(out.asnumpy(),
                                   want.transpose((0, 2, 1, 3)))
    assert ti.qk_prep_kernel_share.value == 0.0


# -- the whole step, its gauges and its scopes ------------------------------

def test_train_step_takes_it_whole_with_gauges_and_scopes(bench, toy):
    from mxnet_tpu.diagnostics import introspect

    cfg, weights, batch = toy
    introspect.reset()
    for g in (ti.looped_stack_copies, ti.ut_steps, ti.exit_mass):
        g.clear()
    ti._staged_exit_mass.clear()
    net = _net(bench, cfg, weights, remat=True, dtype="bfloat16")
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    tokens = NDArray(batch[0])
    gate = net.exit_loss.exit_gate_weight.data().asnumpy()
    losses = [float(step(tokens).asnumpy().mean()) for _ in range(4)]
    assert step.last_path == "whole_step", step.ineligible_reason()
    assert step.jit_trace_count() == 1
    assert losses[-1] < losses[0]
    now = net.exit_loss.exit_gate_weight.data()
    assert str(now.dtype) == "float32"
    assert not onp.array_equal(gate, now.asnumpy())         # the gate learns
    assert (ti.looped_stack_copies.value, ti.ut_steps.value) == (1, 4)
    mass = ti.flush_exit_mass()
    assert len(mass) == 4 and sum(mass) == pytest.approx(1.0, abs=1e-5)
    assert max(mass) < 0.9 and min(mass) > 0.01             # the gate is live
    assert ti.step_scalar_operands.value == 4
    scopes = set()
    for (block, _), entry in introspect.compile_registry().items():
        if block == "whole_step":
            scopes.update(entry["op_scopes"].values())
    text = "\n".join(scopes)
    for name in ("ut_step/", "lm_head/", "exit_gate/", "exit_loss/",
                 "/attention/", "OuroDecoderLayer_1", "GatedMLP_mlp",
                 "GroupedQueryAttention_self_attn", "/optimizer/"):
        assert name in text, name
    # the head, the gate and the loss run outside the loop's body
    assert not [s for s in scopes if "ut_step/" in s and any(
        p in s for p in ("lm_head/", "exit_gate/", "exit_loss/"))]
    ti._staged_exit_mass.clear()
    ti.exit_mass.clear()
