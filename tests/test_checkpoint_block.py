"""`gluon.block.checkpoint_block`, the one way a layer is recomputed: the
four decoder cells call every layer through it (`decoder.run_layers`).
On a small stack of `GroupedQueryAttention` + MLP layers, on the CPU with
the flash kernels interpreted: the loss and every gradient with and
without it, a `custom_vjp` inside a segment, what `SAVED_BY_NAME` keeps,
the untraced call, state written through the trace's sink, and what the
compiled step holds."""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import block as gblock
from mxnet_tpu.gluon.block import checkpoint_block
from mxnet_tpu.gluon.contrib.nn import DroplessMoE, GatedMLP
from mxnet_tpu.gluon.model_zoo import decoder
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.pallas_attention import SAVED_BY_NAME

UNITS, HEADS, KV_HEADS, HEAD_DIM, SEQ = 32, 4, 2, 8, 16
SAVES = pytest.mark.parametrize(
    "save", [(), SAVED_BY_NAME], ids=["save_nothing", "saved_by_name"])


class _Layer(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        self.attn_norm = decoder.RMSNorm(UNITS)
        self.attn = decoder.GroupedQueryAttention(UNITS, HEADS, KV_HEADS,
                                                  HEAD_DIM)
        self.mlp_norm = decoder.RMSNorm(UNITS)
        self.mlp = GatedMLP(UNITS, 2 * UNITS)

    def forward(self, x, positions):
        x = x + self.attn(self.attn_norm(x), positions, causal=True)
        return x + self.mlp(self.mlp_norm(x))


class _Stack(gluon.HybridBlock):
    """``make()`` ``depth`` times; ``save`` None calls each layer plainly,
    anything else through `checkpoint_block`."""

    def __init__(self, save, depth=2, make=_Layer):
        super().__init__()
        self._save = save
        self.layers = gluon.nn.HybridSequential()
        for _ in range(depth):
            self.layers.add(make())

    def forward(self, x, *args):
        for layer in self.layers:
            x = layer(x, *args) if self._save is None \
                else checkpoint_block(layer, x, *args, save=self._save)
        return x


def _stack(save, **kw):
    mx.seed(0)
    net = _Stack(save, **kw)
    net.initialize()
    return net


def _inputs(seq=SEQ, units=UNITS):
    rs = onp.random.RandomState(1)
    return (jnp.asarray(rs.randn(2, seq, units).astype("f")),
            jnp.arange(seq, dtype=jnp.int32))


def _step(net, weight=1.0):
    """(params, *inputs) -> ((loss, the state the step wrote), gradients)
    as one traced program — the inputs are the trace's, as a whole step's
    are — and the parameters."""
    fn, params = net.as_pure_function(training=True)
    train = net.trainable_param_names()

    def total(p, *inputs):
        out, new = fn(p, jax.random.PRNGKey(0), *inputs)
        return jnp.sum(jnp.square(out)) * weight, \
            {n: v for n, v in new.items() if n not in train}

    def step(p, *inputs):
        got, grads = jax.value_and_grad(total, has_aux=True)(p, *inputs)
        return got, {n: grads[n] for n in train}

    return jax.jit(step), params


@pytest.fixture
def interpreted(monkeypatch):
    """`decoder.attend` on the flash kernels, interpreted."""
    monkeypatch.setattr(pa, "flash_attention", functools.partial(
        pa.flash_attention, interpret=True))
    pa._plan.cache_clear(), pa._shared.cache_clear()
    yield
    pa._plan.cache_clear(), pa._shared.cache_clear()


def _pallas_names(jaxpr):
    """Names of the pallas_call equations of a jaxpr, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_pallas_names(inner))
    return out


@SAVES
def test_loss_and_every_gradient_are_those_of_the_plain_stack(interpreted,
                                                              save):
    inputs = _inputs()
    step, params = _step(_stack(None))
    (want, _), want_grads = step(params, *inputs)
    step, params = _step(_stack(save))
    (loss, _), grads = step(params, *inputs)
    onp.testing.assert_allclose(loss, want, rtol=1e-6)
    assert set(grads) == set(want_grads) and len(grads) == 2 * 11
    for name, g in grads.items():
        assert float(jnp.abs(g).max()) > 0, name
        onp.testing.assert_allclose(g, want_grads[name], rtol=1e-5,
                                    atol=1e-6, err_msg=name)


class _ConstantGrad(gluon.HybridBlock):
    """Dense -> BatchNorm -> make_loss: BatchNorm's closed-form backward
    and make_loss's, which IGNORES the cotangent it is handed, are
    `custom_vjp` rules: a recomputation that differentiated the primal
    instead would follow the cotangent."""

    def __init__(self):
        super().__init__()
        self.dense = gluon.nn.Dense(UNITS, flatten=False, in_units=UNITS,
                                    use_bias=False)
        self.bn = gluon.nn.BatchNorm(axis=-1, in_channels=UNITS)

    def forward(self, x):
        return mx.nd.make_loss(self.bn(self.dense(x)), grad_scale=3.0)


@SAVES
def test_a_custom_vjp_inside_the_segment_keeps_its_own_backward(save):
    x, _ = _inputs()
    grads = {}
    for name, net, weight in (
            ("plain", _stack(None, depth=1, make=_ConstantGrad), 1.0),
            ("segment", _stack(save, depth=1, make=_ConstantGrad), 1.0),
            ("segment x 100", _stack(save, depth=1, make=_ConstantGrad),
             100.0)):
        step, params = _step(net, weight=weight)
        grads[name] = step(params, x)[1]
    for name, g in grads["plain"].items():
        assert float(jnp.abs(g).max()) > 0, name
        # make_loss's rule: the gradient does not see the loss's weight
        for other in ("segment", "segment x 100"):
            onp.testing.assert_allclose(grads[other][name], g, rtol=1e-5,
                                        atol=1e-6, err_msg=name)


@pytest.mark.parametrize("save,forward_calls", [((), 2), (SAVED_BY_NAME, 1)],
                         ids=["save_nothing", "saved_by_name"])
def test_the_backward_replays_the_forward_kernel_only_where_nothing_is_kept(
        interpreted, save, forward_calls):
    """A layer's segment holds one forward flash call; the backward runs
    it a second time unless the kernel's two results are kept by name."""
    step, params = _step(_stack(save))
    names = _pallas_names(jax.make_jaxpr(step)(params, *_inputs()).jaxpr)
    assert names.count("flash_attention_fwd") == 2 * forward_calls
    assert names.count("flash_attention_bwd") == 2


def test_untraced_it_is_a_plain_call(monkeypatch):
    calls = []

    def block(*args):
        calls.append(args)
        return args[0] * 2

    def no_segment(*a, **kw):
        raise AssertionError("no program is traced: nothing to checkpoint")

    monkeypatch.setattr(jax, "checkpoint", no_segment)
    x = NDArray(jnp.ones((2, 3)))
    out = checkpoint_block(block, x, "static", save=SAVED_BY_NAME)
    assert len(calls) == 1 and calls[0][0] is x and calls[0][1] == "static"
    onp.testing.assert_array_equal(out.asnumpy(), 2 * onp.ones((2, 3)))


class _Normed(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        self.bn = gluon.nn.BatchNorm(axis=-1, in_channels=UNITS)
        self.mlp = GatedMLP(UNITS, 2 * UNITS)

    def forward(self, x):
        return x + self.mlp(self.bn(x))


def _sparse():
    return DroplessMoE(UNITS, 2 * UNITS, num_experts=4, top_k=2)


@pytest.mark.parametrize("make,written", [
    (_Normed, ["bn.running_mean", "bn.running_var"]),
    (_sparse, ["running_load"]),
], ids=["batchnorm", "running_load"])
def test_state_written_in_a_segment_is_recorded_once_outside_it(
        monkeypatch, make, written):
    """What a block writes through the trace's sink leaves the segment as
    outputs: the trace's own sink holds each parameter once, with the
    value the plain stack gives, and nothing of the segment's trace."""
    x, _ = _inputs()
    step, params = _step(_stack(None, make=make))
    (_, want), _ = step(params, x)
    net = _stack((), make=make)
    names = {id(p): n for n, p in net.collect_params().items()}
    records = []
    record = gblock._StateSink.record

    def spy(self, param, value):
        records.append((self, names[id(param)]))
        record(self, param, value)

    monkeypatch.setattr(gblock._StateSink, "record", spy)
    step, params = _step(net)
    (_, state), _ = step(params, x)
    expect = sorted(f"layers.{i}.{w}" for i in range(2) for w in written)
    # each write twice: inside its segment, then once in the trace's sink
    outer = records[-1][0]
    assert sorted(n for s, n in records if s is outer) == expect
    assert sorted(n for s, n in records if s is not outer) == expect
    for name in expect:
        assert float(jnp.abs(state[name] - params[name]).max()) > 0, name
        onp.testing.assert_allclose(state[name], want[name], rtol=1e-6,
                                    err_msg=name)


def test_a_four_layer_step_holds_one_layers_activations_at_a_time():
    """What the segments are for.  Between the forward and the backward
    the step keeps each layer's input and not what the layer computed
    (the residuals `jax.vjp` holds), and the step compiled for the CPU
    has fewer temporary bytes (`memory_analysis()`; the CPU's plan packs
    little, so the margin there is small)."""
    inputs = _inputs(seq=256)

    def held(save):
        net = _stack(save, depth=4)
        fn, params = net.as_pure_function(training=True)
        kept = []

        def forward(p, *inputs):
            out, back = jax.vjp(
                lambda p: fn(p, jax.random.PRNGKey(0), *inputs)[0], p)
            kept.append(sum(r.size * r.dtype.itemsize
                            for r in jax.tree_util.tree_leaves(back)))
            return out

        jax.eval_shape(forward, params, *inputs)
        step, params = _step(net)
        return kept[0], step.lower(params, *inputs).compile() \
            .memory_analysis().temp_size_in_bytes

    (plain_kept, plain_temp), (kept, temp) = held(None), held(())
    layer_inputs = 4 * inputs[0].size * 4
    # the four inputs and the weights, against every layer's activations
    assert layer_inputs <= kept < 3 * layer_inputs < plain_kept / 8
    assert 0 < temp < plain_temp
