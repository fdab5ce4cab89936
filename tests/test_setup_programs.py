"""What a process builds before its first step (ISSUE 27): the work a
phase does to every parameter is one XLA program, not one per distinct
(operation, shape, type); a value that replaces an initializer's draw
runs no initializer; the whole step is lowered once; every program is
stored in the persistent cache.  CPU: counts and values, never a time.
"""
import contextlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray.ndarray import NDArray

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_programs = []      # fun_name of every executable JAX obtained, built or loaded


def _on_duration(event, duration, fun_name=None, **_kw):  # noqa: ARG001
    if event == "/jax/core/compile/backend_compile_duration":
        _programs.append(("backend", fun_name))
    elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        _programs.append(("lower", fun_name))


jax.monitoring.register_event_duration_secs_listener(_on_duration)


@contextlib.contextmanager
def programs(stage="backend"):
    """Names of the programs of `stage` that arrive inside the block."""
    start, out = len(_programs), []
    yield out
    out.extend(n for s, n in _programs[start:] if s == stage)


def _net(n_shapes, deferred=False):
    """Dense layers whose weights and biases have `n_shapes` distinct
    shapes between them."""
    net = nn.HybridSequential()
    for k in range(n_shapes // 2):
        net.add(nn.Dense(3 + k, in_units=0 if deferred else 2 + k))
    return net


OPTIMIZERS = {
    "sgd": {"learning_rate": 0.1, "momentum": 0.9},
    "adam": {"learning_rate": 0.001},
}


@pytest.mark.parametrize("n_shapes", [4, 40])
@pytest.mark.parametrize("multi_precision", [False, True])
@pytest.mark.parametrize("opt", sorted(OPTIMIZERS))
def test_cast_and_state_programs_do_not_follow_parameter_count(
        opt, multi_precision, n_shapes):
    net = _net(n_shapes)
    net.initialize()
    params = list(net.collect_params().values())
    assert len({p.shape for p in params}) == n_shapes
    before = {id(p): (p.data()._data, p.grad()._data) for p in params}

    with programs() as cast:
        amp.convert_hybrid_block(net, "bfloat16")
    trainer = gluon.Trainer(
        net.collect_params(), opt,
        dict(OPTIMIZERS[opt], multi_precision=multi_precision))
    with programs() as made:
        trainer._ensure_states(
            [(i, p.data()) for i, p in enumerate(trainer._params)])
    # the parameters' data in one program, their gradients in another
    # (committed to their device, as the data after set_data is not),
    # every state in a third: at 4 shapes as at 40
    assert len(cast) <= 2 and len(made) == 1, (cast, made)

    # bit for bit what the per-parameter operations give
    optimizer = trainer._optimizer
    for i, p in enumerate(trainer._params):
        w0, g0 = before[id(p)]
        w, g = p.data()._data, p.grad()._data
        assert w.dtype == g.dtype == jnp.bfloat16 == p.dtype
        assert w.committed == w0.committed and g.committed == g0.committed
        onp.testing.assert_array_equal(w, w0.astype(jnp.bfloat16))
        onp.testing.assert_array_equal(g, g0.astype(jnp.bfloat16))
        want = optimizer.create_state(i, NDArray(
            w.astype(jnp.float32) if multi_precision else w))
        state = trainer._states[i]
        if multi_precision:
            master, state = state
            assert master.dtype == onp.float32
            onp.testing.assert_array_equal(master._data,
                                           w.astype(jnp.float32))
        got = jax.tree_util.tree_leaves(
            state, is_leaf=lambda x: isinstance(x, NDArray))
        want = jax.tree_util.tree_leaves(
            want, is_leaf=lambda x: isinstance(x, NDArray))
        assert len(got) == len(want) == (1 if opt == "sgd" else 2)
        for a, b in zip(got, want):
            assert isinstance(a, NDArray) and a.dtype == b.dtype
            assert a.dtype == (onp.float32 if multi_precision
                               else jnp.bfloat16)
            assert a._data.committed == b._data.committed
            onp.testing.assert_array_equal(a._data, b._data)


def test_trainer_update_creates_every_state_in_one_program():
    """The lazy path: the first `Trainer.step` of an eager loop."""
    net = _net(12)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            dict(OPTIMIZERS["sgd"]))
    x = mx.np.ones((2, 2))
    with mx.autograd.record():
        loss = net(x).sum()
    loss.backward()
    with programs() as made:
        trainer.step(2)
    assert made.count("jit(create_states)") == 1
    assert all(trainer._states_created)


def test_one_state_and_one_cast_go_the_same_way():
    from mxnet_tpu import optimizer as opt_mod

    p = gluon.Parameter("weight", shape=(3, 5))
    p.initialize()
    w0, g0 = p.data()._data, p.grad()._data
    version = p.data()._version
    with programs() as cast:
        p.cast("bfloat16")
    assert set(cast) <= {"jit(_as_dtype)"}
    assert p.dtype == jnp.bfloat16 and p.data()._version == version + 1
    onp.testing.assert_array_equal(p.data()._data, w0.astype(jnp.bfloat16))
    onp.testing.assert_array_equal(p.grad()._data, g0.astype(jnp.bfloat16))
    assert p.data()._grad is p.grad()       # still wired for autograd
    p.cast("bfloat16")                      # nothing left to cast

    sgd = opt_mod.create("sgd", momentum=0.9, multi_precision=True)
    master, mom = sgd.create_state_multi_precision(0, p.data())
    assert master.dtype == mom.dtype == onp.float32
    onp.testing.assert_array_equal(master._data,
                                   p.data()._data.astype(jnp.float32))
    assert not mom.asnumpy().any()
    assert opt_mod.create("sgd").create_state_multi_precision(
        0, p.data()) is None                # no momentum, no state
    # a weight that is still a host array
    master, mom = sgd.create_state_multi_precision(
        1, NDArray(onp.ones((2, 3), "float16")))
    assert master.dtype == onp.float32 and (master.asnumpy() == 1).all()


def _leaves(tree):
    return jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, NDArray))


@pytest.mark.parametrize("multi_precision", [False, True])
@pytest.mark.parametrize("opt", sorted(OPTIMIZERS))
@pytest.mark.parametrize("plan", [None, "dp=2,tp=2", "dp=2,fsdp=2"])
def test_state_of_a_sharded_weight_lives_where_the_weight_does(
        plan, opt, multi_precision, monkeypatch):
    """Zeros read nothing of the weight: without a constraint the one
    program would make them whole on every device of the mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.observability import flight
    from mxnet_tpu.sharding import ShardingPlan

    # a plan stamps its mesh on the process's identity: hand it back
    monkeypatch.setattr(flight, "_identity", dict(flight._identity))
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8), nn.Dense(4, in_units=16))
    net.initialize()
    kwargs = dict(OPTIMIZERS[opt], multi_precision=multi_precision)
    if plan is None:
        # placed by hand: row-sharded weights, biases on every device
        mesh = Mesh(onp.array(jax.devices()[:4]), ("x",))
        for p in net.collect_params().values():
            arr = p.data()
            arr._data = jax.device_put(arr._data, NamedSharding(
                mesh, P("x", None) if arr.ndim == 2 else P()))
        trainer = gluon.Trainer(net.collect_params(), opt, kwargs)
    else:
        if "fsdp" in plan:
            monkeypatch.setenv("MXTPU_ZERO", "1")
        trainer = gluon.Trainer(
            net.collect_params(), opt, kwargs, kvstore="tpu_dist",
            sharding_plan=ShardingPlan(
                plan, rules=[(r"0\.weight", (None, "tp"))]
                if "tp" in plan else None))
        trainer._maybe_apply_plan()
        assert trainer._plan_applied
    amp.convert_hybrid_block(net, "bfloat16")
    weights = [p.data() for p in trainer._params]
    spread = [w._data.sharding for w in weights]
    assert all(len(s.device_set) == 4 for s in spread)
    # the cast left every weight where it was
    assert all(w.dtype == jnp.bfloat16 for w in weights)

    with programs() as made:
        trainer._ensure_states(list(enumerate(weights)))
    assert made.count("jit(create_states)") == 1

    assert any(not leaf._data.sharding.is_fully_replicated
               for leaf in _leaves(trainer._states))
    # the per-parameter result: eager operations, then the plan's placing
    optimizer = trainer._optimizer
    for i, w in enumerate(weights):
        if multi_precision:
            master = NDArray(w._data.astype(jnp.float32))
            want = (master, optimizer.create_state(i, master))
        else:
            want = optimizer.create_state(i, w)
        if plan is not None:
            opt_mod.place_state_like(want, w, plan=trainer._sharding_plan,
                                     name=trainer._param_names[i])
        got, want = _leaves(trainer._states[i]), _leaves(want)
        assert len(got) == len(want) > 0
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.shape == b.shape == w.shape
            assert a._data.sharding.is_equivalent_to(
                b._data.sharding, a.ndim), (i, a._data.sharding,
                                            b._data.sharding)
            onp.testing.assert_array_equal(a._data, b._data)


def test_lone_state_of_a_sharded_weight_is_sharded():
    """`Updater` and a lone `create_state_multi_precision` never re-place
    what they made."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu import optimizer as opt_mod

    mesh = Mesh(onp.array(jax.devices()[:4]), ("x",))
    where = NamedSharding(mesh, P("x", None))
    w = NDArray(jax.device_put(jnp.ones((8, 6), jnp.bfloat16), where))
    adam = opt_mod.create("adam", multi_precision=True)
    master, (m, v) = adam.create_state_multi_precision(0, w)
    for leaf in (master, m, v):
        assert leaf.dtype == onp.float32
        assert leaf._data.sharding.is_equivalent_to(where, 2)
    updater = opt_mod.get_updater(opt_mod.create("sgd", momentum=0.9))
    updater(0, NDArray(jnp.zeros_like(w._data)), w)
    assert updater.states[0]._data.sharding.is_equivalent_to(where, 2)


def test_create_state_with_host_code_runs_eagerly():
    from mxnet_tpu import optimizer as opt_mod

    class HostState(opt_mod.SGD):
        def create_state(self, index, weight):
            # a user's optimizer that looks at the values on the host
            return NDArray(jnp.asarray(onp.abs(weight.asnumpy())))

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    opt = HostState(momentum=0.9, multi_precision=True)
    ws = [NDArray(jnp.full((4, 3), -k - 1.0, jnp.bfloat16)) for k in range(3)]
    where = NamedSharding(Mesh(onp.array(jax.devices()[:4]), ("x",)),
                          P("x", None))
    ws.append(NDArray(jax.device_put(ws[0]._data * 4, where)))
    states = opt.create_states_multi_precision([0, 1, 2, 3], ws)
    for k, (master, st) in enumerate(states):
        assert master.dtype == st.dtype == onp.float32
        assert (master.asnumpy() == -k - 1.0).all()
        assert (st.asnumpy() == k + 1.0).all()
    assert states[3][0]._data.sharding.is_equivalent_to(where, 2)


def test_a_large_tree_is_cast_in_runs(monkeypatch):
    """Never two whole copies of a model: one program per byte budget."""
    from mxnet_tpu.gluon import parameter

    net = _net(8)
    net.initialize()
    params = list(net.collect_params().values())
    want = {id(p): p.data()._data.astype(jnp.bfloat16) for p in params}
    sizes = [a.nbytes for a in want.values()]
    monkeypatch.setattr(parameter, "_CAST_BYTES", 2 * max(sizes) * 2)
    with programs() as made:
        net.cast("bfloat16")
    assert 2 < made.count("jit(_as_dtype)") < 2 * len(params)
    for p in params:
        assert p.grad().dtype == jnp.bfloat16
        onp.testing.assert_array_equal(p.data()._data, want[id(p)])
    runs = list(parameter._runs([p.data() for p in params], 1))
    assert [len(r) for r in runs] == [1] * len(params)  # never an empty run


def test_set_data_on_a_deferred_parameter_draws_nothing(monkeypatch):
    from mxnet_tpu import initializer

    net = _net(6, deferred=True)
    net.initialize()
    values = {id(layer.weight): onp.full((3 + k, 2 + k), k + 1.0, "float32")
              for k, layer in enumerate(net)}

    def no_draw(*_a, **_k):
        raise AssertionError("an initializer ran for a value nobody reads")

    monkeypatch.setattr(initializer.Initializer, "init_array", no_draw)
    with programs() as made:
        for layer in net:
            assert layer.weight._is_deferred
            layer.weight.set_data(values[id(layer.weight)])
    assert made == []
    for layer in net:
        p, value = layer.weight, values[id(layer.weight)]
        assert not p._is_deferred and p.shape == value.shape
        onp.testing.assert_array_equal(p.data().asnumpy(), value)
        assert p.grad().shape == p.shape and not p.grad().asnumpy().any()
        assert p.data()._grad is p.grad()
    # and the net runs on them
    out = net(mx.np.ones((2, 2)))
    assert out.shape == (2, 5)


def test_load_parameters_is_the_initialization(tmp_path, monkeypatch):
    from mxnet_tpu import initializer

    saved = _net(6)
    saved.initialize()
    saved(mx.np.ones((2, 2)))
    path = str(tmp_path / "net.params")
    saved.save_parameters(path)

    def no_draw(*_a, **_k):
        raise AssertionError("an initializer ran for a value nobody reads")

    monkeypatch.setattr(initializer.Initializer, "init_array", no_draw)
    for deferred in (False, True):
        net = _net(6, deferred=deferred)    # never initialized
        with programs() as made:
            net.load_parameters(path)
        assert "jit(_uniform)" not in made
        for a, b in zip(saved.collect_params().values(),
                        net.collect_params().values()):
            onp.testing.assert_array_equal(a.data().asnumpy(),
                                           b.data().asnumpy())
            assert b.grad().shape == b.shape
        onp.testing.assert_array_equal(
            net(mx.np.ones((2, 2))).asnumpy(),
            saved(mx.np.ones((2, 2))).asnumpy())


def test_constant_initializers_need_no_program():
    p = gluon.Parameter("beta", shape=(7, 11), init="zeros")
    q = gluon.Parameter("gamma", shape=(7, 13), init="ones")
    r = gluon.Parameter("c", shape=(5, 17),
                        init=mx.initializer.Constant(2.5))
    with programs() as made:
        for x in (p, q, r):
            x.initialize()
    assert made == []
    assert not p.data().asnumpy().any() and not p.grad().asnumpy().any()
    assert (q.data().asnumpy() == 1).all()
    assert (r.data().asnumpy() == 2.5).all()
    assert isinstance(p.data()._data, jax.Array)


def _whole_step(committed):
    net = _net(4, deferred=not committed)
    net.initialize()
    if not committed:       # values handed over, as a checkpoint's are
        for k, p in enumerate(net.collect_params().values()):
            if p.name == "weight":
                p.set_data(onp.full((3 + k // 2, 2 + k // 2), 0.01,
                                    "float32"))
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            dict(OPTIMIZERS["sgd"]), kvstore="tpu_dist")
    return gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)


@pytest.mark.parametrize("committed", [True, False])
def test_capture_compile_takes_its_entry_from_the_one_lowering(committed):
    from mxnet_tpu import telemetry
    from mxnet_tpu.diagnostics import introspect
    from mxnet_tpu.optimizer.optimizer import _specs
    from mxnet_tpu.telemetry import instruments as ti

    was = telemetry.enabled()
    telemetry.enable()
    try:
        step = _whole_step(committed)
        x, y = mx.np.ones((4, 2)), mx.np.ones((4, 4))

        def traces():
            return sum(c.value for k, c in ti.jit_trace_total.series()
                       if k[0] == "whole_step")

        traces0 = traces()
        with programs("lower") as lowered, programs() as built:
            step(x, y)
        assert step.last_path == "whole_step"
        assert lowered.count("jit(whole_step)") == 1
        assert built.count("jit(whole_step)") == 1
        assert traces() - traces0 == 1 == step.jit_trace_count()
        entry = introspect.compile_registry()[("whole_step", step._variant)]
        assert ti.step_scalar_operands.value == 4

        # the same entry as a lowering of one's own
        donate, _n, tws, frozen, states, key, inputs = step._operands((x, y))
        opt = step._trainer._optimizer
        lrs, wds, ts = opt._packed_schedule([])
        n = len(step._train_index)
        step._introspecting = True
        compiled = step._jitted(donate).lower(
            *_specs((tws, frozen, states, key)),
            onp.zeros(n, "float32"), onp.zeros(n, "float32"),
            onp.zeros(n, "int32"), opt._packed_hyper(step._hyper_keys),
            *_specs(inputs)).compile()
        step._introspecting = False
        text = compiled.as_text()
        assert entry["flops"] == float(compiled.cost_analysis()["flops"]) > 0
        assert entry["tpu_custom_calls"] == 0
        assert entry["op_scopes"] == introspect.op_scopes(text)
        assert any("/optimizer/" in s for s in entry["op_scopes"].values())
        with programs("lower") as lowered:
            step(x, y)
        assert lowered == [] and step.jit_trace_count() == 1
    finally:
        if not was:
            telemetry.disable()


def test_specs_keep_where_a_committed_operand_lives():
    from mxnet_tpu.optimizer.optimizer import _specs

    dev = jax.devices()[0]
    here = jax.device_put(onp.ones((2, 3), "float32"), dev)
    free = jnp.ones((2, 3), "float32")
    a, b, c = _specs((here, free, 0.5))
    assert a.sharding == here.sharding and b.sharding is None and c == 0.5
    assert (a.shape, a.dtype) == (b.shape, b.dtype) == ((2, 3), onp.float32)


def test_compile_cache_stores_every_program_unless_told(monkeypatch):
    from mxnet_tpu import _jax_defaults

    policy = _jax_defaults._CACHE_POLICY
    was = {name: getattr(jax.config, name) for name, _var, _ours in policy}
    # this process, pinned to the CPU, runs the policy the chip runs
    for name, var, ours in policy:
        assert var in os.environ or was[name] == ours
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       jax.config.jax_compilation_cache_dir or "unused")
    try:
        for name, var, _ours in policy:
            monkeypatch.delenv(var, raising=False)
            jax.config.update(name, 3)
        _jax_defaults.place_compile_cache()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
        # JAX's own variable in the environment: that option is the user's
        for name, var, _ours in policy:
            jax.config.update(name, 3)
            monkeypatch.setenv(var, "3")
        _jax_defaults.place_compile_cache()
        for name, _var, _ours in policy:
            assert getattr(jax.config, name) == 3
    finally:
        for name, value in was.items():
            jax.config.update(name, value)


_HASH_PROBE = """
import hashlib, jax, numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import amp, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.optimizer.optimizer import _specs
mx.seed(0)
net = nn.HybridSequential()
net.add(nn.Dense(8, in_units=6, activation="relu"), nn.BatchNorm(),
        nn.Dropout(0.5), nn.Dense(4))
net.initialize()
x, y = mx.np.ones((4, 6)), mx.np.ones((4, 4))
net(x)
amp.convert_hybrid_block(net, "bfloat16")
net.hybridize()
trainer = gluon.Trainer(net.collect_params(), "adam",
                        {"learning_rate": 0.01, "multi_precision": True},
                        kvstore="tpu_dist")
step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
step(x, y)
assert step.last_path == "whole_step", step.ineligible_reason()
donate, _n, tws, frozen, states, key, inputs = step._operands((x, y))
opt = trainer._optimizer
n = len(step._train_index)
step._introspecting = True
lowered = step._jitted(donate).lower(
    *_specs((tws, frozen, states, key)), onp.zeros(n, "float32"),
    onp.zeros(n, "float32"), onp.zeros(n, "int32"),
    opt._packed_hyper(step._hyper_keys), *_specs(inputs))
order = [str(p) for p, _ in jax.tree_util.tree_flatten_with_path(
    (tws, frozen, states))[0]] + list(step._hyper_keys) \\
    + [n for _i, n, _p in step._train_items]
print("PROBE", hashlib.sha1(lowered.as_text().encode()).hexdigest(),
      hashlib.sha1(repr(order).encode()).hexdigest())
"""


def test_whole_step_operand_order_does_not_follow_the_hash_seed():
    """A program whose operand order followed PYTHONHASHSEED would have
    another cache key in every process (ROADMAP S3(c))."""
    seen = set()
    for seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=ROOT)
        out = subprocess.run([sys.executable, "-c", _HASH_PROBE], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        seen.add([ln for ln in out.stdout.splitlines()
                  if ln.startswith("PROBE")][-1])
    assert len(seen) == 1, seen


def test_xla_compile_event_names_the_program():
    from mxnet_tpu import telemetry
    from mxnet_tpu.observability import flight

    was = telemetry.enabled()
    telemetry.enable()
    try:
        def a_program_of_its_own(v):
            return jnp.tanh(v * 1.75 + 0.375).sum()

        jax.jit(a_program_of_its_own)(jnp.arange(5.0))
        events = [e for e in flight.events() if e["kind"] == "xla_compile"]
        assert events[-1]["name"] == "jit(a_program_of_its_own)"
        assert events[-1]["how"] in ("built", "loaded")
    finally:
        if not was:
            telemetry.disable()
