"""SpecLayout rule library + the plan recipes (ISSUE 19,
mxnet_tpu/sharding/layouts.py): role -> PartitionSpec resolution with
mesh/shape pruning, structural block-role classification, name-token
fallback, ZeRO state-spec extension, ShardingPlan.from_layout / env
construction, and the dryrun bar — every promoted recipe partitions a
train step at >= 99.5% efficiency on the 8-virtual-device CPU mesh
(per-device FLOPs of the partitioned module over the ideal 1/N, by
XLA's cost model)."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.sharding import (DEFAULT_LAYOUT, RECIPES, ShardingPlan,
                                SpecLayout, block_roles, plan_recipe,
                                role_from_name, zero_state_spec)

AX = {"dp": 2, "fsdp": 2, "tp": 2}


# -- role -> spec resolution -------------------------------------------------

def test_ideal_role_specs():
    lay = DEFAULT_LAYOUT
    assert lay.embedding() == P(("fsdp", "tp"), None)
    assert lay.qkv_projection() == P("tp", "fsdp")      # column parallel
    assert lay.attn_output() == P("fsdp", "tp")         # row parallel
    assert lay.ffn_up() == P("tp", "fsdp")
    assert lay.ffn_down() == P("fsdp", "tp")
    assert lay.norm() == P("fsdp")
    assert lay.conv() == P(("tp", "fsdp"), None, None, None)
    assert lay.bias() == P()
    assert lay.model_axes() == ("fsdp", "tp")


def test_spec_for_role_prunes_absent_axes():
    lay = DEFAULT_LAYOUT
    # no fsdp on the mesh: the fsdp entry vanishes (trailing None pops)
    assert lay.spec_for_role("ffn_up", (16, 12),
                             {"dp": 4, "tp": 2}) == P("tp")
    # no model axes at all: everything replicates
    assert lay.spec_for_role("ffn_up", (16, 12), {"dp": 8}) == P()
    # full hybrid mesh keeps both entries
    assert lay.spec_for_role("ffn_up", (16, 12), AX) == P("tp", "fsdp")


def test_spec_for_role_divisibility_degrades_not_raises():
    lay = DEFAULT_LAYOUT
    # 7 is indivisible by tp=2: the sharded dim replicates instead
    assert lay.spec_for_role("ffn_up", (7, 12), AX) == P(None, "fsdp")
    # tuple entries drop right-to-left: vocab 6 % (fsdp*tp=4) != 0 but
    # 6 % fsdp=2 == 0, so only fsdp survives in the joint entry
    assert lay.spec_for_role("embedding", (6, 8), AX) == P("fsdp")
    # nothing divides: fully replicated
    assert lay.spec_for_role("ffn_up", (7, 7), AX) == P()
    # no shape given: axes prune by mesh only, divisibility deferred
    assert lay.spec_for_role("ffn_up", None, AX) == P("tp", "fsdp")


def test_custom_axis_names():
    lay = SpecLayout(data_axis="data", fsdp_axis="shard", tp_axis="model")
    assert lay.ffn_up() == P("model", "shard")
    assert lay.model_axes() == ("shard", "model")
    assert lay.spec_for_role(
        "ffn_up", (16, 12), {"data": 4, "model": 2}) == P("model")


# -- role classification -----------------------------------------------------

def test_role_from_name_tokens():
    assert role_from_name("encoder.q_proj.weight") == "qkv_projection"
    assert role_from_name("blk.attention.query.weight") == "qkv_projection"
    assert role_from_name("blk.out_proj.weight") == "attn_output"
    assert role_from_name("embedding0.weight") == "embedding"
    assert role_from_name("bn.gamma") == "norm"
    assert role_from_name("bn.running_mean") == "norm"
    assert role_from_name("fc.bias") == "bias"
    assert role_from_name("conv0.weight", (8, 3, 3, 3)) == "conv"
    # plain Dense weights classify by shape: growing = up, shrinking = down
    assert role_from_name("fc1.weight", (64, 16)) == "ffn_up"
    assert role_from_name("fc2.weight", (16, 64)) == "ffn_down"
    assert role_from_name("mystery.scale") is None


def test_block_roles_structural_walk():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Embedding(32, 8),
            gluon.nn.Dense(64, in_units=8, activation="relu"),
            gluon.nn.LayerNorm(),
            gluon.nn.Dense(16, in_units=64))
    net.initialize()
    roles = block_roles(net)
    assert roles["0.weight"] == "embedding"
    assert roles["1.weight"] == "ffn_up"       # 64 >= 8
    assert roles["1.bias"] == "bias"
    assert roles["2.gamma"] == "norm"
    assert roles["2.beta"] == "norm"
    assert roles["3.weight"] == "ffn_down"     # 16 < 64
    assert roles["3.bias"] == "bias"


def test_block_roles_conv_and_attention_names():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, in_channels=3))
    net.initialize()
    assert block_roles(net)["0.weight"] == "conv"
    # a Dense whose path carries an attention token wins over shape
    class Blk(gluon.nn.HybridBlock):
        def __init__(self):
            super().__init__()
            self.q_proj = gluon.nn.Dense(8, in_units=16)

        def forward(self, x):
            return self.q_proj(x)

    b = Blk()
    b.initialize()
    assert block_roles(b)["q_proj.weight"] == "qkv_projection"


# -- ZeRO state specs --------------------------------------------------------

def test_zero_state_spec_extends_first_free_dim():
    # replicated bias: state shards its only dim over fsdp
    assert zero_state_spec(P(), (16,), AX, "fsdp") == P("fsdp")
    # tp-sharded weight with a free dim: fsdp lands there
    assert zero_state_spec(P("tp"), (16, 12), AX, "fsdp") \
        == P("tp", "fsdp")
    # param already fsdp-sharded: spec unchanged (state already 1/N)
    assert zero_state_spec(P("tp", "fsdp"), (16, 12), AX, "fsdp") \
        == P("tp", "fsdp")
    # indivisible everywhere: unchanged
    assert zero_state_spec(P(), (7, 9), AX, "fsdp") == P()
    # mesh without fsdp: unchanged
    assert zero_state_spec(P(), (16,), {"dp": 8}, "fsdp") == P()


def test_plan_state_spec_and_zero_axis(monkeypatch):
    plan = ShardingPlan.from_layout("dp=2,fsdp=2,tp=2")
    assert plan.zero_axis() == "fsdp"
    assert plan.state_spec_for("fc.bias", (16,)) == P("fsdp")
    assert plan.shards_state([("fc.bias", (16,))])
    monkeypatch.setenv("MXTPU_ZERO", "0")
    assert plan.zero_axis() is None
    assert plan.state_spec_for("fc.bias", (16,)) == P()
    monkeypatch.delenv("MXTPU_ZERO")
    # no fsdp axis on the mesh: no ZeRO regardless of the knob
    assert ShardingPlan.from_layout("dp=4,tp=2").zero_axis() is None


# -- plan construction -------------------------------------------------------

def test_from_layout_spec_resolution():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, in_units=12, activation="relu"),
            gluon.nn.Dense(4, in_units=16))
    net.initialize()
    plan = ShardingPlan.from_layout("dp=2,fsdp=2,tp=2", net=net)
    assert plan.spec_for("0.weight", (16, 12)) == P("tp", "fsdp")
    assert plan.spec_for("1.weight", (4, 16)) == P("fsdp", "tp")
    assert plan.spec_for("0.bias", (16,)) == P()
    assert plan.shards_params([("0.weight", (16, 12))])
    # regex rules still win over the layout
    ruled = ShardingPlan.from_layout(
        "dp=2,fsdp=2,tp=2", net=net, rules=[(r"0\.weight", None)])
    assert ruled.spec_for("0.weight", (16, 12)) == P()


def test_from_env_attaches_layout(monkeypatch):
    monkeypatch.setenv("MXTPU_MESH", "dp=2,fsdp=2,tp=2")
    plan = ShardingPlan.from_env()
    assert plan.layout is not None
    assert plan.spec_for("fc1.weight", (64, 16)) == P("tp", "fsdp")
    # layout kill switch: axes only, params replicate
    monkeypatch.setenv("MXTPU_SPEC_LAYOUT", "0")
    bare = ShardingPlan.from_env()
    assert bare.layout is None
    assert bare.spec_for("fc1.weight", (64, 16)) == P()
    monkeypatch.delenv("MXTPU_SPEC_LAYOUT")
    # a mesh without model axes never attaches the layout
    monkeypatch.setenv("MXTPU_MESH", "dp=-1")
    assert ShardingPlan.from_env().layout is None


def test_manifest_roundtrip_keeps_layout_and_roles():
    net = gluon.nn.Dense(16, in_units=12)
    net.initialize()
    plan = ShardingPlan.from_layout("dp=2,fsdp=2,tp=2", net=net)
    plan.mesh
    d = plan.to_manifest()
    assert d["layout"] == ["dp", "fsdp", "tp"]
    assert d["zero_axis"] == "fsdp"
    back = ShardingPlan.from_manifest(d)
    assert back.layout == plan.layout
    assert back.roles == plan.roles
    assert back.spec_for("weight", (16, 12)) \
        == plan.spec_for("weight", (16, 12))


def test_plan_recipe_names():
    assert set(RECIPES) >= {"dp8", "dp4_tp2", "dp2_fsdp2_tp2", "fsdp4",
                            "ring_sp8", "moe_ep8", "pipeline_pp8"}
    p = plan_recipe("dp2_fsdp2_tp2")
    assert p.layout is not None
    assert p.axis_sizes() == {"dp": 2, "fsdp": 2, "tp": 2}
    assert plan_recipe("dp8").layout is None
    with pytest.raises(KeyError, match="dp4_tp2"):
        plan_recipe("nope")


# -- the dryrun bar: >= 99.5% partition efficiency ---------------------------

BATCH, HID, CLS = 1024, 512, 16


def _mlp():
    """Named-param MLP forward+backward on plan-resolved shardings:
    the returned
    grads land on the plan's STATE specs — the reduce-scatter layout
    the ZeRO-sharded optimizer consumes."""
    rng = onp.random.RandomState(0)
    dims = [(784, HID), (HID, HID), (HID, CLS)]
    params = {}
    for i, (fin, fout) in enumerate(dims):
        params[f"fc{i}.weight"] = jnp.asarray(
            rng.randn(fout, fin).astype("f") * 0.05)
        params[f"fc{i}.bias"] = jnp.zeros(fout, "f")
    x = jnp.asarray(rng.rand(BATCH, 784).astype("f"))
    y = jnp.asarray(rng.randint(0, CLS, (BATCH,)))

    def step(params, x, y):
        def loss_fn(pd):
            h = x
            for i in range(len(dims)):
                h = h @ pd[f"fc{i}.weight"].T + pd[f"fc{i}.bias"]
                if i < len(dims) - 1:
                    h = jax.nn.relu(h)
            logp = jax.nn.log_softmax(h)
            return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return grads, loss

    return step, params, x, y


def _flops(compiled):
    return float(compiled.cost_analysis()["flops"])


@pytest.mark.parametrize("recipe", ["dp8", "dp4_tp2", "dp2_fsdp2_tp2",
                                    "fsdp4"])
def test_recipe_partition_efficiency(recipe):
    """Every plan recipe partitions the train step at
    >= 99.5% efficiency: per-device FLOPs of the GSPMD module vs the
    ideal 1/N of the single-device module (XLA cost model), with params
    on the layout's specs and gradients delivered on the ZeRO state
    layout (reduce-scatter semantics)."""
    step, params, x, y = _mlp()
    flops1 = _flops(jax.jit(step).lower(params, x, y).compile())

    plan = plan_recipe(recipe)
    mesh = plan.mesh
    n_dev = mesh.devices.size
    assert n_dev == 8
    p_sh = {n: NamedSharding(mesh, plan.spec_for(n, a.shape))
            for n, a in params.items()}
    g_sh = {n: NamedSharding(mesh, plan.state_spec_for(n, a.shape))
            for n, a in params.items()}
    b_sh = NamedSharding(mesh, plan.data_spec())
    rep = NamedSharding(mesh, P())
    comp = jax.jit(
        step, in_shardings=(p_sh, b_sh, b_sh),
        out_shardings=(g_sh, rep),
    ).lower(params, x, y).compile()
    flops_n = _flops(comp)
    eff = (flops1 / n_dev) / flops_n
    assert eff >= 0.995, (recipe, eff, flops1, flops_n)
    # and the partitioned program actually runs on the mesh, grads
    # landing 1/fsdp-sharded where ZeRO asks for them
    pp = {n: jax.device_put(a, p_sh[n]) for n, a in params.items()}
    grads, loss = comp(pp, jax.device_put(x, b_sh),
                       jax.device_put(y, b_sh))
    assert onp.isfinite(float(loss))
    for n, g in grads.items():
        assert g.sharding.is_equivalent_to(g_sh[n], g.ndim), n
