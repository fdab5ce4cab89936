"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not attached — so what Mosaic refuses (an i64 index
map, a block that does not tile, too much VMEM) fails HERE, at real
widths, at no chip time.  Interpret-mode parity (tests/test_kernels.py,
tests/test_flash_attention.py) cannot see any of that.  A compile that
passes is not a chip run: chip_smoke.py checks the numbers on the chip.

This is the ONLY file that describes the chip, and it does so inside a
module-scoped fixture: only one process may hold the TPU library, and
pytest-xdist runs no test at all when workers collect different tests —
so nothing here touches the topology while a module is imported (no
top-level call, no skipif condition, no parametrize argument), and the
compiles run in the test's own process.
"""
import os

import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu  # noqa: F401  (x64 on: the contract the kernels escape)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology executable can be written to the persistent
    # cache but not read back without a chip; keep these compiles out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def spec(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture
def force_kernels(monkeypatch):
    """The dispatch asks jax.devices() for the platform and, on the
    CPU-pinned test process, would answer 'platform'; the compile target
    here IS a TPU, so steer it in the test."""
    from mxnet_tpu.kernels import dispatch

    monkeypatch.setenv("MXTPU_KERNELS", "force")
    monkeypatch.delenv("MXTPU_KERNELS_INTERPRET", raising=False)
    monkeypatch.setattr(dispatch, "platform_ok", lambda: True)


def _kernel_calls(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


# -- flash attention: BERT-base heads at the SQuAD and the 512 lengths ------

@pytest.mark.parametrize("seq", [384, 512])
@pytest.mark.parametrize("case", ["fwd", "fwd_causal_dropout", "grad"])
def test_flash_attention_compiles_for_v5e(spec, seq, case):
    from mxnet_tpu.ops.pallas_attention import flash_attention

    q = spec((8, 12, seq, 64), jnp.bfloat16)
    seed = spec((1,), jnp.int32)
    if case == "fwd":
        calls = _kernel_calls(
            lambda q, k, v: flash_attention(q, k, v, interpret=False),
            q, q, q)
        assert calls == 1
    elif case == "fwd_causal_dropout":
        calls = _kernel_calls(
            lambda q, k, v, s: flash_attention(
                q, k, v, causal=True, dropout_p=0.1, dropout_seed=s,
                interpret=False), q, q, q, seed)
        assert calls == 1
    else:
        def loss(q, k, v, s):
            out = flash_attention(q, k, v, dropout_p=0.1, dropout_seed=s,
                                  interpret=False)
            return out.astype(jnp.float32).sum()

        calls = _kernel_calls(jax.grad(loss, argnums=(0, 1, 2)),
                              q, q, q, seed)
        assert calls == 3      # forward, dQ, dK/dV


# -- batch-norm training pair at ResNet-50's b=128 activations --------------

@pytest.mark.parametrize("rows,channels", [
    (128 * 56 * 56, 64), (128 * 56 * 56, 256), (128 * 7 * 7, 2048)])
def test_bn_train_compiles_for_v5e(spec, force_kernels, rows, channels):
    from mxnet_tpu.kernels import norm

    x = spec((rows, channels), jnp.bfloat16)
    c = spec((channels,), jnp.float32)

    def fwd_bwd(x, gamma, beta, shift):
        def loss(x, gamma, beta):
            out, mean, var = norm.bn_train(x, gamma, beta, shift, 1e-5, 1)
            return out.astype(jnp.float32).sum() + mean.sum() + var.sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, gamma, beta)

    assert _kernel_calls(fwd_bwd, x, c, c, c) == 2     # forward, backward


# -- fused optimizer ladder at BERT-base's FFN weight -----------------------

@pytest.mark.parametrize("rule", ["sgd_momentum", "adam"])
def test_param_step_compiles_for_v5e(spec, force_kernels, rule):
    from mxnet_tpu.kernels import opt
    from mxnet_tpu.optimizer import SGD, Adam

    w = spec((768, 3072), jnp.bfloat16)
    f32 = spec((768, 3072), jnp.float32)
    if rule == "sgd_momentum":
        hyper = {"rescale_grad": 1.0 / 8, "momentum": 0.9}

        def step(w, master, mom, g):
            return opt.param_step(SGD, None, False, True, w, (master, mom),
                                  g, 0.1, 1e-4, 3, 1.0, hyper)
        calls = _kernel_calls(step, w, f32, f32, w)
    else:
        hyper = {"rescale_grad": 1.0 / 8, "beta1": 0.9, "beta2": 0.999,
                 "eps": 1e-8}

        def step(w, master, m, v, g):
            return opt.param_step(Adam, None, False, True, w,
                                  (master, (m, v)), g, 2e-5, 0.01, 3, 1.0,
                                  hyper)
        calls = _kernel_calls(step, w, f32, f32, f32, w)
    assert calls == 1
