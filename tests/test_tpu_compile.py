"""The main path's Pallas kernels compile for a TPU v5e chip.

Each test lowers a kernel with ``interpret=False`` at the paper model's
shape (B=64, T=5, F=5, H=40; the int8 weights at the forecaster's four
(K, N) shapes) for one chip of a v5e topology described without the chip,
and asserts that the HLO holds the compiled kernel (``tpu_custom_call``).
Interpret-mode tests cannot catch what only Mosaic refuses: a value-level
dynamic slice, or a block whose tiling XLA and Mosaic disagree on.

The topology is described inside a module-scoped fixture — never while a
module imports — and the tests skip where it cannot be described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, T, F, H = 64, 5, 5, 40
S = 8  # fleet streams for the vmapped int8 serving matmul
FORECASTER_INT8_SHAPES = [(F, 4 * H), (H, 4 * H), (H, 10), (10, 1)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip lands in the persistent cache but can
    # never be read back without one: keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _lstm_args(one_chip):
    return (_sds(one_chip, (B, T, F)), _sds(one_chip, (F, 4 * H)),
            _sds(one_chip, (H, 4 * H)), _sds(one_chip, (4 * H,)))


def test_lstm_sequence_fused_compiles(one_chip):
    from repro.kernels.lstm_cell.kernel import lstm_sequence_fused

    hlo = _hlo(lambda *a: lstm_sequence_fused(*a, interpret=False),
               *_lstm_args(one_chip))
    assert "tpu_custom_call" in hlo


def test_lstm_sequence_custom_vjp_grad_compiles(one_chip):
    """The fused forward-train / backward kernel pair behind the custom
    VJP: both must be compiled kernels in the gradient's HLO."""
    from repro.kernels.lstm_cell.ops import lstm_sequence

    grad = jax.grad(
        lambda *a: jnp.sum(lstm_sequence(*a, interpret=False)),
        argnums=(0, 1, 2, 3))
    hlo = _hlo(grad, *_lstm_args(one_chip))
    assert hlo.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("K,N", FORECASTER_INT8_SHAPES)
def test_int8_matmul_compiles(one_chip, K, N):
    from repro.kernels.int8_matmul.kernel import int8_matmul

    hlo = _hlo(lambda x, q, s: int8_matmul(x, q, s, interpret=False),
               _sds(one_chip, (B, K)), _sds(one_chip, (K, N), jnp.int8),
               _sds(one_chip, (N,)))
    assert "tpu_custom_call" in hlo


def test_qmatmul_vmapped_over_fleet_compiles(one_chip):
    """The int8 fleet serving path: ``qmatmul`` vmapped over S stacked
    per-stream QTensors (the recurrent projection's shape)."""
    from repro.kernels.int8_matmul.ops import qmatmul
    from repro.serving.quantize import QTensor

    qt = QTensor(q=_sds(one_chip, (S, H, 4 * H), jnp.int8),
                 scale=_sds(one_chip, (S, 4 * H)), orig_dtype="float32")
    hlo = _hlo(jax.vmap(lambda x, w: qmatmul(x, w, interpret=False)),
               _sds(one_chip, (S, B, H)), qt)
    assert "tpu_custom_call" in hlo
