"""Every Pallas kernel the two engines launch compiles for a TPU v5e.

The CPU tier runs the kernels in interpret mode, which accepts block
shapes and layouts that the chip's compiler (Mosaic) refuses.  These
tests compile each kernel with ``interpret=False`` for a v5e that is
described, not attached, at the paper MLP's real widths: R = 512
requester sessions x N = 5 contributors, P = 2821 parameters (8-64-32-5),
int8 wire rows padded to Lp = 3072.  R = 85 covers a requester count
that is not a multiple of 8.  Nothing runs; a compile that passes says
nothing about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under
pytest-xdist every worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

N, P, LP, NQ = 5, 2821, 3072, 3
H, F = 64, 6   # the paper LSTM's hidden width and channels


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off:
    entries compiled for a chip that is not attached cannot be read
    back."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernels(r):
    """name -> (fn, [(shape, dtype), ...]) for a fleet of ``r`` lanes."""
    from repro.kernels.fedavg import kernel as fk
    from repro.kernels.quantize import kernel as qk
    from repro.kernels.robust import kernel as rk
    f32, i8 = jnp.float32, jnp.int8
    dense = [((r, N, P), f32), ((r, N), f32)]
    wire = [((r, N, LP), i8), ((r, N, NQ), f32), ((r, N), f32)]
    return {
        "fedavg_batched": (fk.fedavg_batched_pallas, dense),
        "fedavg_batched_q8": (fk.fedavg_batched_q8_pallas, wire),
        "quantize_batched": (qk.quantize_batched_pallas, [((r * N, LP), f32)]),
        "trimmed_mean": (rk.trimmed_mean_batched_pallas, dense),
        "trimmed_mean_q8": (rk.trimmed_mean_batched_q8_pallas, wire),
        "median": (rk.median_batched_pallas, dense),
        "median_q8": (rk.median_batched_q8_pallas, wire),
        "sqnorm": (rk.sqnorm_batched_pallas, dense[:1]),
        "sqnorm_q8": (rk.sqnorm_batched_q8_pallas, wire[:2]),
    }


def _single():
    from repro.kernels.fedavg.kernel import fedavg_pallas
    from repro.kernels.lstm_cell.kernel import lstm_cell_pallas
    from repro.kernels.quantize.kernel import (dequantize_pallas,
                                               quantize_pallas)
    f32 = jnp.float32
    return {
        "fedavg": (fedavg_pallas, [((N, P), f32), ((N,), f32)]),
        "quantize": (quantize_pallas, [((P,), f32)]),
        "dequantize": (lambda q, s, **kw: dequantize_pallas(q, s, P, **kw),
                       [((LP,), jnp.int8), ((NQ,), f32)]),
        "lstm_cell": (lstm_cell_pallas,
                      [((512, F), f32), ((512, H), f32), ((512, H), f32),
                       ((F, 4 * H), f32), ((H, 4 * H), f32), ((4 * H,), f32)]),
    }


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("r", [512, 85])
@pytest.mark.parametrize("name", list(_kernels(1)))
def test_fleet_kernel_compiles_for_v5e(one_chip, name, r):
    fn, shapes = _kernels(r)[name]
    _compile(fn, shapes, one_chip)


@pytest.mark.parametrize("name", list(_single()))
def test_single_session_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _single()[name]
    _compile(fn, shapes, one_chip)
