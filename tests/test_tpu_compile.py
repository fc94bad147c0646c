"""TPU v5e compile rehearsals of the main-path kernels at real widths.

The TPU compiler ships with libtpu and compiles for a described chip
that is not attached, so these run on a CPU host and refuse what the
chip's compiler would refuse (misaligned blocks, too much VMEM). Each
test asserts the compiled program holds the Mosaic kernel
(`tpu_custom_call`), i.e. the pallas path lowered to the real kernel
and not to the jnp reference. Nothing runs: no numbers, no times.

The topology is described inside a module fixture (never at import),
so only the worker that runs this file loads libtpu. The persistent
compilation cache is off around these compiles: an entry compiled for a
described chip cannot be read back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the temp directory
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no libtpu, or it cannot describe v5e
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from repro.compile_cache import compile_cache_off

    with compile_cache_off():
        yield


@pytest.fixture
def compile_v5e(one_chip, no_compile_cache):
    """(fn, *args) -> compiled text of jit(fn) for one v5e chip. Only
    the shapes and dtypes of `args` (arrays, ShapeDtypeStructs, or
    pytrees of them) count."""

    def compile_(fn, *args):
        placed = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip),
            args,
        )
        return jax.jit(fn).lower(*placed).compile().as_text()

    return compile_


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("M,N", [(4096, 256), (1000, 300)])
def test_carbon_scores_compiles(compile_v5e, M, N):
    text = compile_v5e(
        functools.partial(ops.carbon_scores, interpret=False),
        f32(M, N), f32(M, N), f32(M), f32(M), f32(N), f32(),
    )
    assert "tpu_custom_call" in text


def test_route_scores_compiles(compile_v5e):
    M, L = 4096, 512
    text = compile_v5e(
        functools.partial(ops.route_scores, interpret=False),
        f32(M, L), f32(M, L), f32(M, L), f32(M, L), f32(M), f32(M),
        f32(L), f32(),
    )
    assert "tpu_custom_call" in text


def test_pallas_serve_step_compiles(compile_v5e):
    from repro.core import (
        CarbonIntensityPolicy,
        UKRegionalTraceSource,
        UniformArrivals,
    )
    from repro.core.queueing import init_state
    from repro.serve import make_serve_step
    from repro.serve.loop import demo_spec

    M, N = 1024, 64
    # score_interpret=False: on a CPU host auto dispatch would pick the
    # reference; on the chip auto dispatch picks this compiled kernel
    policy = CarbonIntensityPolicy(
        V=0.05, score_backend="pallas", score_interpret=False
    )
    step = make_serve_step(
        policy, demo_spec(M, N, 0), UKRegionalTraceSource(N=N),
        UniformArrivals(M=M, amax=300), jax.random.PRNGKey(0),
    )
    text = compile_v5e(step, init_state(M, N), jnp.int32(0))
    assert "tpu_custom_call" in text
