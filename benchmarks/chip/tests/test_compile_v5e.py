"""Compiles the served step and the fleet what-if program, at the sizes
their cells time, for a described TPU v5e that is not attached.
What the chip's compiler refuses, or a program that cannot fit one
chip's memory, fails here at no chip time. The topology is described
inside a fixture, never at import."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import BENCH, load_cfg

import gen
from paths import fleet as fleet_path
from paths import serve as serve_path


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    from repro.compile_cache import compile_cache_off

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with compile_cache_off():
        yield SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used


def test_served_step_compiles(one_chip):
    from repro.core.queueing import init_state
    from repro.serve import make_serve_step

    cfg = load_cfg("paper-table1")
    mix = gen.load_json(BENCH / "traffic" / "steady.json")
    M, N = cfg["M"], cfg["N"]
    inp = serve_path._inputs(cfg, mix, 7)
    step = make_serve_step(inp["policy"], inp["spec"], inp["carbon"],
                           inp["arrivals"], inp["key"])
    compiled = step.lower(_on(one_chip, init_state(M, N)),
                          _on(one_chip, jnp.int32(0))).compile()
    _fits(compiled)


def test_fleet_whatif_program_compiles(one_chip):
    cfg = load_cfg("paper-table1")
    mix = gen.load_json(BENCH / "traffic" / "whatif.json")
    lanes, fleet, policy = fleet_path._inputs(cfg, mix, 7)
    assert fleet.F == 1024
    fn = fleet_path._program(policy, mix)
    compiled = fn.lower(_on(one_chip, fleet),
                        _on(one_chip, gen.seed_key(7, 4))).compile()
    _fits(compiled)
