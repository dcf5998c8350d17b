"""Compile every kernel of the FL path for a TPU v5e, without the chip.

The TPU compiler is installed with libtpu and compiles for a described
topology: what Mosaic refuses (unaligned blocks, unsupported casts, lane
gathers) fails here exactly as it would on the chip, while interpret mode
(tests/test_wire.py, tests/test_kernels.py) accepts it.  Shapes are the
segments and flat groups that fl-tiny's compression policy produces
(``fl.simulation.default_tiny_arch``), plus one segment wider than 512
columns so the coefficient scales span several column blocks.

The topology is described inside a fixture, never at import: only one
process may load libtpu at a time, and every pytest-xdist worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.policy import make_policy
from repro.fl.simulation import default_tiny_arch
from repro.kernels import ops
from repro.kernels.wire import coeff_dequant_pallas
from repro.models import param_group_shapes

_PLANS = make_policy(param_group_shapes(default_tiny_arch()),
                     min_params=4096).plans.values()
#: (l, k, m) of every compressed fl-tiny segment, plus a wider one
_SEGMENTS = sorted({(p.l, p.k, p.m) for p in _PLANS if p.compress}
                   | {(512, 32, 1536)})
#: flat group sizes the per-tensor codecs see (plan.raw_scalars)
_FLAT = sorted({p.raw_scalars for p in _PLANS})


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def spec(topo):
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; it must hold a Mosaic kernel."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_fl_tiny_shapes_are_covered():
    assert (128, 16, 128) in _SEGMENTS and 262144 in _FLAT


@pytest.mark.parametrize("n", _FLAT)
def test_sign_wire(spec, n):
    _compile(lambda g: ops.sign_wire(g, interpret=False), spec((n,)))
    _compile(lambda w, s: ops.sign_unwire(w, s, n, interpret=False),
             spec((-(-n // 32),), jnp.uint32), spec(()))


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("n", [_FLAT[0], _FLAT[-1]])
def test_block_quant_wire(spec, bits, n):
    rows = -(-n // 512)
    _compile(lambda g, key: ops.block_quant_wire(
        g, key, bits=bits, interpret=False)[:2],
        spec((n,)), spec((2,), jnp.uint32))
    _compile(lambda w, s: ops.block_dequant_wire(
        w, s, rows * 512 - n, bits=bits, interpret=False),
        spec((rows * 512 * bits // 32,), jnp.uint32), spec((rows,)))


@pytest.mark.parametrize("l,k,m", _SEGMENTS)
def test_gradestc_segment(spec, l, k, m):
    _compile(lambda M, G: ops.encode(M, G, interpret=False),
             spec((l, k)), spec((l, m)))
    _compile(lambda M, A: ops.decode(M, A, interpret=False),
             spec((l, k)), spec((k, m)))


@pytest.mark.parametrize("l,k,m", _SEGMENTS)
def test_int8_coefficient_wire(spec, l, k, m):
    nb = -(-m // 512)
    _compile(lambda A: ops.coeff_quant(A, interpret=False), spec((k, m)))
    _compile(lambda c, s: coeff_dequant_pallas(c, s, interpret=False),
             spec((k, nb * 512), jnp.int8), spec((nb, k, 1)))
    _compile(lambda M, G: ops.encode_quant(M, G, interpret=False),
             spec((l, k)), spec((l, m)))
    _compile(lambda M, c, s: ops.decode_wire(M, c, s, interpret=False),
             spec((l, k)), spec((k, m), jnp.int8), spec((k, nb)))


def test_kernels_under_client_and_layer_vmap(spec):
    # codecs vmap encode over clients and, for GradESTC, over stacked layers
    l, k, m = _SEGMENTS[0]
    both = jax.vmap(jax.vmap(lambda M, G: ops.decode(
        M, ops.coeff_roundtrip(ops.encode(M, G, interpret=False)[0],
                               "int8", interpret=False), interpret=False)))
    _compile(both, spec((3, 4, l, k)), spec((3, 4, l, m)))
    _compile(jax.vmap(lambda g, key: ops.quantize_update(
        g, key, use_pallas=True, interpret=False)),
        spec((3, _FLAT[-1])), spec((3, 2), jnp.uint32))
