"""Compile the Pallas kernels for a described TPU v5e, with no chip attached.

The TPU compiler is installed next to jax; it compiles for a topology that
is described, not attached, and refuses what the chip would refuse
(misaligned tiles, too much VMEM, unsupported vector layouts or casts) --
failures the interpret-mode tests in tests/test_kernels.py cannot see.
Each kernel compiles with ``interpret=False`` at the size the trainer uses:
the count-sketch at bert-100m's real ``d_total`` and ``b_total``.

The topology is described inside a module-scoped fixture, never at import
time, so that under several pytest workers only the worker that runs this
file loads the TPU library.  The persistent compile cache is off around
these tests: a compile for a described chip is written to it but cannot be
read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.countsketch import countsketch_clients_pallas
from repro.kernels.fwht import fwht_rows_pallas
from repro.kernels.gaussian_sketch import (gaussian_desk_pallas,
                                           gaussian_sk_pallas)

BERT_D_TOTAL = 132_008_448      # configs/bert_100m.py, every parameter
BERT_B_TOTAL = 2_640_275        # its packed sketch at ratio 0.02
BERT_CLIENTS = 5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is there
    return compiled


def test_countsketch_clients_compiles_at_bert_100m(one_chip):
    x = jax.ShapeDtypeStruct((BERT_CLIENTS, BERT_D_TOTAL), jnp.float32,
                             sharding=one_chip)
    h = jax.ShapeDtypeStruct((BERT_D_TOTAL,), jnp.int32, sharding=one_chip)
    _compile(lambda x, h: countsketch_clients_pallas(
        x, h, BERT_B_TOTAL, interpret=False), x, h)


def test_fwht_rows_compiles(one_chip):
    x = jax.ShapeDtypeStruct((64, 4096), jnp.float32, sharding=one_chip)
    _compile(lambda x: fwht_rows_pallas(x, interpret=False), x)


@pytest.mark.parametrize("b", [128, 1024])
def test_gaussian_sk_compiles(one_chip, b):
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32, sharding=one_chip)
    _compile(lambda s, x: gaussian_sk_pallas(s, x, b, interpret=False),
             seed, x)


@pytest.mark.parametrize("b", [128, 1024])
def test_gaussian_desk_compiles(one_chip, b):
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one_chip)
    _compile(lambda seed, s: gaussian_desk_pallas(seed, s, 1 << 20,
                                                  interpret=False), seed, s)
