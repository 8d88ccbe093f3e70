"""Compile rehearsals of the BaM hot path for one chip of a described TPU
v5e, at the deployment shapes ``chip_smoke.py`` drives (a 65,536-set x
4-way directory of 4 KiB float32 lines = a 1 GiB cache, and a 4096-lane
wavefront).

Nothing runs on a chip here: each test compiles with the TPU compiler that
ships with JAX, so a kernel the compiler refuses (tiling, VMEM, an
unsupported op) fails here instead of on the chip.  Each test compiles the
implementation the main path resolves to on a TPU backend
(``ops.resolve_impl``); the ``on_tpu`` fixture steers that rule, which
would otherwise see this process's CPU backend.

The topology is described only inside the module-scoped fixture: the TPU
library may be loaded by one process at a time, so it must never be
touched while a module is imported.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import BamArray, IORequest
from repro.core import cache as C
from repro.core import queues as Q
from repro.core.bam_array import BamState
from repro.core.metrics import IOMetrics
from repro.core.storage import SimStorage
from repro.kernels import ops

NUM_SETS, WAYS, LINE_ELEMS, WAVEFRONT = 65536, 4, 1024, 4096
N_ELEMS = 1 << 30
HBM_BYTES = 16 << 30                     # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip; check the program fits its HBM."""
    compiled = jax.jit(fn).lower(*args).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need < HBM_BYTES, f"{need} bytes do not fit one v5e chip"
    return compiled.as_text()


def _has_kernel(hlo: str) -> bool:
    return "tpu_custom_call" in hlo


def test_auto_rule_on_tpu(on_tpu):
    assert ops.resolve_impl("gather_blocks") == "pallas"
    assert ops.resolve_impl("cache_probe") == "pallas"
    assert ops.resolve_impl("probe_allocate") == "ref"
    assert ops.resolve_impl("cache_probe", platform="cpu") == "ref"


def test_gather_blocks_compiles_for_v5e(one_chip, on_tpu):
    hlo = _compile(
        lambda d, s, o: ops.gather_blocks(d, s, off=o),
        _spec((NUM_SETS * WAYS, LINE_ELEMS), jnp.float32, one_chip),
        _spec((WAVEFRONT,), jnp.int32, one_chip),
        _spec((WAVEFRONT,), jnp.int32, one_chip))
    assert _has_kernel(hlo) == (ops.resolve_impl("gather_blocks")
                                == "pallas")


def test_cache_probe_compiles_for_v5e(one_chip, on_tpu):
    d = _spec((NUM_SETS, WAYS), jnp.int32, one_chip)
    hlo = _compile(lambda t, o, k: ops.cache_probe(t, k, owner=o), d, d,
                   _spec((WAVEFRONT,), jnp.int32, one_chip))
    assert _has_kernel(hlo) == (ops.resolve_impl("cache_probe")
                                == "pallas")


def test_probe_allocate_compiles_for_v5e(one_chip, on_tpu):
    d = _spec((NUM_SETS, WAYS), jnp.int32, one_chip)
    b = _spec((NUM_SETS, WAYS), jnp.bool_, one_chip)
    k = _spec((WAVEFRONT,), jnp.int32, one_chip)
    hlo = _compile(
        lambda t, o, r, dy, sp, h, keys: ops.probe_allocate(
            t, o, r, dy, sp, h, keys),
        d, d, d, b, b, _spec((NUM_SETS,), jnp.int32, one_chip), k)
    assert _has_kernel(hlo) == (ops.resolve_impl("probe_allocate")
                                == "pallas")


def test_submit_wait_round_compiles_for_v5e(one_chip, on_tpu):
    """The fused, donated submit -> wait round over a 4 GiB host-side
    tier (its fetch and write-back are host callbacks) and a 1 GiB HBM
    cache — the executable ``chip_smoke.py``'s traffic runs."""
    n_blocks = N_ELEMS // LINE_ELEMS
    # a zero-stride view stands in for the 4 GiB tier: compiling never
    # calls the host callbacks that would read it
    tier = np.broadcast_to(np.zeros((1, LINE_ELEMS), np.float32),
                           (n_blocks, LINE_ELEMS))
    arr = BamArray(storage=SimStorage(tier), shape=(N_ELEMS,),
                   dtype=jnp.dtype(jnp.float32), block_elems=LINE_ELEMS)

    def state():
        return BamState(
            cache=C.make_cache(NUM_SETS, WAYS, LINE_ELEMS, jnp.float32),
            queues=Q.make_queues(16, 1024, n_devices=1,
                                 stripe_blocks=arr.ssd.stripe_blocks),
            metrics=IOMetrics.zeros(1), storage=None)

    st = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                      jax.eval_shape(state))
    req = IORequest.read(_spec((WAVEFRONT,), jnp.int32, one_chip),
                         _spec((WAVEFRONT,), jnp.bool_, one_chip))
    compiled = arr.submit_wait_jit(donate=True).lower(st, req).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= NUM_SETS * WAYS * LINE_ELEMS * 4, \
        "the 1 GiB line array must be donated, not copied"
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes) < HBM_BYTES
    assert _has_kernel(compiled.as_text())
