"""AOT compiles of the main-path kernels for a TPU v5e, without the chip.

The membership kernel (``member`` / ``signed_member``) is the kernel family
the default path runs (``repro.kernels.on_default_path``).  Interpret mode
cannot see what Mosaic refuses — 64-bit vectors, vector gathers, misaligned
blocks — so each case lowers the jitted probe for a described v5e device at
serving widths (a 2^26-entry base region, delta regions, 2048 queries) and
compiles it, with int32 keys and with int64 / composite keys carried as
int32 word pairs.  A second check compares HBM temporaries with the jnp
probe: on a TPU, XLA materializes an int64 array's 32-bit halves whenever
an op reads it, so any probe of an int64 region holds O(capacity)
temporaries; the kernel's operands must add nothing of that order on top
(a whole-region word split would add two capacity-sized int32 arrays).  The topology is described inside the module fixture, so
collection never loads the TPU library; the persistent compilation cache is
off around these compiles (an entry written for a described chip cannot be
read back without one).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import csr
from repro.kernels.intersect.ops import _signed_member_jit, member

BASE_CAP = 1 << 26  # the base region of chip_smoke's scale-22 R-MAT graph
DELTA_CAP = 4096
QUERIES = 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to rehearse
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            cc.reset_cache()


KEYS = {  # name: (hi-word dtype, composite lo word?)
    "int32": (jnp.int32, False),
    "int64-pair": (jnp.int64, False),
    "composite-int32-hi": (jnp.int32, True),
    "composite-int64-hi": (jnp.int64, True),
}


@pytest.mark.parametrize("regions", ["versioned", "one"])
@pytest.mark.parametrize("keys", list(KEYS))
def test_member_kernel_compiles_for_v5e(one_chip, keys, regions):
    key_dtype, composite = KEYS[keys]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def region(cap):
        n = sds((), jnp.int32)
        if composite:
            return (sds((cap,), key_dtype), sds((cap,), jnp.int64),
                    sds((cap,), jnp.int32), n)
        return (sds((cap,), key_dtype), sds((cap,), jnp.int32), n)

    # a versioned index: base + committed + staged inserts, two deletes
    caps = ((BASE_CAP, DELTA_CAP, DELTA_CAP, DELTA_CAP, DELTA_CAP)
            if regions == "versioned" else (BASE_CAP,))
    num_pos = 3 if regions == "versioned" else 1
    ql = sds((QUERIES,), jnp.int64) if composite else None
    lowered = _signed_member_jit.lower(
        tuple(region(c) for c in caps), sds((QUERIES,), key_dtype),
        sds((QUERIES,), jnp.int32), num_pos=num_pos, interpret=False, ql=ql)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("keys", list(KEYS))
def test_member_kernel_temporaries_within_jnp_probe(one_chip, keys):
    key_dtype, composite = KEYS[keys]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    idx = csr.IndexData(sds((BASE_CAP,), key_dtype),
                        sds((BASE_CAP,), jnp.int32), sds((), jnp.int32),
                        sds((BASE_CAP,), jnp.int64) if composite else None)
    qk = sds((QUERIES,), key_dtype)
    ql = sds((QUERIES,), jnp.int64) if composite else None
    qv = sds((QUERIES,), jnp.int32)

    def kernel(i, a, b, c):
        return member(i.key, i.val, i.n, a, b, interpret=False, los=i.lo,
                      ql=c)

    def jnp_probe(i, a, b, c):
        return csr.index_member(i, a if c is None else (a, c), b)

    def temp_bytes(f):
        return jax.jit(f).lower(idx, qk, qv, ql).compile() \
            .memory_analysis().temp_size_in_bytes

    words = 4 * BASE_CAP  # one capacity-sized int32 word array
    assert temp_bytes(kernel) <= temp_bytes(jnp_probe) + words // 16
