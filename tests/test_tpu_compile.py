"""Compile the Gram kernels for a described TPU v5e chip, at real size.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what the chip's compiler would
refuse (unaligned blocks, scoped-VMEM overflow, lowering faults) — what
interpret-mode tests cannot see.  The shape is the smoke run's stats pass:
m = 8 agents, N = 8192 samples, L = 2048 features (d_in = 4096 raw inputs
for the fused producer), (512, 128) blocks.  Nothing runs, so this says
nothing about results or times.
"""

from __future__ import annotations

import os

import pytest

M, N, L, D, D_IN = 8, 8192, 2048, 10, 4096
BLOCK_N, BLOCK_L = 512, 128


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent compile
    cache off (a compile for an absent chip cannot be read back)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _case(name):
    import jax.numpy as jnp

    from repro.kernels.gram import kernel as K

    f32, bf16 = jnp.float32, jnp.bfloat16
    tri = dict(block_l=BLOCK_L, block_n=BLOCK_N)
    if name in ("tri_fp32", "tri_bf16"):
        dt = f32 if name == "tri_fp32" else bf16
        return (lambda H, T: K.gram_pallas_tri(H, T, **tri),
                [((M, N, L), dt), ((M, N, D), dt)])
    if name == "tri_int8":
        return (lambda Hq, s, T: K.gram_pallas_tri_q(Hq, s, T, **tri),
                [((M, N, L), jnp.int8),
                 ((M, N // BLOCK_N, L // BLOCK_L), f32),
                 ((M, N, D), bf16)])
    dt = f32 if name == "fused_fp32" else bf16
    return (lambda X, W, b, T: K.gram_pallas_fused(
                X, W, b, T, n_true=N, l_true=L, compute_dtype=dt, **tri),
            [((M, N, D_IN), f32), ((D_IN, L), f32), ((1, L), f32),
             ((M, N, D), dt)])


@pytest.mark.parametrize("name", [
    "tri_fp32", "tri_bf16", "tri_int8", "fused_fp32", "fused_bf16",
])
def test_gram_kernel_compiles_for_v5e(one_chip, name):
    import jax

    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
