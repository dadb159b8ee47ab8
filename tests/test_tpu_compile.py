"""The main-path Pallas kernels compile for a TPU v5e at ann-laion widths.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, unaligned DMA windows, ops it does
not lower. These tests hand the TPU compiler a described ``v5e:2x2``
chip — no chip needed — and compile each kernel at the shapes the
ann-laion deployment serves: d'=600 (PCA600), R=32 (NSG32), ef=64,
1024-query batches, PQ300 codes over the ~270k rows AntiHub keeps of 300k
(their ADC table centroid-major, (Q, C, M), as the kernels take it).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and it keeps it until it
exits.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.beam_hop import beam_hop_pallas
from repro.kernels.gather_dist.gather_dist import gather_dist_pallas
from repro.kernels.lut_dist.lut_dist import lut_dist_pallas
from repro.kernels.topk_merge.topk_merge import topk_merge_pallas

Q, D, R, EF, M, C, N = 1024, 600, 32, 64, 300, 256, 270_000
# NN-Descent merges (B, K) table rows with (B, U) candidates, B = 2048
MERGE_ROWS, MERGE_WIDTH, MERGE_K = 2048, 80, 20

i32, f32, u8 = jnp.int32, jnp.float32, jnp.uint8
HOP = ((Q,), i32), ((N, R), i32), ((Q, EF), i32), ((Q, EF), f32), \
    ((Q, EF), jnp.bool_)
CASES = {
    "beam_hop_f32": (
        functools.partial(beam_hop_pallas, dist_backend="f32",
                          interpret=False),
        HOP + (((Q, D), f32), ((N, D), f32))),
    "beam_hop_pq": (
        functools.partial(beam_hop_pallas, dist_backend="pq",
                          interpret=False),
        HOP + (((Q, C, M), f32), ((N, M), u8))),
    "gather_dist": (
        functools.partial(gather_dist_pallas, interpret=False),
        (((Q, D), f32), ((N, D), f32), ((Q, R), i32))),
    "lut_dist": (
        functools.partial(lut_dist_pallas, interpret=False),
        (((Q, C, M), f32), ((N, M), u8), ((Q, R), i32))),
    "topk_merge": (
        functools.partial(topk_merge_pallas, k=MERGE_K, interpret=False),
        (((MERGE_ROWS, MERGE_WIDTH), i32), ((MERGE_ROWS, MERGE_WIDTH), f32),
         ((MERGE_ROWS, MERGE_WIDTH), jnp.bool_))),
}


@pytest.fixture(scope="module")
def v5e_chip():
    """One device of a described v5e:2x2, with the compile cache off (a
    compile for a described chip is written to it but cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e_chip, kernel):
    fn, shapes = CASES[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=v5e_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
