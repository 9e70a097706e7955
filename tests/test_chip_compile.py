"""AOT compiles of the served Pallas kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a topology that is only described.  It refuses what the
chip would refuse — blocks off the (8, 128) tiling, too much VMEM — which
the interpret-mode sweeps in ``test_kernels.py`` cannot see.  Shapes are
granite-3-2b's serving widths: bf16, 32 query / 8 KV heads, head_dim 64,
16-token pages, 8 slots of a 1024-token window.

All four compiles stay in this one file, on one worker: the process
that describes the topology holds the TPU library until it exits.
"""

from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.spec_verify_attention import spec_verify_attention
from repro.kernels.topk_sim import topk_similarity

H, KV, HD, PAGE = 32, 8, 64, 16
B, N_SLOTS = 8, 64                # 8 slots x 1024 / 16 table entries
N_PAGES = B * N_SLOTS + 1         # the engine's pool, plus its dump page
SPEC_K = 8                        # the engine's default draft length
BF, I32 = jnp.bfloat16, jnp.int32

POOL = (N_PAGES, PAGE, KV, HD)
CASES = {
    "paged_decode": (
        lambda *a: paged_decode_attention(*a, interpret=False),
        [((B, 1, H, HD), BF), (POOL, BF), (POOL, BF),
         ((B, N_SLOTS), I32), ((B,), I32)]),
    "spec_verify": (
        lambda *a: spec_verify_attention(*a, interpret=False),
        [((B, SPEC_K + 1, H, HD), BF), (POOL, BF), (POOL, BF),
         ((B, N_SLOTS), I32), ((B,), I32)]),
    "chunked_prefill": (
        lambda *a: chunked_prefill_attention(*a, interpret=False),
        [((B, 256, H, HD), BF), ((B, 256, KV, HD), BF),
         ((B, 256, KV, HD), BF), ((B, 512, KV, HD), BF),
         ((B, 512, KV, HD), BF), ((B,), I32)]),
    # prefilter at the marketplace scale: 10^3 requests x 10^4 offers,
    # d_model-wide embeddings
    "topk": (
        lambda a, b: topk_similarity(a, b, 8, interpret=False),
        [((1000, 2048), jnp.float32), ((10000, 2048), jnp.float32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off: an entry compiled for a described chip cannot be read
    back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = CASES[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
