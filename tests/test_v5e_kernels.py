"""The session runtime's latent-attention kernel compiled for a described
TPU v5e at the widths of the benchmark's cell ``axk1_serve_doc32k`` (no chip
is needed: the compiler refuses here what it would refuse there). A decode
step's call (every session slot, one query of 64 heads) and a prompt
chunk's (64 queries of one session in row blocks of 512)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_embeddings_tpu.ops.latent_attention import latent_attention

# k_pe (64 wide) sits in the cache zero-padded to 128 lanes
# (MLALMConfig.pe_lanes)
SESSIONS, HEADS, KL, PE, CAP = 24, 64, 512, 128, 36864


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("entries,queries,rows", [(SESSIONS, 1, HEADS),
                                                  (1, 64, 512)])
def test_the_latent_attention_kernel_compiles_for_the_v5e(one_chip, entries,
                                                          queries, rows):
    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda qc, qpe, c, pe, slots, lens: latent_attention(
        qc, qpe, c, pe, slots, lens, heads=HEADS, scale=0.13, rows=rows))
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = fn.lower(
            arg((entries, queries * HEADS, KL)),
            arg((entries, queries * HEADS, PE)), arg((SESSIONS, CAP, KL)),
            arg((SESSIONS, CAP, PE)), arg((entries,), jnp.int32),
            arg((entries,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
