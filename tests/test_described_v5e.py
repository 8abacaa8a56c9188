"""Programs compiled for a described TPU v5e, no chip attached: what the
chip's compiler makes of them, which the CPU's does not show.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and it keeps it until it exits.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ring_attention_tpu.models import RingTransformer

GIB = 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)


def test_prefill_holds_no_second_residual_stream(one_chip):
    """The prefill of StarCoder2-7B's widths (two layers, 2,048 positions,
    bfloat16, the flash kernel) keeps in temporaries the feed-forward's
    hidden rows and at most two copies of the residual stream: neither the
    (n, vocab) logits nor, as a slice of the (b, n, d) stream made XLA do,
    every layer's output held to rebuild the stream in that shape."""
    n, dim, vocab = 2048, 4608, 49152
    model = RingTransformer(
        num_tokens=vocab, dim=dim, depth=2, heads=36, dim_head=128,
        kv_heads=4, causal=True, use_ring=False, mesh=None,
        use_pallas=True, bucket_size=256)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype,
            sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    cache = on_chip(jax.eval_shape(lambda: model.apply(
        {}, 1, 2 * n, method=RingTransformer.init_cache)))
    tokens = jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=one_chip)

    def prefill(p, t, c):
        logits, c = model.apply(p, t, c, method=RingTransformer.prefill)
        return jnp.argmax(logits, axis=-1), c

    memory = jax.jit(prefill, donate_argnums=2).lower(
        params, tokens, cache).compile().memory_analysis()
    hidden, stream = n * 4 * dim * 2, n * dim * 2
    assert memory.temp_size_in_bytes <= hidden + 2 * stream, (
        memory.temp_size_in_bytes / GIB)
