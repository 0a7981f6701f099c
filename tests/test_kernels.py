"""Per-kernel shape/dtype sweeps of the LM and recsys Pallas kernels vs
pure-jnp oracles and the model functions they mirror (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.embedding_bag import (
    embedding_bag_kernel_call, embedding_bag_ref,
)
from repro.kernels.flash_attention import attention_ref, flash_attention


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5
    )


class TestEmbeddingBag:
    @pytest.mark.parametrize("V,D,nb,bs", [(500, 64, 16, 8), (1000, 128, 8, 4),
                                           (256, 96, 32, 16)])
    @pytest.mark.parametrize("mode", ["sum", "mean"])
    def test_shapes(self, V, D, nb, bs, mode, rng):
        table = jnp.asarray(rng.standard_normal((V, D)).astype(np.float32))
        ids = jnp.asarray(rng.integers(0, V, (nb, bs)).astype(np.int32))
        out = embedding_bag_kernel_call(table, ids, mode=mode)
        ref = embedding_bag_ref(table, ids, mode=mode)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_matches_model_embedding_bag(self, rng):
        """Kernel == the model-level take+segment_sum EmbeddingBag."""
        from repro.models.recsys.two_tower import embedding_bag as model_bag

        V, D, nb, bs = 300, 64, 8, 4
        table = jnp.asarray(rng.standard_normal((V, D)).astype(np.float32))
        ids = rng.integers(0, V, (nb, bs)).astype(np.int32)
        out = embedding_bag_kernel_call(table, jnp.asarray(ids), mode="sum")
        bag_ids = np.repeat(np.arange(nb), bs).astype(np.int32)
        ref = model_bag(
            table, jnp.asarray(ids.reshape(-1)), jnp.asarray(bag_ids), nb
        )
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize(
        "B,S,Hq,Hkv,D", [(1, 128, 4, 4, 32), (2, 256, 8, 2, 64),
                         (1, 512, 4, 1, 128)]
    )
    @pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                               (False, None)])
    def test_shapes(self, B, S, Hq, Hkv, D, causal, window, rng):
        q = jnp.asarray(rng.standard_normal((B, S, Hq, D)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)).astype(np.float32))
        out = flash_attention(q, k, v, causal=causal, window=window)
        ref = attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_bf16(self, rng):
        B, S, Hq, Hkv, D = 1, 256, 4, 2, 64
        mk = lambda h: jnp.asarray(
            rng.standard_normal((B, S, h, D)).astype(np.float32)
        ).astype(jnp.bfloat16)
        q, k, v = mk(Hq), mk(Hkv), mk(Hkv)
        out = flash_attention(q, k, v)
        ref = attention_ref(q, k, v)
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32),
            rtol=3e-2, atol=3e-2,
        )

    def test_matches_chunked_model_attention(self, rng):
        """Kernel == models/lm/attention.chunked_attention."""
        from repro.models.lm.attention import chunked_attention

        B, S, Hq, Hkv, D = 2, 256, 8, 2, 32
        q = jnp.asarray(rng.standard_normal((B, S, Hq, D)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)).astype(np.float32))
        out = flash_attention(q, k, v, causal=True, window=32)
        ref = chunked_attention(
            q, k, v, causal=True, window=32, q_chunk=64, kv_chunk=64
        )
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
