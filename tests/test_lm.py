"""LM substrate: attention variants, MoE, decode==forward consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.lm.attention import chunked_attention
from repro.models.lm.moe import MoEConfig, init_moe_params, moe_ffn
from repro.models.lm.transformer import (
    LMConfig, init_kv_cache, init_lm_params, lm_decode_step, lm_forward,
    lm_loss,
)
from repro.kernels.flash_attention.ref import attention_ref


def _dense_cfg(**kw):
    base = dict(
        name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        d_ff=64, vocab=128, dtype=jnp.float32, q_chunk=8, kv_chunk=8,
        remat=False,
    )
    base.update(kw)
    return LMConfig(**base)


class TestChunkedAttention:
    @given(
        s=st.sampled_from([32, 64, 128]),
        window=st.sampled_from([None, 16]),
        qc=st.sampled_from([8, 16, 32]),
    )
    @settings(max_examples=10, deadline=None)
    def test_matches_reference(self, s, window, qc):
        rng = np.random.default_rng(0)
        B, Hq, Hkv, D = 2, 4, 2, 16
        q = jnp.asarray(rng.standard_normal((B, s, Hq, D)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((B, s, Hkv, D)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((B, s, Hkv, D)).astype(np.float32))
        out = chunked_attention(q, k, v, causal=True, window=window,
                                q_chunk=qc, kv_chunk=qc)
        ref = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


@pytest.mark.slow
class TestDecodeConsistency:
    def _roundtrip(self, cfg, T=16):
        params = init_lm_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0, cfg.vocab)
        full, _ = lm_forward(params, toks, cfg)
        cache = init_kv_cache(cfg, 1, T)
        outs = []
        for t in range(T):
            lg, cache = lm_decode_step(
                params, cache, toks[:, t:t + 1], jnp.int32(t + 1), cfg
            )
            outs.append(lg)
        dec = jnp.stack(outs, axis=1)
        return float(
            jnp.max(jnp.abs(dec - full)) / jnp.max(jnp.abs(full))
        )

    def test_gqa(self):
        assert self._roundtrip(_dense_cfg()) < 2e-5

    def test_swa(self):
        assert self._roundtrip(_dense_cfg(window=8)) < 2e-5

    def test_mla(self):
        cfg = _dense_cfg(
            attn_type="mla", d_model=48, q_lora=32, kv_lora=24,
            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, n_heads=4,
            n_kv_heads=4, d_head=16,
        )
        assert self._roundtrip(cfg) < 2e-4

    def test_moe(self):
        cfg = _dense_cfg(
            moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                          capacity_factor=8.0, groups=1),
        )
        assert self._roundtrip(cfg) < 2e-5

    def test_moe_shared_first_dense(self):
        cfg = _dense_cfg(
            n_layers=3,
            moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32, n_shared=1,
                          d_ff_shared=32, first_dense=1, d_ff_dense=64,
                          capacity_factor=8.0, groups=1),
        )
        assert self._roundtrip(cfg) < 2e-5


class TestMoE:
    def test_group_invariance_at_high_capacity(self):
        p = init_moe_params(
            jax.random.PRNGKey(3), 32,
            MoEConfig(4, 2, 48, groups=1, capacity_factor=8.0),
        )
        x = jax.random.normal(jax.random.PRNGKey(4), (64, 32))
        y1, _ = moe_ffn(p, x, MoEConfig(4, 2, 48, groups=1, capacity_factor=8.0))
        y4, _ = moe_ffn(p, x, MoEConfig(4, 2, 48, groups=4, capacity_factor=8.0))
        np.testing.assert_allclose(y1, y4, rtol=1e-6, atol=1e-6)

    def test_capacity_drops_tokens(self):
        """With tiny capacity, overflow tokens route to the null slot."""
        p = init_moe_params(
            jax.random.PRNGKey(3), 16, MoEConfig(2, 1, 16),
        )
        x = jax.random.normal(jax.random.PRNGKey(5), (64, 16))
        y_full, _ = moe_ffn(p, x, MoEConfig(2, 1, 16, capacity_factor=8.0, groups=1))
        y_tight, _ = moe_ffn(p, x, MoEConfig(2, 1, 16, capacity_factor=0.25, groups=1))
        # tight capacity zeroes some rows
        dropped = np.sum(np.all(np.abs(np.asarray(y_tight)) < 1e-9, axis=-1))
        assert dropped > 0
        assert not np.allclose(y_full, y_tight)

    def test_aux_loss_near_one_for_uniform(self):
        p = init_moe_params(jax.random.PRNGKey(0), 16, MoEConfig(4, 1, 16))
        x = jax.random.normal(jax.random.PRNGKey(1), (512, 16))
        _, aux = moe_ffn(p, x, MoEConfig(4, 1, 16, groups=1))
        assert 0.8 < float(aux) < 2.0


class TestTraining:
    def test_loss_decreases(self):
        from repro.optim.adamw import adamw_init, adamw_update

        cfg = _dense_cfg()
        params = init_lm_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)

        @jax.jit
        def step(p, o):
            (l, _), g = jax.value_and_grad(
                lambda pp: lm_loss(pp, toks, cfg), has_aux=True
            )(p)
            p2, o2 = adamw_update(g, p, o, lr=3e-3)
            return p2, o2, l

        losses = []
        for _ in range(12):
            params, opt, l = step(params, opt)
            losses.append(float(l))
        assert losses[-1] < losses[0] - 0.3
