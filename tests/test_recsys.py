"""RecSys substrate: embedding bag, two-tower training and serving."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models.recsys.two_tower import (
    TwoTowerConfig, embedding_bag, init_two_tower, item_embedding,
    score_candidates, serve_user_tower, two_tower_loss,
)

CFG = TwoTowerConfig(
    embed_dim=16, tower_mlp=(32, 16), n_user_fields=3, n_item_fields=2,
    bag_size=4, user_vocab=500, item_vocab=500,
)


def _params():
    return init_two_tower(jax.random.PRNGKey(0), CFG)


@given(n_bags=st.integers(1, 10), bag=st.integers(1, 6))
@settings(max_examples=10, deadline=None)
def test_embedding_bag_property(n_bags, bag):
    """sum-mode bag == explicit loop; permutation of ids inside a bag is
    invariant."""
    rng = np.random.default_rng(n_bags * 7 + bag)
    table = jnp.asarray(rng.standard_normal((100, 8)).astype(np.float32))
    ids = rng.integers(0, 100, (n_bags, bag))
    flat = jnp.asarray(ids.reshape(-1).astype(np.int32))
    segs = jnp.asarray(np.repeat(np.arange(n_bags), bag).astype(np.int32))
    out = embedding_bag(table, flat, segs, n_bags)
    ref = np.stack([np.asarray(table)[ids[i]].sum(0) for i in range(n_bags)])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    # permutation invariance
    perm_ids = np.stack([rng.permutation(ids[i]) for i in range(n_bags)])
    out2 = embedding_bag(
        table, jnp.asarray(perm_ids.reshape(-1).astype(np.int32)), segs, n_bags
    )
    np.testing.assert_allclose(out, out2, rtol=1e-5, atol=1e-5)


def test_train_improves_retrieval_accuracy():
    from repro.optim.adamw import adamw_init, adamw_update

    params = _params()
    opt = adamw_init(params)
    rng = np.random.default_rng(0)
    # correlated user/item ids so there is signal
    base = rng.integers(0, 500, (64,))
    uids = jnp.asarray(
        np.stack([base] * CFG.n_user_fields, 1)[:, :, None]
        .repeat(CFG.bag_size, 2).astype(np.int32)
    )
    iids = jnp.asarray(
        np.stack([base] * CFG.n_item_fields, 1)[:, :, None]
        .repeat(CFG.bag_size, 2).astype(np.int32)
    )

    @jax.jit
    def step(p, o):
        (l, acc), g = jax.value_and_grad(
            lambda pp: two_tower_loss(pp, uids, iids, CFG), has_aux=True
        )(p)
        p2, o2 = adamw_update(g, p, o, lr=3e-3)
        return p2, o2, l, acc

    accs = []
    for _ in range(30):
        params, opt, l, acc = step(params, opt)
        accs.append(float(acc))
    assert accs[-1] > accs[0] + 0.3


def test_serve_and_retrieval_shapes():
    params = _params()
    rng = np.random.default_rng(1)
    uids = jnp.asarray(rng.integers(0, 500, (8, 3, 4)).astype(np.int32))
    emb = serve_user_tower(params, uids, CFG)
    assert emb.shape == (8, 16)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(emb), axis=-1), 1.0, rtol=1e-4
    )
    cand = item_embedding(
        params, jnp.asarray(rng.integers(0, 500, (200, 2, 4)).astype(np.int32)),
        CFG,
    )
    vals, idx = score_candidates(params, uids[:1], cand, CFG, top_k=10)
    assert vals.shape == (1, 10) and idx.shape == (1, 10)
    # scores sorted descending
    assert np.all(np.diff(np.asarray(vals)[0]) <= 1e-6)
    # top-1 really is the argmax
    u = serve_user_tower(params, uids[:1], CFG)
    full = np.asarray(u @ cand.T)[0]
    assert int(idx[0, 0]) == int(np.argmax(full))
