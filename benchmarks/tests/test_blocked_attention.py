"""The query-blocked attention a long-context reference calls equals
``decoder.attention`` at a size both can run: full, windowed, grouped and
not, at lengths that are and are not whole blocks; and its key mask hides
what it says."""

import numpy as np
import pytest

from benchmarks.reference import decoder


def _qkv(T, H, K, D, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((T, H, D), dtype=np.float32),
            rng.standard_normal((T, K, D), dtype=np.float32),
            rng.standard_normal((T, K, D), dtype=np.float32))


@pytest.mark.parametrize("window", [0, 24, 1])
@pytest.mark.parametrize("T, H, K, block", [(96, 4, 2, 32), (70, 4, 4, 16), (50, 8, 1, 64)])
def test_equals_the_unblocked_attention(T, H, K, block, window):
    q, k, v = _qkv(T, H, K, 16)
    want = np.asarray(decoder.attention(q, k, v, window))
    got = np.asarray(decoder.attention_blocked(q, k, v, window, block=block))
    assert got.shape == want.shape == (T, H * 16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_a_key_mask_selects_blocks_of_keys_per_query_and_kv_head():
    import jax
    import jax.numpy as jnp

    T, H, K, D, kb = 64, 4, 2, 8, 8
    q, k, v = _qkv(T, H, K, D, seed=1)
    # each query of each kv head may see its own block of 8 keys, the first
    # block, and every third block besides
    nb = T // kb
    rng = np.random.default_rng(2)
    keep = rng.random((K, T, nb)) < 0.34
    keep[:, :, 0] = True
    keep[:, np.arange(T), np.arange(T) // kb] = True

    def key_mask(lo, n):
        rows = jax.lax.dynamic_slice_in_dim(jnp.asarray(keep), lo, n, axis=1)
        return jnp.repeat(rows, kb, axis=2)  # [K, n, T]

    got = np.asarray(decoder.attention_blocked(q, k, v, key_mask=key_mask, block=16))
    # by hand: a dense masked softmax, head by head
    want = np.zeros((T, H, D), np.float32)
    g = H // K
    full = np.repeat(keep, kb, axis=2) & (np.arange(T)[None, :] <= np.arange(T)[:, None])
    for hd in range(H):
        s = q[:, hd] @ k[:, hd // g].T / np.sqrt(D)
        s = np.where(full[hd // g], s, -np.inf)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        want[:, hd] = (p / p.sum(axis=-1, keepdims=True)) @ v[:, hd // g]
    np.testing.assert_allclose(got, want.reshape(T, H * D), rtol=1e-4, atol=1e-5)
    # and it is not the unmasked result
    assert np.abs(got - np.asarray(decoder.attention(q, k, v, 0))).max() > 1e-2


def test_the_largest_transient_is_a_block_of_queries_not_all_of_them():
    import jax

    T, H, K, D, block = 4096, 4, 1, 16, 128
    q, k, v = (jax.ShapeDtypeStruct(s, "float32")
               for s in ((T, H, D), (T, K, D), (T, K, D)))
    temp = lambda f: jax.jit(f).lower(q, k, v).compile().memory_analysis().temp_size_in_bytes  # noqa: E731
    blocked = temp(lambda q, k, v: decoder.attention_blocked(q, k, v, block=block))
    whole = temp(lambda q, k, v: decoder.attention(q, k, v, 0))
    scores = H * T * T * 4
    assert whole >= scores  # [g, T, T] scores at the least
    assert blocked <= 8 * (H * block * T * 4)  # a few [g, block, T] arrays
