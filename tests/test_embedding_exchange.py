"""ICI embedding federation: deterministic embedder, all-gather exchange on
the CPU mesh, cross-node similarity recall."""

import jax
import numpy as np
import pytest

from fei_tpu.memory.memorychain.embedding_exchange import (
    EmbeddingFederation,
    exchange_banks,
    hash_embed,
)
from fei_tpu.parallel.mesh import make_mesh


class TestHashEmbed:
    def test_deterministic_across_calls(self):
        a = hash_embed("ring attention rotates kv blocks")
        b = hash_embed("ring attention rotates kv blocks")
        np.testing.assert_array_equal(a, b)

    def test_normalized_and_discriminative(self):
        a = hash_embed("paged kv cache block tables")
        b = hash_embed("feicoin wallet reward balance")
        assert abs(np.linalg.norm(a) - 1.0) < 1e-5
        assert float(a @ b) < 0.5  # unrelated topics stay far apart

    def test_similar_texts_score_higher(self):
        q = hash_embed("pallas flash attention kernel")
        close = hash_embed("the flash attention pallas kernel for prefill")
        far = hash_embed("maildir folder hierarchy statistics")
        assert float(q @ close) > float(q @ far)


@pytest.fixture(scope="module")
def node_mesh():
    n = 4 if len(jax.devices()) >= 4 else len(jax.devices())
    return make_mesh({"dp": n}, devices=jax.devices()[:n])


class TestExchange:
    def test_all_gather_gives_every_node_every_bank(self, node_mesh):
        n = node_mesh.shape["dp"]
        rng = np.random.default_rng(0)
        banks = rng.normal(size=(n, 8, 16)).astype(np.float32)
        out = np.asarray(exchange_banks(banks, node_mesh))
        assert out.shape == (n, n, 8, 16)
        for node in range(n):
            np.testing.assert_allclose(out[node], banks, atol=1e-6)


class TestFederation:
    def test_cross_node_recall(self, node_mesh):
        n = node_mesh.shape["dp"]
        feds = [
            EmbeddingFederation(i, n, bank_size=8, dim=64) for i in range(n)
        ]
        # each node remembers something different
        topics = [
            ("m-kernels", "pallas flash attention kernel tiling"),
            ("m-memdir", "maildir atomic delivery tmp new cur"),
            ("m-chain", "proof of work consensus quorum voting"),
            ("m-mesh", "device mesh sharding collectives ici"),
        ]
        for i, fed in enumerate(feds):
            mem_id, text = topics[i % len(topics)]
            fed.add(f"{mem_id}@{i}", text)

        all_banks = np.stack([f.local_bank for f in feds])
        ids = [list(f._ids) for f in feds]
        for fed in feds:
            fed.sync(node_mesh, all_banks)
            fed.install_global(np.asarray(fed._global), ids)

        # node 0 recalls node 1's memory by content
        hits = feds[0].search("atomic maildir delivery", top_k=2)
        assert hits
        assert hits[0]["id"] == f"m-memdir@{1 % n}"
        assert hits[0]["node"] == 1 % n

    def test_local_fallback_before_sync(self):
        fed = EmbeddingFederation(0, 4, bank_size=4, dim=64)
        fed.add("m1", "grpc transport over dcn")
        hits = fed.search("dcn grpc transport")
        assert hits and hits[0]["id"] == "m1"

    def test_ring_buffer_overwrites(self):
        fed = EmbeddingFederation(0, 1, bank_size=2, dim=32)
        fed.add("a", "alpha")
        fed.add("b", "beta")
        slot = fed.add("c", "gamma")  # wraps onto slot 0
        assert slot == 0
        assert fed._ids == ["c", "b"]

    def test_rejects_bad_node_index(self):
        with pytest.raises(ValueError):
            EmbeddingFederation(5, 4)


class TestMultiNodePerDevice:
    def test_more_nodes_than_devices(self, node_mesh):
        """num_nodes = 2x devices: no bank may be dropped."""
        n = node_mesh.shape["dp"]
        rng = np.random.default_rng(1)
        banks = rng.normal(size=(2 * n, 4, 8)).astype(np.float32)
        out = np.asarray(exchange_banks(banks, node_mesh))
        assert out.shape == (n, 2 * n, 4, 8)
        for row in range(n):
            np.testing.assert_allclose(out[row], banks, atol=1e-6)

    def test_rejects_indivisible_nodes(self, node_mesh):
        n = node_mesh.shape["dp"]
        if n == 1:
            import pytest as _pytest

            _pytest.skip("needs >1 device")
        banks = np.zeros((n + 1, 4, 8), dtype=np.float32)
        with pytest.raises(ValueError):
            exchange_banks(banks, node_mesh)
