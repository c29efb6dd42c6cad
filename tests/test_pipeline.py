"""Pipeline-parallelism tests (SPMD collective-permute pipelining).

Invariant (reference checks loss-curve equivalence across pp configs):
pp2 / pp4 training trajectories == pp1, including with dp/tp inside
stages and multiple micro-batches.
"""
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import hetu_tpu as ht
from hetu_tpu import optim
from hetu_tpu.graph import ctor
from hetu_tpu.models.gpt import llama_config
from hetu_tpu.models.gpt_pipeline import GPTPipelineModel


# full-model training loops: excluded from the dev fast path
pytestmark = pytest.mark.slow


def _train(mesh_shape, num_stages, steps=3, nmb=2, seed=555, mk=None,
           **cfg_kw):
    ctor._seed_counter[0] = seed
    mesh = ht.create_mesh(mesh_shape)
    mk = mk or llama_config
    kw = dict(vocab_size=64, hidden_size=32, num_layers=4,
              num_heads=4, max_seq_len=16, sp=False)
    kw.update(cfg_kw)
    cfg = mk(**kw)
    with ht.graph("define_and_run", create_new=True, mesh=mesh) as g:
        ids = ht.parallel_placeholder("int32", (8, 16), pspec=P("dp", None),
                                      name="ids")
        lbl = ht.parallel_placeholder("int32", (8, 16), pspec=P("dp", None),
                                      name="lbl")
        m = GPTPipelineModel(cfg, num_stages=num_stages)
        loss = m(ids, lbl, num_micro_batches=nmb)
        op = optim.AdamOptimizer(lr=1e-2).minimize(loss)
        rng = np.random.RandomState(0)
        I = rng.randint(0, 64, (8, 16)).astype(np.int32)
        L = np.roll(I, -1, 1)
        return [float(np.asarray(g.run(loss, [loss, op],
                                       {ids: I, lbl: L})[0]))
                for _ in range(steps)]


class TestPipeline:
    def test_pp2_with_dp_tp_matches_pp1(self, devices8):
        base = _train({"pp": 1, "dp": 1, "tp": 1}, 1)
        pp2 = _train({"pp": 2, "dp": 2, "tp": 2}, 2)
        np.testing.assert_allclose(base, pp2, rtol=3e-3, atol=1e-4)

    def test_pp4_matches_pp1(self, devices8):
        base = _train({"pp": 1, "dp": 1, "tp": 1}, 1)
        pp4 = _train({"pp": 4, "dp": 2, "tp": 1}, 4)
        np.testing.assert_allclose(base, pp4, rtol=3e-3, atol=1e-4)

    def test_micro_batch_counts_agree(self, devices8):
        a = _train({"pp": 2, "dp": 1, "tp": 1}, 2, nmb=2)
        b = _train({"pp": 2, "dp": 1, "tp": 1}, 2, nmb=4)
        np.testing.assert_allclose(a, b, rtol=3e-3, atol=1e-4)

    def test_gpt2_blocks_pipeline(self, devices8):
        """GPT-2-style blocks (gelu/layernorm/learned positions, biases)
        pipeline too — the GPT-2 architecture is not barred from
        pp (reference places the same blocks across stages regardless of
        architecture, examples/gpt/train_hetu.py:256)."""
        from hetu_tpu.models.gpt import GPTConfig
        base = _train({"pp": 1, "dp": 1, "tp": 1}, 1, mk=GPTConfig)
        pp2 = _train({"pp": 2, "dp": 2, "tp": 2}, 2, mk=GPTConfig)
        np.testing.assert_allclose(base, pp2, rtol=3e-3, atol=1e-4)

    def test_pp2_with_sp_matches_pp1(self, devices8):
        """Megatron-SP composes with pp (reference per-layer sp flag,
        parallel_multi_ds.py:156-170): the residual stream stays
        seq-sharded over tp inside pipeline stages."""
        base = _train({"pp": 1, "dp": 1, "tp": 1}, 1, sp=True)
        pp2 = _train({"pp": 2, "dp": 2, "tp": 2}, 2, sp=True)
        np.testing.assert_allclose(base, pp2, rtol=3e-3, atol=1e-4)

    def test_pp2_gqa_matches_pp1(self, devices8):
        """GQA (num_kv_heads < num_heads) trains through the pipelined
        blocks — pp no longer bars the GQA model family."""
        base = _train({"pp": 1, "dp": 1, "tp": 1}, 1, num_kv_heads=2)
        pp2 = _train({"pp": 2, "dp": 2, "tp": 2}, 2, num_kv_heads=2)
        np.testing.assert_allclose(base, pp2, rtol=3e-3, atol=1e-4)

    def test_pp2_moe_matches_pp1(self, devices8):
        """All-MoE stacks (moe_every=1) pipeline with the balance aux
        loss threaded through warmup/drain-masked pipeline ticks."""
        moe_kw = dict(num_experts=4, moe_top_k=2, moe_every=1,
                      moe_capacity_factor=2.0)
        base = _train({"pp": 1, "dp": 1, "tp": 1}, 1, **moe_kw)
        pp2 = _train({"pp": 2, "dp": 2, "tp": 2}, 2, **moe_kw)
        assert base[-1] < base[0]          # actually learning
        np.testing.assert_allclose(base, pp2, rtol=3e-3, atol=1e-4)

    def test_pp2_moe_ep_matches_pp1(self, devices8):
        """MoE + expert parallelism inside pipeline stages (pp2 x ep2)."""
        moe_kw = dict(num_experts=4, moe_top_k=2, moe_every=1,
                      moe_capacity_factor=2.0)
        base = _train({"pp": 1, "dp": 1, "tp": 1}, 1, **moe_kw)
        pp2 = _train({"pp": 2, "dp": 2, "ep": 2}, 2, ep_axis="ep",
                     **moe_kw)
        np.testing.assert_allclose(base, pp2, rtol=3e-3, atol=1e-4)

    def test_mixed_dense_moe_raises(self, devices8):
        mesh = ht.create_mesh({"pp": 2, "dp": 2, "tp": 2})
        cfg = llama_config(vocab_size=64, hidden_size=32, num_layers=4,
                           num_heads=4, max_seq_len=16, sp=False,
                           num_experts=4, moe_every=2)
        with ht.graph("define_and_run", create_new=True, mesh=mesh):
            with pytest.raises(NotImplementedError, match="moe_every"):
                GPTPipelineModel(cfg, num_stages=2)

    def test_layers_not_divisible_raises(self, devices8):
        mesh = ht.create_mesh({"pp": 4, "dp": 2, "tp": 1})
        cfg = llama_config(vocab_size=64, hidden_size=32, num_layers=6,
                           num_heads=4, max_seq_len=16, sp=False)
        with ht.graph("define_and_run", create_new=True, mesh=mesh):
            with pytest.raises(AssertionError):
                GPTPipelineModel(cfg, num_stages=4)
