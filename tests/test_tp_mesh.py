"""2-D tensor parallelism (ISSUE 17 acceptance): a transformer serves,
decodes, and trains on a `('batch', 'model')` mesh with params,
activations, and KV state sharded over the model axis — numerically
matching the single-chip programs, decoding token-identically, holding
fewer bytes per chip than the replicated layout, and round-tripping
per-shard checkpoints across topologies without materializing a global
leaf."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.zoo import char_transformer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import checkpoint
from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.plan import ShardPlan, plan_mesh
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

VOCAB = 32

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8 forced host devices")


def _net():
    conf = char_transformer(VOCAB, d_model=16, n_blocks=2, n_heads=2,
                            max_seq_len=32)
    return MultiLayerNetwork(conf, seed=0).init()


def _greedy_tokens(net, prompt, n_new=8):
    net.warmup_generate(slots=2, max_seq=32, prompt_buckets=(8,))
    cb = ContinuousBatcher(net, n_slots=2, max_seq=32,
                           prompt_buckets=(8,))
    try:
        stream = cb.submit(prompt, max_new_tokens=n_new)
        return list(stream.tokens(timeout=120.0))
    finally:
        cb.stop()


class TestTwoDServe:
    def test_output_matches_single_chip(self):
        x = np.random.RandomState(0).randint(
            1, VOCAB, size=(8, 16)).astype(np.int32)
        ref = np.asarray(_net().output(x))
        net = _net()
        net.set_serve_mesh(spec="batch=2,model=4")
        got = np.asarray(net.output(x))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_per_chip_bytes_shrink(self):
        net = _net()
        net.set_serve_mesh(spec="batch=2,model=4")
        x = np.ones((8, 16), np.int32)
        net.infer_cache.output(net.conf, net.params, x,
                               compile_only=True)
        rows = [r for r in net.infer_cache.program_memory()
                if r["entry"] == "output"]
        assert rows
        r = rows[0]
        assert r["per_device_argument_bytes"] < \
            r["replicated_argument_bytes"]


class TestTwoDDecode:
    def test_greedy_trajectory_identical_to_single_chip(self):
        prompt = [1, 7, 3]
        ref = _greedy_tokens(_net(), prompt)
        assert ref  # really decoded something
        net = _net()
        net.set_serve_mesh(spec="batch=1,model=4")
        assert _greedy_tokens(net, prompt) == ref

    def test_paged_greedy_trajectory_identical(self):
        prompt = [2, 5, 9]
        net_ref = _net()
        net_ref.warmup_generate(slots=2, max_seq=32, prompt_buckets=(8,),
                                page_size=8, n_pages=8)
        cb = ContinuousBatcher(net_ref, n_slots=2, max_seq=32,
                               prompt_buckets=(8,), page_size=8)
        try:
            ref = list(cb.submit(prompt, max_new_tokens=8)
                       .tokens(timeout=120.0))
        finally:
            cb.stop()
        net = _net()
        net.set_serve_mesh(spec="batch=1,model=4")
        net.warmup_generate(slots=2, max_seq=32, prompt_buckets=(8,),
                            page_size=8, n_pages=8)
        cb = ContinuousBatcher(net, n_slots=2, max_seq=32,
                               prompt_buckets=(8,), page_size=8)
        try:
            got = list(cb.submit(prompt, max_new_tokens=8)
                       .tokens(timeout=120.0))
        finally:
            cb.stop()
        assert got == ref

    @pytest.mark.parametrize("prefix_cache", [False, True])
    def test_admission_programs_under_the_model_axis(self, prefix_cache):
        """`prefill_slot`, and with the prefix cache `prefill_logp_slot`
        (the miss) and `write_row` (the hit), take the sharded table in
        and hand it back on the plan's specs: warm, no fresh compile, and
        the single chip's tokens, sampled streams included."""
        asks = [([1, 7, 3], 0.0, 0), ([4, 4, 9, 2], 0.8, 1),
                ([1, 7, 3], 0.7, 2)]

        def tokens(net):
            net.warmup_generate(slots=2, max_seq=32, prompt_buckets=(8,),
                                prefix_cache=prefix_cache)
            warm = net.infer_cache.stats.misses
            cb = ContinuousBatcher(net, n_slots=2, max_seq=32,
                                   prompt_buckets=(8,),
                                   prefix_cache=prefix_cache)
            try:
                got = [cb.generate(p, max_new_tokens=6, temperature=t,
                                   rng_seed=s, timeout=120.0)
                       for p, t, s in asks]
            finally:
                cb.stop()
            assert net.infer_cache.stats.misses == warm
            return got

        ref = tokens(_net())
        net = _net()
        net.set_serve_mesh(spec="batch=1,model=4")
        assert tokens(net) == ref
        entries = {k[0] for k in net.infer_cache._programs}
        assert entries == ({"decode", "prefill-logp-slot", "write-row"}
                           if prefix_cache else {"decode", "prefill-slot"})
        assert all(k[3] == ("mesh", ("batch", "model"), (1, 4))
                   for k in net.infer_cache._programs)

    def test_decode_state_sharded_over_model_axis(self):
        net = _net()
        net.set_serve_mesh(spec="batch=1,model=4")
        rows = 0
        net.warmup_generate(slots=2, max_seq=32, prompt_buckets=(8,))
        mem = [r for r in net.infer_cache.program_memory()
               if r["entry"] == "decode"]
        assert mem
        for r in mem:
            rows += 1
            assert r["per_device_argument_bytes"] < \
                r["replicated_argument_bytes"]
        assert rows


class TestPlanTrainer:
    def _batches(self, n_batches=2, bs=8, seed=0):
        rng = np.random.RandomState(seed)
        out = []
        for _ in range(n_batches):
            x = rng.randint(1, VOCAB, size=(bs, 16)).astype(np.int32)
            y = np.eye(VOCAB, dtype=np.float32)[
                rng.randint(0, VOCAB, bs * 16)]
            out.append((x, y))
        return out

    def test_two_d_plan_trains_with_zero1(self):
        plan = ShardPlan(mesh=plan_mesh({"batch": 2, "model": 4}))
        net = _net()
        t = DataParallelTrainer(net, zero1=True, plan=plan)
        t.fit(self._batches(), epochs=1)
        assert int(t.state.step) == 2
        # updater moments compose batch over the model split
        flat, _ = jax.tree_util.tree_flatten_with_path(t.state.updater)
        composed = [
            leaf.sharding.spec for path, leaf in flat
            if hasattr(leaf, "sharding")
            and getattr(leaf.sharding, "spec", None) is not None
            and tuple(leaf.sharding.spec) == ("batch", "model")]
        assert composed, "no updater leaf composed batch over model"
        # params stay tensor-sharded on the mesh after fit
        p_specs = {tuple(leaf.sharding.spec)
                   for leaf in jax.tree_util.tree_leaves(net.params)
                   if hasattr(leaf, "sharding")
                   and getattr(leaf.sharding, "spec", None) is not None}
        assert any("model" in s for s in p_specs)

    def test_remainder_batch_pads_and_masks(self):
        plan = ShardPlan(mesh=plan_mesh({"batch": 2, "model": 4}))
        batches = self._batches()
        x, y = self._batches(1, seed=9)[0]
        tail = (x[:6], y[:6 * 16])  # 6 prompt rows -> 96 label rows

        t_ref = DataParallelTrainer(_net(), zero1=True, plan=plan)
        t_ref.fit(batches, epochs=1)
        t = DataParallelTrainer(_net(), zero1=True, plan=plan)
        t.fit(batches, epochs=1)
        ref = jax.tree_util.tree_map(np.asarray, t_ref.state.params)
        got = jax.tree_util.tree_map(np.asarray, t.state.params)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(got)):
            assert np.array_equal(a, b)  # divisible prefix is bitwise
        t.fit([tail], epochs=1)  # 6 rows on a 2-row mesh: pad + mask
        assert int(t.state.step) == 3


class TestShardedCheckpoint:
    def test_round_trip_n_to_m_without_global_leaf(self, tmp_path):
        net = _net()
        plan_a = ShardPlan(mesh=plan_mesh({"batch": 2, "model": 4}))
        sharded = jax.tree_util.tree_map(
            jax.device_put, net.params, plan_a.param_shardings(net.params))
        d = str(tmp_path / "ckpt")
        checkpoint.save_sharded(d, sharded, conf=net.conf, step=7,
                                metadata={"note": "tp"})

        plan_b = ShardPlan(mesh=plan_mesh({"batch": 4, "model": 2}))
        like = net.params
        stats = {}
        params, upd, meta = checkpoint.load_sharded(
            d, like_params=like,
            params_shardings=plan_b.param_shardings(like), stats=stats)
        assert upd is None
        assert meta["step"] == 7
        for a, b in zip(jax.tree_util.tree_leaves(net.params),
                        jax.tree_util.tree_leaves(params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # the working-set bound: no assembled region reached the size
        # of the largest global leaf
        biggest = max(
            int(np.prod(np.asarray(l.shape), dtype=np.int64)) * 4
            for l in jax.tree_util.tree_leaves(net.params))
        assert stats["max_region_bytes"] < biggest

    def test_plain_load_reads_sharded_layout(self, tmp_path):
        net = _net()
        plan = ShardPlan(mesh=plan_mesh({"batch": 2, "model": 4}))
        sharded = jax.tree_util.tree_map(
            jax.device_put, net.params, plan.param_shardings(net.params))
        d = str(tmp_path / "ckpt")
        checkpoint.save_sharded(d, sharded, conf=net.conf, step=3)
        params, _, meta = checkpoint.load(d, like_params=net.params)
        assert meta["step"] == 3
        for a, b in zip(jax.tree_util.tree_leaves(net.params),
                        jax.tree_util.tree_leaves(params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
