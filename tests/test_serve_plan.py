"""ShardPlan (ISSUE 17): mesh-spec parsing, per-leaf PartitionSpec
rules, and — the contract the whole refactor hangs on — 1-D and
single-chip plan fingerprints **byte-identical** to the pre-plan cache
keys, so disk artifacts written before the plan existed stay pure hits
(`fresh_compiles == 0`, zero evictions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.models.zoo import char_transformer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.step_cache import arg_signature
from deeplearning4j_tpu.parallel.plan import (
    ShardPlan, parse_mesh_spec, plan_mesh)

VOCAB = 32


def _net():
    conf = char_transformer(VOCAB, d_model=16, n_blocks=2, n_heads=2,
                            max_seq_len=32)
    return MultiLayerNetwork(conf, seed=0).init()


class TestParseMeshSpec:
    def test_empty_and_all_mean_default(self):
        assert parse_mesh_spec("") == {}
        assert parse_mesh_spec("all") == {}
        assert parse_mesh_spec(None) == {}

    def test_explicit_axes(self):
        assert parse_mesh_spec("batch=2,model=4") == {"batch": 2,
                                                      "model": 4}
        assert parse_mesh_spec("model=4") == {"model": 4}

    def test_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_mesh_spec("bogus")
        with pytest.raises(ValueError):
            parse_mesh_spec("batch=x")
        with pytest.raises(ValueError):
            parse_mesh_spec("batch=0")
        with pytest.raises(ValueError):
            parse_mesh_spec("batch=2,batch=4")


class TestPlanMesh:
    def test_default_is_one_d_batch(self):
        mesh = plan_mesh({})
        assert mesh.axis_names == ("batch",)
        assert mesh.devices.size == jax.device_count()

    def test_two_d_shape(self):
        mesh = plan_mesh({"batch": 2, "model": 4})
        assert mesh.axis_names == ("batch", "model")
        assert tuple(mesh.devices.shape) == (2, 4)

    def test_model_only_defaults_batch_to_one(self):
        mesh = plan_mesh({"model": 4})
        assert mesh.axis_names == ("batch", "model")
        assert tuple(mesh.devices.shape) == (1, 4)

    def test_minus_one_fills(self):
        mesh = plan_mesh({"batch": 2, "model": -1})
        assert tuple(mesh.devices.shape) == (2, jax.device_count() // 2)


class TestParamSpecs:
    def test_transformer_split_rules(self):
        net = _net()
        plan = ShardPlan(mesh=plan_mesh({"batch": 2, "model": 4}))
        specs = plan.param_pspecs(net.params)
        flat, _ = jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))
        by_name = {}
        for path, spec in flat:
            name = str(getattr(path[-1], "key", path[-1]))
            by_name.setdefault(name, set()).add(spec)
        # QKV and first-FFN kernels column-split over the model axis
        assert by_name["Wqkv"] == {P(None, "model")}
        assert by_name["W1"] == {P(None, "model")}
        # output / second-FFN projections row-split (all-reduce after)
        assert by_name["Wo"] == {P("model", None)}
        assert by_name["W2"] == {P("model", None)}
        # biases and layer-norm scales stay replicated
        for name in ("bqkv", "bo", "b1", "b2", "ln_g", "ln_b"):
            assert by_name[name] == {P()}

    def test_indivisible_leaves_stay_replicated(self):
        plan = ShardPlan(mesh=plan_mesh({"batch": 2, "model": 4}))
        # 5 divides by neither axis ordering: replicated, never an error
        assert plan._param_spec("W", (5, 7)) == P()

    def test_zero1_composes_batch_axis(self):
        net = _net()
        plan = ShardPlan(mesh=plan_mesh({"batch": 2, "model": 4}))
        specs = plan.zero1_pspecs(net.params)
        flat, _ = jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))
        by_name = {str(getattr(p[-1], "key", p[-1])): s for p, s in flat}
        # a column-split kernel gains the batch axis on its leading dim
        assert by_name["Wqkv"] == P("batch", "model")


class TestKeyByteIdentity:
    """The tentpole invariant: for single-chip and 1-D plans the keys
    the plan emits are byte-for-byte the pre-PR tuples, hand-built here
    from the old schema."""

    def test_single_chip_output_key(self):
        net = _net()
        x = np.ones((8, 16), np.int32)
        net.infer_cache.output(net.conf, net.params, x,
                               compile_only=True)
        ic = net.infer_cache
        xp = jnp.zeros((ic._serve_bucket(8), 16), jnp.int32)
        expected = ("output", ic._fingerprint(net.conf),
                    arg_signature(xp), "single")
        assert expected in ic._programs

    def test_one_d_mesh_output_key(self):
        net = _net()
        mesh = net.set_serve_mesh()  # 1-D batch mesh, pre-plan pattern
        x = np.ones((8, 16), np.int32)
        net.infer_cache.output(net.conf, net.params, x,
                               compile_only=True)
        ic = net.infer_cache
        xp = jnp.zeros((ic._serve_bucket(8), 16), jnp.int32)
        expected = ("output", ic._fingerprint(net.conf),
                    arg_signature(xp),
                    ("mesh", tuple(mesh.axis_names),
                     tuple(int(d) for d in mesh.devices.shape)))
        assert expected in ic._programs

    def test_decode_keys_stay_single_under_one_d_mesh(self):
        # generation is single-chip under a 1-D (or no) mesh: the key
        # keeps the pre-plan "single" tag so warmed decode programs
        # survive flipping `--mesh` on
        net = _net()
        net.set_serve_mesh()
        net.warmup_generate(slots=2, max_seq=16, prompt_buckets=(4,))
        decode_keys = [k for k in net.infer_cache._programs
                       if k[0] in ("decode", "prefill-slot")]
        assert {k[0] for k in decode_keys} == {"decode", "prefill-slot"}
        assert all(k[3] == "single" for k in decode_keys)

    def test_decode_keys_carry_plan_tag_with_model_axis(self):
        net = _net()
        net.set_serve_mesh(spec="batch=2,model=2")
        net.warmup_generate(slots=2, max_seq=16, prompt_buckets=(4,))
        decode_keys = [k for k in net.infer_cache._programs
                       if k[0] == "decode"]
        assert decode_keys
        assert all(k[3] == ("mesh", ("batch", "model"), (2, 2))
                   for k in decode_keys)

    def test_policy_suffix_unchanged(self):
        plan = ShardPlan()
        assert plan.policy_suffix() == ()
        assert ShardPlan(policy="bf16").policy_suffix() == \
            (("policy", "bf16"),)


class TestDiskBackCompat:
    def test_pre_plan_disk_cache_warms_with_zero_fresh_compiles(
            self, tmp_path):
        """A disk store written by one process (byte-identical keys to
        the pre-plan schema, per TestKeyByteIdentity) warms a second
        process with fresh_compiles == 0 and zero evictions."""
        cache_dir = str(tmp_path / "cc")
        warm = _net()
        warm.set_compile_cache(cache_dir)
        warm.warmup([8], entries=("output",))
        assert warm.infer_cache.stats.misses == 1  # the one real compile

        cold = _net()
        store = cold.set_compile_cache(cache_dir)
        cold.warmup([8], entries=("output",))
        assert cold.infer_cache.stats.misses == 0  # fresh_compiles == 0
        assert cold.infer_cache.stats.disk_hits == 1
        assert store.evictions == 0

    def test_mesh_and_single_programs_coexist_on_disk(self, tmp_path):
        cache_dir = str(tmp_path / "cc")
        net = _net()
        net.set_compile_cache(cache_dir)
        net.warmup([8], entries=("output",))
        net.set_serve_mesh(spec="batch=2,model=4")
        net.warmup([8], entries=("output",))
        assert net.infer_cache.stats.misses == 2  # one per sharding

        net2 = _net()
        net2.set_compile_cache(cache_dir)
        net2.set_serve_mesh(spec="batch=2,model=4")
        net2.warmup([8], entries=("output",))
        net2.infer_cache.set_mesh(None)  # back to 1-chip: still a hit
        net2.warmup([8], entries=("output",))
        assert net2.infer_cache.stats.misses == 0
        assert net2.infer_cache.stats.disk_hits == 2
