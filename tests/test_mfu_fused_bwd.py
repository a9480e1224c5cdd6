"""Parity tests for the fused flash backward of the MFU campaign, at the level
of the op (`test_mfu_paths.py` has the other hot paths, `test_mfu_end_to_end.py`
the compiled train step)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nd.attention import full_attention
from deeplearning4j_tpu.nd.pallas_kernels import flash_attention
from mfu_helpers import _assert_tree_bitwise


# -- fused flash backward ----------------------------------------------------
#
# The fused path (attention_fused_bwd) swaps the jax-level recompute VJP for
# three Pallas kernels fed by saved logsumexp residuals.  Claims enforced
# here: grads allclose (tight f32) to full_attention autodiff across
# causal/non-causal x block_skip x shapes, in interpret AND jit-compiled
# modes; the forward output is bitwise-unchanged by residual emission; every
# fallback (flag off, ragged S, auto-detected interpret mode) stays bitwise
# identical to the pre-fused recompute path; the flag never touches
# serving-cache keys; and no [S,S] intermediate appears in the lowering.

def _qkvg(seed, B, S, H, D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (B, S, H, D), jnp.float32) for k in ks]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_skip", [False, True])
@pytest.mark.parametrize(
    "shape,fwd_blocks,bwd_blocks",
    [((2, 64, 2, 8), (32, 16), (16, 32)),    # asymmetric fwd vs bwd tiles
     ((1, 128, 2, 16), (32, 32), (32, 32))])
def test_fused_bwd_grad_parity_vs_full_attention(causal, block_skip, shape,
                                                 fwd_blocks, bwd_blocks):
    B, S, H, D = shape
    q, k, v, g = _qkvg(20, B, S, H, D)
    bq, bk = fwd_blocks
    bqb, bkb = bwd_blocks

    def loss_fused(q, k, v):
        o = flash_attention(q, k, v, causal, bq, bk, interpret=True,
                            block_skip=block_skip, fused_bwd=True,
                            block_q_bwd=bqb, block_k_bwd=bkb)
        return jnp.sum(o * g)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) * g)

    ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for mode, fn in [("interpret", jax.grad(loss_fused, argnums=(0, 1, 2))),
                     ("compiled",
                      jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2))))]:
        got = fn(q, k, v)
        for name, a, b in zip("qkv", got, ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
                err_msg=f"d{name} {mode} causal={causal} "
                        f"skip={block_skip} S={S}")


def test_fused_bwd_forward_output_bitwise():
    """Emitting the logsumexp residual must not perturb o: the fused
    forward (under vjp, residuals saved) is bitwise the plain flash
    forward."""
    q, k, v, _ = _qkvg(21, 2, 64, 2, 8)
    plain = flash_attention(q, k, v, True, 32, 16, interpret=True,
                            block_skip=True)
    fused_primal = flash_attention(q, k, v, True, 32, 16, interpret=True,
                                   block_skip=True, fused_bwd=True)
    out_vjp, _ = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, True, 32, 16,
                                        interpret=True, block_skip=True,
                                        fused_bwd=True), q, k, v)
    _assert_tree_bitwise(plain, fused_primal, "primal")
    _assert_tree_bitwise(plain, out_vjp, "vjp forward")


@pytest.mark.parametrize("case", ["flag_off", "ragged_s", "auto_interpret"])
def test_fused_bwd_fallbacks_bitwise_vs_recompute(case):
    """Every fused-path degrade keeps the pre-PR backward bit for bit:
    flag off, ragged S (no Pallas block divides it), and auto-detected
    interpret mode (interpret=None off-TPU — the fused kernels are gated
    to real TPU lowerings or an explicit interpret pin)."""
    from deeplearning4j_tpu.nd.attention import blockwise_attention
    from deeplearning4j_tpu.nd.platform import is_tpu

    if case == "auto_interpret" and is_tpu():
        pytest.skip("auto-detect resolves to the real kernels on TPU")
    S = 70 if case == "ragged_s" else 64
    q, k, v, g = _qkvg(22, 2, S, 2, 8)
    kwargs = {"fused_bwd": case != "flag_off"}
    if case != "auto_interpret":
        kwargs["interpret"] = True

    def f(q, k, v):
        return flash_attention(q, k, v, True, 32, 16, **kwargs)

    _, vjp = jax.vjp(f, q, k, v)
    _, vjp_ref = jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v, block_size=16,
                                            causal=True), q, k, v)
    _assert_tree_bitwise(vjp(g), vjp_ref(g), case)
    # and under jit, as the train step runs it
    jg = jax.jit(jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * g),
                          argnums=(0, 1, 2)))(q, k, v)
    rg = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(blockwise_attention(
            q, k, v, block_size=16, causal=True) * g),
        argnums=(0, 1, 2)))(q, k, v)
    _assert_tree_bitwise(jg, rg, f"{case} jit")


# the recursive jaxpr walk that used to live here is library code now
# (analysis/program_audit.py) so the `analyze` gate and this test assert
# the exact same structural contract
from deeplearning4j_tpu.analysis.program_audit import (  # noqa: E402
    assert_no_materialized_scores as _assert_no_ss_lib)


def _assert_no_ss(fn, args, S, where):
    _assert_no_ss_lib(fn, args, seq_threshold=S, where=where)


@pytest.mark.parametrize("fused", [True, False])
def test_no_ss_intermediate_at_long_seq(fused):
    """The flash memory contract, asserted structurally: at S=1024 neither
    the forward nor the backward jaxpr (fused kernels or the blockwise
    recompute fallback) contains an intermediate with two S-sized dims.
    Trace-only — nothing executes."""
    S, D = 1024, 8
    q = jax.ShapeDtypeStruct((1, S, 1, D), jnp.float32)

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, 256, 256, interpret=True,
                               block_skip=True, fused_bwd=fused,
                               block_q_bwd=256, block_k_bwd=256)

    _assert_no_ss(fwd, (q, q, q), S, f"forward fused={fused}")
    _assert_no_ss(
        jax.grad(lambda a, b, c: jnp.sum(fwd(a, b, c)), argnums=(0, 1, 2)),
        (q, q, q), S, f"backward fused={fused}")


def test_fused_bwd_flag_never_changes_infer_cache_key():
    """Serving programs are gradient-free: flipping attention_fused_bwd
    must not re-key (or invalidate on disk) any inference program — and
    the normalized fingerprint equals the flag-off fingerprint, so pre-PR
    artifacts stay live.  The training step cache, by contrast, must
    re-key."""
    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.optimize.infer_cache import InferCache
    from deeplearning4j_tpu.optimize.step_cache import (CompiledProgramCache,
                                                        conf_fingerprint)

    conf_off = char_transformer(17, d_model=32, n_blocks=1, n_heads=2,
                                max_seq_len=16)
    conf_on = char_transformer(17, d_model=32, n_blocks=1, n_heads=2,
                               max_seq_len=16, attention_fused_bwd=True)
    ic = InferCache()
    assert ic._fingerprint(conf_on) == ic._fingerprint(conf_off)
    assert ic._fingerprint(conf_off) == conf_fingerprint(conf_off)
    base = CompiledProgramCache()
    assert base._fingerprint(conf_on) != base._fingerprint(conf_off)
