"""What the `test_generate*.py` files share: the two tiny generative nets, the
compiled prefill-and-decode loop a slot runs, a draft net for speculation,
and the fault plan put back round every case.  No test file: nothing here is
collected."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import char_lstm, char_transformer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork, network_output
from deeplearning4j_tpu.reliability import faults

VOCAB = 13


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def lstm_net():
    return MultiLayerNetwork(char_lstm(VOCAB, hidden=16, n_layers=2),
                             seed=0).init()


@pytest.fixture(scope="module")
def transformer_net():
    return MultiLayerNetwork(
        char_transformer(VOCAB, d_model=16, n_blocks=2, n_heads=2,
                         max_seq_len=32), seed=0).init()


def _compiled_tokens(net, prompt, n_new, temperature=0.0, rng_seed=0,
                     max_seq=16, bucket=8):
    """Prompt -> n_new tokens through the compiled prefill + decode
    programs (the exact sequence ContinuousBatcher runs per slot)."""
    ic = net.infer_cache
    state = ic.init_decode_state(net.conf, 1, max_seq)
    pb = np.zeros((1, bucket), np.int32)
    pb[0, :len(prompt)] = prompt
    length = jnp.asarray([len(prompt)], jnp.int32)
    keys = jnp.asarray(np.asarray(jax.random.PRNGKey(rng_seed))[None])
    temps = jnp.full((1,), float(temperature), jnp.float32)
    tok, keys, state = ic.prefill(net.conf, net.params, state,
                                  jnp.asarray(pb), length, keys, temps)
    got = [int(tok[0])]
    pos = jnp.asarray([len(prompt)], jnp.int32)
    for _ in range(n_new - 1):
        tok, keys, state = ic.decode(net.conf, net.params, state, tok,
                                     pos, keys, temps)
        got.append(int(tok[0]))
        pos = pos + 1
    return got


def _eager_transformer_tokens(net, prompt, n_new):
    """Greedy reference by full eager re-forward over the sequence so far —
    no cache at all, so agreement means the cached path IS the model.  The
    ids are padded to one width (a causal model's outputs do not see what
    follows), so that the eager ops compile for one shape and not for
    `n_new`."""
    seq, toks = list(prompt), []
    width = len(prompt) + n_new
    for _ in range(n_new):
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(seq)] = seq
        probs = network_output(net.conf, net.params, jnp.asarray(ids))
        probs = probs.reshape(width, -1)
        toks.append(int(jnp.argmax(
            jnp.log(jnp.clip(probs[len(seq) - 1], 1e-9, 1.0)))))
        seq.append(toks[-1])
    return toks


#: a count on the `decode` spans and the `stats()` total that sums it
TOTALS = {"picks_here": "expert_picks_total", "experts_hit": "experts_hit_total",
          "kv_cells_live": "kv_cells_live_total",
          "kv_cells_spanned": "kv_cells_spanned_total",
          "dsa_cells_live": "dsa_cells_live_total",
          "dsa_cells_selected": "dsa_cells_selected_total",
          "ring_cells_live": "ring_cells_live_total",
          "ring_cells_spanned": "ring_cells_spanned_total"}


def spans_sum_to_totals(spans, stats):
    """Assert that every count the batcher totals in `stats` is the sum of
    that count over the `decode` spans; returns the counts' names."""
    have = [name for name, total in TOTALS.items() if total in stats]
    decodes = [s for s in spans if s.name == "decode"]
    for name in have:
        assert stats[TOTALS[name]] == sum(s.attrs.get(name, 0) for s in decodes), name
    return have


def _drain(streams, timeout=60.0):
    return [list(s.tokens(timeout=timeout)) for s in streams]


def _draft_net(agrees_with=None):
    """A draft model: `agrees_with` clones the target (full acceptance)
    while None builds a smaller, differently-seeded one (frequent
    rejection — the adversarial case for the rollback math)."""
    if agrees_with is not None:
        return MultiLayerNetwork(agrees_with.conf, seed=0).init()
    return MultiLayerNetwork(char_lstm(VOCAB, hidden=8, n_layers=1),
                             seed=1).init()
