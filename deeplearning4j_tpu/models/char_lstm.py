"""Char-LSTM language model: training, sampling, and beam-search decoding.

Parity: reference `nn/layers/recurrent/LSTM.java:53` is a karpathy-style
char-LSTM whose decode path (`:236-341`) does beam search over characters.
TPU-native design: training reuses the LSTM layer's scan (zoo.char_lstm
config + MultiLayerNetwork), while decoding keeps the recurrent state as
explicit (h, c) arrays and steps the fused cell — temperature sampling via
`jax.random.categorical`, beam search as a host loop over jitted steps
(beams are a batch dimension, so every candidate advances in one call).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.zoo import char_lstm
from deeplearning4j_tpu.nn.conf import LayerType
from deeplearning4j_tpu.nn.layers import get_layer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


class CharLSTM:
    def __init__(self, hidden: int = 128, n_layers: int = 1,
                 seq_len: int = 32, lr: float = 0.1, iterations: int = 50,
                 seed: int = 0, batch_size: Optional[int] = None):
        self.hidden = hidden
        self.n_layers = n_layers
        self.seq_len = seq_len
        self.lr = lr
        self.iterations = iterations
        self.seed = seed
        # batch_size=None trains all windows as one batch; an int slices
        # the windows into mini-batches that all reuse ONE compiled solver
        # program via the network's step cache (the remainder slice pads
        # into the same bucket)
        self.batch_size = batch_size
        self.char_index: Dict[str, int] = {}
        self.chars: List[str] = []
        self.net: Optional[MultiLayerNetwork] = None

    # -- data
    def _encode(self, text: str) -> np.ndarray:
        return np.asarray([self.char_index[c] for c in text], np.int32)

    def fit(self, text: str) -> "CharLSTM":
        self.chars = sorted(set(text))
        self.char_index = {c: i for i, c in enumerate(self.chars)}
        v = len(self.chars)
        ids = self._encode(text)
        if len(ids) < self.seq_len + 1:
            raise ValueError(
                f"text too short for seq_len={self.seq_len}: need at least "
                f"{self.seq_len + 1} chars, got {len(ids)}")
        n_win = max(1, (len(ids) - 1) // self.seq_len)
        xs = ids[:n_win * self.seq_len].reshape(n_win, self.seq_len)
        ys = ids[1:n_win * self.seq_len + 1].reshape(-1)
        eye = np.eye(v, dtype=np.float32)
        conf = char_lstm(v, hidden=self.hidden, n_layers=self.n_layers,
                         lr=self.lr, iterations=self.iterations)
        self.net = MultiLayerNetwork(conf, seed=self.seed).init()
        x, y = eye[xs], eye[ys]
        bs = self.batch_size
        if bs and bs < n_win:
            from deeplearning4j_tpu.datasets.dataset import DataSet
            from deeplearning4j_tpu.datasets.iterator import PrefetchIterator

            # label rows are window-major blocks of T
            batches = DataSet(x, y, label_rows=self.seq_len).batch_by(bs)
            # async input pipeline: each window batch device_puts one
            # step ahead of the compiled train step it feeds
            self.net.fit(PrefetchIterator(batches))
        else:
            self.net.fit(x, y)
        return self

    # -- decoding plumbing
    def _lstm_params(self):
        """(layer_conf, params) pairs for the LSTM stack + output layer."""
        conf = self.net.conf
        stack = []
        for i in range(conf.n_layers):
            c = conf.conf(i)
            stack.append((c, self.net.params[i]))
        return stack

    def _step_fn(self):
        stack = self._lstm_params()
        lstm = get_layer(LayerType.LSTM)
        out_impl = get_layer(LayerType.OUTPUT)

        def step(x_onehot, hs, cs):
            """One char step.  x_onehot [B, V]; hs/cs lists per layer."""
            h_new, c_new = [], []
            inp = x_onehot
            for li, (c, p) in enumerate(stack[:-1]):
                h, c_ = lstm.step(p, c, inp, hs[li], cs[li])
                h_new.append(h)
                c_new.append(c_)
                inp = h
            out_conf, out_p = stack[-1]
            logits_in = out_impl.forward(out_p, out_conf, inp)
            return jnp.log(jnp.clip(logits_in, 1e-9, 1.0)), h_new, c_new

        return jax.jit(step)

    def _init_state(self, batch: int):
        n_lstm = len(self._lstm_params()) - 1
        hs = [jnp.zeros((batch, self.hidden)) for _ in range(n_lstm)]
        cs = [jnp.zeros((batch, self.hidden)) for _ in range(n_lstm)]
        return hs, cs

    def _feed(self, step, text: str, hs, cs):
        v = len(self.chars)
        eye = jnp.eye(v)
        logp = None
        for cid in self._encode(text):
            logp, hs, cs = step(eye[cid][None].repeat(hs[0].shape[0], 0),
                                hs, cs)
        return logp, hs, cs

    # -- public decode APIs
    def sample(self, seed_text: str, n: int = 50,
               temperature: float = 1.0, rng_seed: int = 0) -> str:
        """Temperature sampling, one char at a time."""
        assert self.net is not None, "fit() first"
        step = self._step_fn()
        hs, cs = self._init_state(1)
        logp, hs, cs = self._feed(step, seed_text, hs, cs)
        key = jax.random.PRNGKey(rng_seed)
        v = len(self.chars)
        eye = jnp.eye(v)
        out = []
        for _ in range(n):
            key, sub = jax.random.split(key)
            if temperature <= 0:
                cid = int(jnp.argmax(logp[0]))
            else:
                cid = int(jax.random.categorical(sub, logp[0] / temperature))
            out.append(self.chars[cid])
            logp, hs, cs = step(eye[cid][None], hs, cs)
        return "".join(out)

    def generate(self, seed_text: str, n: int = 50,
                 temperature: float = 0.0, rng_seed: int = 0,
                 max_seq: Optional[int] = None) -> str:
        """`sample()` through the compiled KV-cache decode path: one
        prefill program consumes the seed text, then one decode-step
        program (compiled once, state donated) produces each character.
        Token-for-token identical to `sample()` for the same arguments —
        both split the same PRNG key stream and the recurrent math is
        the same f32 ops — which is exactly what
        tests/test_generate.py pins."""
        assert self.net is not None, "fit() first"
        ids = self._encode(seed_text)
        if len(ids) == 0:
            raise ValueError("seed_text must be non-empty")
        if max_seq is None:
            max_seq = max(8, 1 << (len(ids) + n - 1).bit_length())
        bucket = max(4, 1 << (len(ids) - 1).bit_length())
        ic = self.net.infer_cache
        state = ic.init_decode_state(self.net.conf, 1, max_seq)
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :len(ids)] = ids
        length = jnp.asarray([len(ids)], jnp.int32)
        keys = jnp.asarray(np.asarray(jax.random.PRNGKey(rng_seed))[None])
        temps = jnp.full((1,), float(temperature), jnp.float32)
        tok, keys, state = ic.prefill(
            self.net.conf, self.net.params, state, jnp.asarray(prompt),
            length, keys, temps)
        out = [self.chars[int(tok[0])]]
        pos = jnp.asarray([len(ids)], jnp.int32)
        for _ in range(n - 1):
            tok, keys, state = ic.decode(
                self.net.conf, self.net.params, state, tok, pos, keys,
                temps)
            out.append(self.chars[int(tok[0])])
            pos = pos + 1
        return "".join(out)

    def beam_search(self, seed_text: str, n: int = 20,
                    beam_width: int = 4) -> Tuple[str, float]:
        """Beam-search decode (LSTM.java:236-341 parity): returns the best
        continuation and its total log-probability.  Beams ride the batch
        dimension, so each extension is a single jitted step over all
        candidates."""
        assert self.net is not None, "fit() first"
        step = self._step_fn()
        v = len(self.chars)
        # more beams than characters would leave hs/cs rows without a
        # matching candidate on the next step()
        beam_width = min(beam_width, v)
        eye = jnp.eye(v)
        hs, cs = self._init_state(1)
        logp, hs, cs = self._feed(step, seed_text, hs, cs)

        # beams: (chars list, total logp, state index into hs/cs batch)
        top = jnp.argsort(-logp[0])[:beam_width]
        beams = [([int(t)], float(logp[0][int(t)])) for t in top]
        hs = [h.repeat(beam_width, 0) for h in hs]
        cs = [c.repeat(beam_width, 0) for c in cs]

        for _ in range(n - 1):
            x = eye[jnp.asarray([b[0][-1] for b in beams])]
            logp, hs_n, cs_n = step(x, hs, cs)
            # expand: beam_width x V candidates, keep the best beam_width
            cand = []
            for bi, (seq, score) in enumerate(beams):
                for cid in np.argsort(-np.asarray(logp[bi]))[:beam_width]:
                    cand.append((seq + [int(cid)],
                                 score + float(logp[bi][int(cid)]), bi))
            cand.sort(key=lambda t: -t[1])
            cand = cand[:beam_width]
            beams = [(seq, score) for seq, score, _ in cand]
            keep = jnp.asarray([bi for _, _, bi in cand])
            hs = [h[keep] for h in hs_n]
            cs = [c[keep] for c in cs_n]

        best_seq, best_score = beams[0]
        return "".join(self.chars[i] for i in best_seq), best_score
