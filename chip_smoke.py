#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that train -> serve -> generate still
starts on the TPU, through the entry points a user would call.

    python3 chip_smoke.py             one chip (what the driver runs)
    python3 chip_smoke.py --chips 4   only the paths that exist across four
                                      chips, and what each is compared with

The model is `models/zoo.char_transformer` at the width the repo calls its
flagship: vocab 256, d_model 2048, 8 blocks, 16 heads (head dim 128),
sequence 512, bf16 compute over f32 master weights.  Weights and the
synthetic corpus come from `--seed`.

A chip belongs to one process at a time, so this process stays off JAX: it
starts one child at a time, each child holds the chip for its phase and has
exited before the next starts, and the device facts of the last line come
from the first child.  Every phase prints one JSON line; any failure ends
the run with a non-zero exit.  Without a TPU nothing is printed on stdout
and the exit code is 2, whatever the options.

The phase functions take a `Size`, so `tests/test_chip_smoke.py` rehearses
them on the CPU at a tiny size; only `main()` can report success, and only
from a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import io
import json
import math
import os
import select
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import urllib.request
from typing import List, Optional

import numpy as np

# outside a checkout of the repo this import fails and so does the script
from deeplearning4j_tpu.nd import platform

HERE = os.path.dirname(os.path.abspath(__file__))
NO_CHIP = 2  # exit code when JAX finds no TPU


@dataclasses.dataclass(frozen=True)
class Size:
    """What the phases run at.  The defaults are the full width; tests
    pass a tiny one."""

    vocab: int = 256
    d_model: int = 2048
    blocks: int = 8
    heads: int = 16
    seq: int = 512
    # rows per train step.  Chosen so the step fits 16 GB: compiled for a
    # described v5e (compile only) 8 rows need 1.51 GiB of arguments and
    # 9.67 GiB of temporaries; 16 rows are refused (17.85G of 15.75G hbm)
    batch: int = 8
    steps: int = 4
    serve_rows: int = 4
    prompt_len: int = 12
    new_tokens: int = 16
    attn_batch: int = 4
    lstm_batch: int = 256
    lstm_hidden: int = 256
    seed: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    def model(self) -> str:
        return (f"char_transformer vocab{self.vocab} d{self.d_model} "
                f"L{self.blocks} H{self.heads} S{self.seq} bf16/f32")


class PhaseFailed(Exception):
    """A phase ran and what came out is wrong."""


class NoChip(Exception):
    """JAX's default backend is not a TPU."""


def check(ok, message: str) -> None:
    if not ok:
        raise PhaseFailed(message)


def emit(phase: str, **fields) -> dict:
    line = {"phase": phase, **fields}
    print(json.dumps(line), flush=True)
    return line


# --------------------------------------------------------------- device

def require_tpu() -> dict:
    """The device as JAX reports it; raises unless it is a TPU."""
    if platform.default_backend() != "tpu":
        raise NoChip("JAX's default backend is "
                     f"{platform.default_backend()!r}, not 'tpu'")
    found = platform.describe()
    return {"platform": found["platform"], "kind": found["device_kind"],
            "count": found["device_count"]}


def memory_per_device(at_least: int = 0,
                      stat: str = "peak_bytes_in_use") -> List[int]:
    """`stat` of each local device's memory; on a TPU every one must be
    above `at_least` (the CPU backend reports none)."""
    import jax

    held = [int((d.memory_stats() or {}).get(stat, 0))
            for d in jax.local_devices()]
    check(jax.default_backend() != "tpu" or all(b > at_least for b in held),
          f"a device holds no work: {stat} {held}")
    return held


# -------------------------------------------------------------- kernels

def _run_compiled(fn, args, want_kernel: bool):
    """Compile `fn` once, check the program holds a Pallas kernel where
    one was asked for, and run that same executable."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    found = "tpu_custom_call" in compiled.as_text()
    check(found or not want_kernel,
          "no tpu_custom_call in the compiled program: the kernel gave way "
          "to interpret mode or to the jax-level path")
    return compiled(*args), found


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def kernel_flash(size: Size, interpret: bool = False) -> dict:
    """Flash forward and the fused backward (causal, block-skip) against
    `full_attention` autodiff in f32 at highest matmul precision."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nd.attention import full_attention
    from deeplearning4j_tpu.nd.pallas_kernels import (flash_attention,
                                                      pick_attention_blocks)

    b, s, h, d = size.attn_batch, size.seq, size.heads, size.head_dim
    rng = np.random.default_rng(size.seed)
    q, k, v, w = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
                  for _ in range(4))
    bq, bk = pick_attention_blocks(s, d)

    def kernel(q, k, v):
        return flash_attention(q, k, v, True, bq, bk, interpret,
                               block_skip=True, fused_bwd=True)

    def reference(q, k, v):
        return full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)
                                       * w.astype(jnp.float32))

    out, fwd_kernel = _run_compiled(kernel, (q, k, v), not interpret)
    grads, bwd_kernel = _run_compiled(
        jax.grad(loss(kernel), argnums=(0, 1, 2)), (q, k, v), not interpret)
    with jax.default_matmul_precision("highest"):
        want = reference(q, k, v)
        want_grads = jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v)
    errs = {"out": _rel_err(out, want)}
    errs.update({name: _rel_err(g, wg) for name, g, wg
                 in zip(("dq", "dk", "dv"), grads, want_grads)})
    tol = 3e-2  # bf16 outputs: 2^-8 relative, with room for the p cast
    check(all(math.isfinite(e) and e <= tol for e in errs.values()),
          f"flash attention differs from full_attention: {errs} > {tol}")
    return {"kernel": "flash fwd + fused bwd (causal, block-skip)",
            "shape": f"B{b} S{s} H{h} hd{d} bf16", "blocks": [bq, bk],
            "tpu_custom_call": {"fwd": fwd_kernel, "bwd": bwd_kernel},
            "max_rel_err": errs, "tolerance": tol}


def kernel_lstm(size: Size, interpret: bool = False,
                dtype: str = "float32") -> dict:
    """The fused LSTM cell against `_lstm_reference` in f32 at highest
    matmul precision."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nd.pallas_kernels import (_lstm_reference,
                                                      fused_lstm_step)

    bsz, hid = size.lstm_batch, size.lstm_hidden
    rng = np.random.default_rng(size.seed + 1)
    dt = jnp.dtype(dtype)

    def arr(shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dt)

    args = (arr((bsz, hid)), arr((bsz, hid)), arr((bsz, hid)),
            arr((hid, 4 * hid), hid ** -0.5), arr((hid, 4 * hid), hid ** -0.5),
            arr((4 * hid,), 0.1))
    (h_new, c_new), found = _run_compiled(
        lambda *a: fused_lstm_step(*a, interpret), args, not interpret)
    with jax.default_matmul_precision("highest"):
        h_ref, c_ref = _lstm_reference(*(a.astype(jnp.float32) for a in args))
    errs = {"h": _rel_err(h_new, h_ref), "c": _rel_err(c_new, c_ref)}
    tol = 2e-2
    check(all(math.isfinite(e) and e <= tol for e in errs.values()),
          f"fused LSTM cell differs from _lstm_reference: {errs} > {tol}")
    return {"kernel": "fused LSTM cell",
            "shape": f"B{bsz} I{hid} H{hid} {dt.name}",
            "tpu_custom_call": found, "max_rel_err": errs, "tolerance": tol}


def phase_kernels(size: Size, interpret: bool = False) -> dict:
    return emit("kernels", interpret=interpret, kernels=[
        kernel_flash(size, interpret),
        kernel_lstm(size, interpret, "float32"),
        kernel_lstm(size, interpret, "bfloat16")])


# ---------------------------------------------------------------- train

_SYMBOL_BASE = 0x100  # 256 printable code points, none of them a newline


def write_inputs(size: Size, work: str, steps: int) -> tuple:
    """The zoo builder's conf with bf16 compute as JSON, and a synthetic
    corpus over `vocab` symbols for `steps` batches, both from the seed."""
    from deeplearning4j_tpu.models.zoo import char_transformer

    conf = char_transformer(size.vocab, d_model=size.d_model,
                            n_blocks=size.blocks, n_heads=size.heads,
                            max_seq_len=size.seq
                            ).with_compute_dtype("bfloat16")
    conf_path = os.path.join(work, "conf.json")
    with open(conf_path, "w") as f:
        f.write(conf.to_json())
    rng = np.random.default_rng(size.seed)
    n_chars = steps * size.batch * size.seq + 1
    # every symbol once, so the loader finds the whole vocabulary
    ids = np.concatenate([np.arange(size.vocab),
                          rng.integers(0, size.vocab, n_chars - size.vocab)])
    corpus = os.path.join(work, f"corpus_{steps}.txt")
    with open(corpus, "w", encoding="utf-8") as f:
        f.write("".join(chr(_SYMBOL_BASE + int(i)) for i in ids))
    return conf, conf_path, corpus


def run_cli(argv: List[str]) -> dict:
    """`cli.driver.main(argv)` in this process; returns the JSON object of
    the last line it printed."""
    from deeplearning4j_tpu.cli import driver

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = driver.main(argv)
    check(rc == 0, f"cli {argv[0]} returned {rc}")
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _fresh_net(conf):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    return MultiLayerNetwork(conf).init()


def _param_report(conf, ckpt: str) -> dict:
    """Checkpointed parameters against the seed's initial ones: every
    value finite, every layer moved."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel import checkpoint

    init = _fresh_net(conf).params
    trained, _, _ = checkpoint.load(ckpt, like_params=init)
    finite = all(bool(jnp.all(jnp.isfinite(leaf)))
                 for leaf in jax.tree_util.tree_leaves(trained))
    moved = [max((float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree_util.tree_leaves(li),
                                  jax.tree_util.tree_leaves(lt))),
                 default=None)
             for li, lt in zip(init, trained)]
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(init))
    check(finite, "a trained parameter is not finite")
    check(all(m is None or m > 0.0 for m in moved),
          f"a layer's parameters did not change: {moved}")
    return {"n_params": n, "all_finite": finite,
            "layers_changed": sum(m is not None for m in moved),
            "max_abs_change": max(m for m in moved if m is not None)}


def _cpu_reference_loss(net, ids, labels) -> dict:
    """The plain reference on a small input: the same weights and rows
    through the repo's forward on JAX's CPU backend in f32, against this
    backend's loss under the conf's bf16 compute."""
    import jax

    from deeplearning4j_tpu.nn.multilayer import network_loss

    got = net.score(ids, labels)
    out = {"rows": int(ids.shape[0]), "this_backend": got, "tolerance": 2e-2}
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError as e:  # JAX_PLATFORMS names no cpu: say so
        out["unavailable"] = str(e)
        return out
    conf = net.conf.with_compute_dtype("float32")
    want = float(jax.jit(
        lambda p, x, y: network_loss(conf, p, x, y, key=None, training=False)
    )(*jax.device_put((net.params, ids, labels), cpu)))
    out.update(cpu_f32=want, rel_diff=abs(got - want) / abs(want))
    return out


@contextlib.contextmanager
def xla_compile_events():
    """What JAX's own monitoring records inside the block: seconds in the
    backend compiler or in reading its persistent cache, and that cache's
    hits and writes."""
    import jax.monitoring as mon

    seen = {"backend_compile_seconds": 0.0, "cache_hits": 0,
            "cache_writes": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            seen["cache_writes"] += 1

    def on_duration(name, seconds, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            seen["backend_compile_seconds"] += seconds

    mon.register_event_listener(on_event)
    mon.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        mon.unregister_event_listener(on_event)
        mon.unregister_event_duration_listener(on_duration)


def _cli_train(size: Size, conf_path: str, corpus: str, ckpt: str,
               *extra: str) -> dict:
    """`cli train` on the corpus; its JSON, with what XLA's compiles cost
    under "xla"."""
    with xla_compile_events() as xla:
        cli = run_cli(["train", "--model", conf_path, "--input",
                       f"text:{corpus}:{size.seq}", "--output", ckpt,
                       "--properties", f"epochs=1,batch={size.batch}",
                       *extra])
    xla["backend_compile_seconds"] = round(xla["backend_compile_seconds"], 3)
    return {**cli, "xla": xla}


def phase_train(size: Size, work: str) -> dict:
    """`cli train --model <conf.json>` for a few steps, writing a
    checkpoint."""
    from deeplearning4j_tpu.cli.schemes import load_input
    from deeplearning4j_tpu.nn.conf import LayerType
    from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttentionLayer

    conf, conf_path, corpus = write_inputs(size, work, size.steps)
    uri = f"text:{corpus}:{size.seq}"
    # the loss the first step starts from: the seed's weights on the first
    # batch, read the way `cmd_train` reads it
    data = load_input(uri)
    check(data.vocab_size == size.vocab,
          f"corpus has {data.vocab_size} symbols, not {size.vocab}")
    ids = data.features[:size.batch].argmax(-1).astype("int32")
    labels = data.labels[:size.batch * size.seq]
    net = _fresh_net(conf)
    first_loss = net.score(ids, labels)
    reference = _cpu_reference_loss(net, ids[:2], labels[:2 * size.seq])
    del net, data
    gc.collect()

    ckpt = os.path.join(work, "ckpt_train")
    cli = _cli_train(size, conf_path, corpus, ckpt)
    ln_v = math.log(size.vocab)
    # a sanity band, no more: the zoo's init lets the residual stream grow
    # with depth, and 8 blocks start at 1.7 ln 256 on any backend
    check(0.9 * ln_v <= first_loss <= 3.0 * ln_v,
          f"first loss {first_loss} is outside [0.9, 3.0] ln {size.vocab}")
    check(reference.get("rel_diff", 0.0) <= reference["tolerance"],
          f"the chip's loss differs from the CPU's f32 loss: {reference}")
    check(math.isfinite(cli["score"]), f"final loss {cli['score']}")
    check(cli["cache_misses"] == 1 and cli["cache_hits"] == size.steps - 1,
          f"{size.steps} steps of one shape took {cli['cache_misses']} "
          f"compiles and {cli['cache_hits']} hits")
    attn = next(c for c in conf.confs
                if LayerType(str(c.layer_type)) == LayerType.ATTENTION)
    return emit(
        "train", model=size.model(), batch=size.batch, steps=size.steps,
        tokens_per_step=size.batch * size.seq, corpus="synthetic",
        first_loss=first_loss, ln_vocab=ln_v, cpu_reference=reference,
        final_loss=cli["score"],
        # a NaN loss would leave NaN parameters: finite parameters after
        # the last step say every step's loss and gradient were finite
        params=_param_report(conf, ckpt),
        compiles_after_first_step=cli["cache_misses"] - 1,
        attention_impl=MultiHeadAttentionLayer.resolve_impl(
            attn, size.batch, size.seq, size.heads),
        checkpoint=ckpt, peak_bytes=memory_per_device(), cli=cli)


def phase_train_again(size: Size, work: str) -> dict:
    """One step of the same `cli train` program, built a second time in a
    new process: its compile seconds against the first build's are the
    XLA cache's cold and warm."""
    _, conf_path, corpus = write_inputs(size, work, 1)
    cli = _cli_train(size, conf_path, corpus,
                     os.path.join(work, "ckpt_train_again"))
    return emit("train_again", steps=1, cli=cli)


# ------------------------------------------------------------ reference

# one prompt greedy; one sampled from the seed at a temperature that spreads
# the samples: four steps from a random start leave this model at a loss of
# 16, answering one token whatever the prompt (and still at temperature 1),
# and equal samples say more than equal constants
TEMPERATURES = [0.0, 8.0]


def _serve_rows(size: Size) -> np.ndarray:
    """Token ids for /v1/predict, as the float rows its JSON carries."""
    rng = np.random.default_rng(size.seed + 2)
    return rng.integers(0, size.vocab, (size.serve_rows, size.seq)
                        ).astype(np.float32)


def phase_reference(size: Size, work: str) -> dict:
    """What the servers are compared with: `net.output` on fixed rows under
    the bf16 serve policy, and `cli generate` on fixed prompts."""
    from deeplearning4j_tpu.cli import driver

    ckpt = os.path.join(work, "ckpt_train")
    rows = _serve_rows(size)
    prompts = np.random.default_rng(size.seed + 3).integers(
        0, size.vocab, (2, size.prompt_len)).tolist()
    net = driver._load_model(ckpt)
    net.set_serve_precision("bf16", measure=False)  # as `serve` will
    out = np.asarray(net.output(rows))
    check(out.shape == (size.serve_rows * size.seq, size.vocab),
          f"output shape {out.shape}")
    check(np.isfinite(out).all(), "net.output is not finite")
    del net
    gc.collect()
    generated = [run_cli(["generate", "--model", ckpt, "--prompt",
                          ",".join(map(str, p)), "--max-new-tokens",
                          str(size.new_tokens), "--max-seq", str(size.seq),
                          "--temperature", str(t), "--seed", str(size.seed)])
                 for p, t in zip(prompts, TEMPERATURES)]
    check(all(g["n_tokens"] == size.new_tokens for g in generated),
          f"cli generate gave {[g['n_tokens'] for g in generated]} tokens")
    np.savez(os.path.join(work, "reference.npz"), rows=rows, out=out)
    with open(os.path.join(work, "reference.json"), "w") as f:
        json.dump({"prompts": prompts, "temperatures": TEMPERATURES,
                   "tokens": [g["tokens"] for g in generated]}, f)
    return emit("reference", rows=list(rows.shape), prompts=len(prompts),
                generate=[{k: g[k] for k in ("tokens", "fresh_compiles",
                                             "platform")}
                          for g in generated])


# ---------------------------------------------------------------- serve

def _http(url: str, body: Optional[dict] = None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def child_env(extra: Optional[dict] = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, env.get("PYTHONPATH", "")) if p)
    env.update(extra or {})
    return env


class Served:
    """One `python -m deeplearning4j_tpu.cli serve ...` process: started,
    ready once its startup JSON is read, SIGTERMed on the way out."""

    READY_TIMEOUT_S = 600.0

    def __init__(self, argv: List[str]):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve", *argv],
            stdout=subprocess.PIPE, text=True, cwd=HERE, env=child_env(),
            start_new_session=True)
        self.startup: dict = {}
        self.drained: dict = {}

    def __enter__(self) -> "Served":
        try:
            ready, _, _ = select.select([self.proc.stdout], [], [],
                                        self.READY_TIMEOUT_S)
            line = self.proc.stdout.readline() if ready else ""
            check(line, "serve printed no startup line "
                        f"(exit {self.proc.poll()})")
            self.startup = json.loads(line)
        except BaseException:
            self._kill()
            raise
        return self

    def stop(self) -> None:
        """SIGTERM -> the drained JSON and exit 0."""
        self.proc.send_signal(signal.SIGTERM)
        out, _ = self.proc.communicate(timeout=120)
        lines = out.strip().splitlines()
        check(lines, "serve printed nothing on SIGTERM")
        self.drained = json.loads(lines[-1])
        check(self.proc.returncode == 0 and self.drained.get("drained"),
              f"serve exited {self.proc.returncode} with {self.drained}")

    def _kill(self) -> None:
        # the whole session: a router's replicas go with it
        with contextlib.suppress(ProcessLookupError):
            os.killpg(self.proc.pid, signal.SIGKILL)
        self.proc.wait()

    def __exit__(self, *exc) -> None:
        if self.proc.poll() is None:
            self._kill()


def _predict(url: str, rows: np.ndarray) -> np.ndarray:
    body = json.loads(_http(url + "/v1/predict",
                            {"features": rows.tolist()}))
    return np.asarray(body["output"], np.float32)


def _predict_pairs(url: str, ref, size: Size, row_counts=None):
    """(answer, direct output on the same rows) for a few requests of
    different row counts."""
    for n in row_counts or sorted({1, size.serve_rows // 2 or 1,
                                   size.serve_rows}):
        got = _predict(url, ref["rows"][:n])
        want = ref["out"][:n * size.seq]
        check(got.shape == want.shape and np.isfinite(got).all(),
              f"predict of {n} rows: shape {got.shape}")
        yield got, want


def _check_predict(url: str, ref, size: Size, tol: float,
                   row_counts=None) -> float:
    """The same program on the same rows: every answer within `tol` of
    the largest direct output."""
    worst = max(_rel_err(got, want) for got, want
                in _predict_pairs(url, ref, size, row_counts))
    check(worst <= tol, f"predict differs from net.output by {worst} > {tol} "
                        "of the largest output")
    return worst


def _no_compile_since(url: str, startup: dict) -> dict:
    stats = json.loads(_http(url + "/v1/stats"))
    check(stats["fresh_compiles"] == startup["fresh_compiles"],
          f"compiles after warm-up: {stats['fresh_compiles']} against "
          f"{startup['fresh_compiles']} at start")
    return stats


def phase_serve_predict(size: Size, work: str) -> dict:
    """`cli serve --precision bf16` answering POST /v1/predict."""
    ref = dict(np.load(os.path.join(work, "reference.npz")))
    tol = 1e-3  # of the largest output: the same program on the same rows
    with Served(["--model", os.path.join(work, "ckpt_train"),
                 "--precision", "bf16", "--shapes",
                 f"{size.serve_rows}x{size.seq}"]) as srv:
        url = srv.startup["url"]
        worst = _check_predict(url, ref, size, tol)
        stats = _no_compile_since(url, srv.startup)
        srv.stop()
    return emit("serve_predict", precision=srv.startup["precision"],
                requests=stats["requests"], max_rel_diff=worst,
                tolerance=tol, compiles_after_warmup=0,
                platform=srv.startup["platform"], drained=srv.drained)


def _generate(url: str, prompt: List[int], temperature: float,
              size: Size) -> List[int]:
    raw = _http(url + "/v1/generate",
                {"prompt": prompt, "max_new_tokens": size.new_tokens,
                 "temperature": temperature, "rng_seed": size.seed})
    lines = [json.loads(l) for l in raw.decode().splitlines() if l.strip()]
    check(lines and lines[-1].get("done") and "error" not in lines[-1],
          f"generate stream ended with {lines[-1:]}")
    return [l["token"] for l in lines if "token" in l]


def phase_serve_generate(size: Size, work: str) -> dict:
    """`cli serve --generate` answering POST /v1/generate, greedy and
    sampled, against `cli generate` on the same prompts and seed.  Both run the checkpoint
    as trained (bf16 compute over f32 weights) in one decode slot, so they
    run the same programs; `--precision bf16` rounds the weights themselves
    and `generate` has no such option, so that server answers predict."""
    with open(os.path.join(work, "reference.json")) as f:
        ref = json.load(f)
    with Served(["--model", os.path.join(work, "ckpt_train"),
                 "--shapes", "", "--generate", "--gen-slots", "1",
                 "--gen-max-seq", str(size.seq), "--gen-prompt-buckets",
                 str(_prompt_bucket(size.prompt_len))]) as srv:
        url = srv.startup["url"]
        tokens = [_generate(url, p, t, size)
                  for p, t in zip(ref["prompts"], ref["temperatures"])]
        check(tokens == ref["tokens"],
              f"served tokens {tokens} differ from cli generate's "
              f"{ref['tokens']}")
        stats = _no_compile_since(url, srv.startup)
        srv.stop()
    return emit("serve_generate", prompts=len(tokens),
                temperatures=ref["temperatures"],
                tokens_each=size.new_tokens, equal_to_cli_generate=True,
                streams=stats["generation"]["streams"],
                compiles_after_warmup=0,
                platform=srv.startup["platform"], drained=srv.drained)


def _prompt_bucket(n: int) -> int:
    """The prefill bucket `cli generate` picks for an n-token prompt."""
    return max(4, 1 << (n - 1).bit_length())


# ---------------------------------------------------------------- cache

def cache_entries(directory: str) -> int:
    try:
        return sum(1 for name in os.listdir(directory)
                   if not name.endswith("-atime"))
    except FileNotFoundError:
        return 0


def phase_cache(cache_dir: str, before: int, first: dict,
                second: dict) -> dict:
    """Where the XLA cache lives, what it held, and what the train
    program's compiles cost in this run's first and second `cli train`."""
    after = cache_entries(cache_dir)

    def build(line):
        cli = line["cli"]
        return {"compile_seconds": cli["compile_seconds"], **cli["xla"]}

    cold, warm = build(first), build(second)
    check(after > 0, f"no entry under {cache_dir}")
    # no inequality between the two builds is asserted: two processes'
    # seconds differ by more than the cache saves (most of the step's
    # compile_seconds is tracing and lowering, which no cache spares)
    check(warm["cache_hits"] > 0,
          f"the second build read nothing from the cache: {warm}")
    return emit(
        "cache", dir=cache_dir,
        placed_by=("JAX_COMPILATION_CACHE_DIR"
                   if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                   else "platform.place_compile_cache"),
        entries_before=before, entries_after=after,
        first_build=cold, second_build=warm,
        how="second_build is one step of the same `cli train` program in "
            "a second process of this run; compile_seconds is the CLI's "
            "own for the train step (trace, lower and compile); the other "
            "three are JAX's counts over the whole `cli train` call, "
            "backend_compile_seconds being time in XLA or in reading its "
            "cache; first_build is cold only if entries_before is 0")


# ------------------------------------------------------------ four chips

def phase_mesh_train(size: Size, work: str, name: str) -> dict:
    """`cli train --runtime mesh`, one step on every device this process
    was given; with four, against the one-device run's checkpoint."""
    import jax

    from deeplearning4j_tpu.parallel import checkpoint

    conf, conf_path, corpus = write_inputs(size, work, 1)
    ckpt = os.path.join(work, f"ckpt_{name}")
    cli = _cli_train(size, conf_path, corpus, ckpt, "--runtime", "mesh")
    n_dev = len(jax.devices())
    check(cli["device_count"] == n_dev and math.isfinite(cli["score"]),
          f"train JSON {cli}")
    fields = {"devices": n_dev, "loss_after_step": cli["score"],
              "peak_bytes": memory_per_device(64 << 20), "cli": cli}
    if name != "mesh_one":
        one = os.path.join(work, "ckpt_mesh_one")
        init = _fresh_net(conf).params
        with open(os.path.join(one, "meta.json")) as f:
            one_loss = json.load(f)["metadata"]["score"]
        fields.update(_update_diff(
            init, checkpoint.load(one, like_params=init)[0],
            checkpoint.load(ckpt, like_params=init)[0]))
        fields["one_device_loss"] = one_loss
        # Adam's first step moves every weight by about the learning rate
        # in the sign of its gradient, so a weight whose gradient is lost
        # in rounding flips by 2 lr: a fraction f of flips shows as
        # sqrt(4 f) here.  0.1 admits f < 0.25%; the gradient of one
        # shard alone would flip a large share of the signs
        tol = {"loss_rel": 1e-2, "update_rel_l2": 0.1}
        check(abs(cli["score"] - one_loss) <= tol["loss_rel"] * abs(one_loss)
              and fields["update_rel_l2_diff"] <= tol["update_rel_l2"],
              f"four devices differ from one: {fields}")
        fields["tolerance"] = tol
    return emit(name, model=size.model(), batch=size.batch, **fields)


def _update_diff(init, one, four) -> dict:
    """How far the four-device update is from the one-device update."""
    import jax
    import jax.numpy as jnp

    num = den = 0.0
    worst = 0.0
    for p0, p1, p4 in zip(*(jax.tree_util.tree_leaves(t)
                            for t in (init, one, four))):
        num += float(jnp.sum((p4 - p1) ** 2))
        den += float(jnp.sum((p1 - p0) ** 2))
        worst = max(worst, float(jnp.max(jnp.abs(p4 - p1))))
    check(den > 0.0, "the one-device step did not move the parameters")
    return {"update_rel_l2_diff": math.sqrt(num / den),
            "max_abs_param_diff": worst}


def _serve_in_this_process(argv: List[str], client) -> dict:
    """`cli.driver.main(["serve", ...])` on this thread, with `client(url)`
    on another: the way to look at the server's arrays and each device's
    memory, which only the process that holds the chips can do.  The
    client's end sends the SIGTERM a user would."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    box: dict = {}

    def drive():
        try:
            deadline = time.monotonic() + 600
            while True:
                try:
                    _http(url + "/readyz", timeout=5)
                    break
                except OSError:
                    check(time.monotonic() < deadline, "serve never ready")
                    time.sleep(0.5)
            box["result"] = client(url)
        except BaseException as e:  # noqa: BLE001 — handed to the caller
            box["error"] = e
        finally:
            os.kill(os.getpid(), signal.SIGTERM)

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    out = run_cli(["serve", *argv, "--port", str(port)])
    t.join(timeout=60)
    if "error" in box:
        raise box["error"]
    check(out.get("drained"), f"serve ended with {out}")
    return box["result"]


def _rel_mse(got, want) -> float:
    return float(np.mean((got - want) ** 2) / np.mean(want ** 2))


def _check_near_f32(url: str, ref, size: Size, slack: float = 2.0) -> dict:
    """Answers of a program that is partitioned otherwise than the
    one-device program (`ref["out"]`).  It sums bf16 products in another
    order, so the two differ by rounding, and by how much depends on the
    model and the backend, not on the mesh: the measure is the same
    weights computed in f32 (`ref["exact"]`), which the answers must be as
    near to as one device's are, within `slack` in relative MSE."""
    pairs = list(_predict_pairs(url, ref, size))
    got = np.concatenate([g for g, _ in pairs])
    want = np.concatenate([w for _, w in pairs])
    truth = np.concatenate([ref["exact"][:len(w)] for _, w in pairs])
    diff = {"vs_f32_rel_mse": _rel_mse(got, truth),
            "one_device_vs_f32_rel_mse": _rel_mse(want, truth),
            "vs_one_device_max_rel_diff": _rel_err(got, want),
            "slack": slack}
    check(diff["vs_f32_rel_mse"] <= slack * diff["one_device_vs_f32_rel_mse"],
          f"the answer is further from the f32 answer than {slack} times "
          f"one device's: {diff}")
    return diff


def phase_mesh_serve(size: Size, work: str, spec: str = "batch=2,model=2"
                     ) -> dict:
    """`cli serve --mesh batch=2,model=2` against the single-device
    output on the same rows."""
    import jax

    from deeplearning4j_tpu.cli import driver
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    ckpt = os.path.join(work, "ckpt_mesh_four")
    net = driver._load_model(ckpt)
    exact_net = MultiLayerNetwork(net.conf.with_compute_dtype("float32"))
    exact_net.params = net.params
    rows = _serve_rows(size)
    ref = {"rows": rows,
           "out": np.asarray(net.output(rows)),  # default device, no mesh
           "exact": np.asarray(exact_net.output(rows))}
    np.savez(os.path.join(work, "reference_mesh.npz"), **ref)
    del net, exact_net
    gc.collect()

    def client(url):
        diff = _check_near_f32(url, ref, size)
        sharded = [a for a in jax.live_arrays()
                   if len(a.sharding.device_set) > 1]
        spans = {len({s.device for s in a.addressable_shards})
                 for a in sharded}
        split = sum(not a.sharding.is_fully_replicated for a in sharded)
        return {**diff, "arrays_on_the_mesh": len(sharded),
                "arrays_split_not_replicated": split,
                "devices_spanned": sorted(spans),
                # while the server is up: what it holds now, not the peak
                # of whatever ran in this process before it
                "bytes_in_use": memory_per_device(16 << 20, "bytes_in_use")}

    n_dev = len(jax.devices())
    result = _serve_in_this_process(
        ["--model", ckpt, "--mesh", spec, "--shapes",
         f"{size.serve_rows}x{size.seq}"], client)
    check(result["arrays_on_the_mesh"] > 0
          and result["devices_spanned"] == [n_dev]
          and result["arrays_split_not_replicated"] > 0,
          f"the mesh's arrays do not span {n_dev} devices: {result}")
    return emit("mesh_serve", mesh=spec, devices=n_dev, **result)


def _loads_libtpu(pid: int) -> bool:
    with open(f"/proc/{pid}/maps") as f:
        return any("libtpu" in line for line in f)


def phase_replicas(size: Size, work: str, n: int = 4,
                   mesh: Optional[str] = None, chips_each: int = 1,
                   want_platform: str = "tpu") -> dict:
    """`cli serve --replicas n [--mesh ...]`: n replica processes behind
    the router, each on `chips_each` chips of its own, and the router
    process on none.  (The CPU rehearsal passes `want_platform="cpu"`: no
    libtpu to look for, and every process sees every virtual device.)"""
    ref = dict(np.load(os.path.join(work, "reference_mesh.npz")))
    with Served(["--model", os.path.join(work, "ckpt_mesh_four"),
                 "--replicas", str(n), *(["--mesh", mesh] if mesh else []),
                 "--shapes", f"{size.serve_rows}x{size.seq}"]) as srv:
        up = srv.startup
        devices = up["replica_devices"]
        chips = [c for d in devices for c in d["chip"].split(",")]
        check(len(devices) == n
              and all(d["platform"] == want_platform for d in devices)
              and (want_platform != "tpu"
                   or all(d["device_count"] == chips_each for d in devices))
              and len(set(chips)) == len(chips) == n * chips_each
              and up["mesh_devices"] == (chips_each if mesh else None),
              f"replicas are not on {chips_each} chip(s) each of their "
              f"own: {devices}, mesh_devices {up['mesh_devices']}")
        router_on_chip = _loads_libtpu(srv.proc.pid)
        replicas_on_chip = [_loads_libtpu(p) for p in up["replica_pids"]]
        check(not router_on_chip
              and (want_platform != "tpu" or all(replicas_on_chip)),
              f"libtpu loaded: router {router_on_chip}, replicas "
              f"{replicas_on_chip}")
        # round-robin: every replica answers
        if mesh is None:  # the one-device program on one chip
            compared = {"tolerance": 1e-3, "max_rel_diff": max(
                _check_predict(up["url"], ref, size, 1e-3, row_counts=(1,))
                for _ in range(2 * n))}
        else:
            compared = max((_check_near_f32(up["url"], ref, size)
                            for _ in range(n)),
                           key=lambda d: d["vs_f32_rel_mse"])
        answered = [json.loads(_http(u + "/v1/stats"))["requests"]
                    for u in up["replicas"]]
        check(all(a > 0 for a in answered),
              f"a replica answered nothing: {answered}")
        srv.stop()
    check(srv.drained["replica_exit_codes"] == [0] * n,
          f"replica exit codes {srv.drained['replica_exit_codes']}")
    return emit("replicas", replicas=n, mesh=mesh, replica_devices=devices,
                router_loaded_libtpu=router_on_chip,
                replicas_loaded_libtpu=replicas_on_chip,
                requests_per_replica=answered, **compared,
                drained=srv.drained)


# ------------------------------------------------------------- children

def phase_device(want_count: int) -> dict:
    facts = require_tpu()
    check(facts["count"] == want_count,
          f"needs {want_count} chip(s), JAX reports {facts['count']}")
    return emit("device", **facts)


def phase_native() -> dict:
    """Which loader `datasets/` would take: the library is git-ignored and
    built on demand from `native/dataloader.cc`."""
    from deeplearning4j_tpu import native

    built = native.get_library() is not None
    return emit("native", loader="built" if built else "python",
                library=native._LIB if built else None)


CHILD_PHASES = {
    "device1": lambda size, work: phase_device(1),
    "device4": lambda size, work: phase_device(4),
    "kernels": lambda size, work: phase_kernels(size),
    "train": phase_train,
    "train_again": phase_train_again,
    "reference": phase_reference,
    "mesh_one": lambda size, work: phase_mesh_train(size, work, "mesh_one"),
    "mesh_four": lambda size, work: phase_mesh_train(size, work,
                                                     "mesh_four"),
    "mesh_serve": phase_mesh_serve,
}


def run_child(phases: str, size: Size, work: str) -> int:
    """Hold the chip for these phases and exit."""
    try:
        require_tpu()
        platform.place_compile_cache()
        for name in phases.split(","):
            CHILD_PHASES[name](size, work)
    except NoChip as e:
        print(f"chip_smoke: {e}", file=sys.stderr, flush=True)
        return NO_CHIP
    except Exception:  # noqa: BLE001 — boundary: report and fail the run
        traceback.print_exc()
        return 1
    return 0


def child(phases: str, size: Size, work: str,
          env: Optional[dict] = None) -> List[dict]:
    """Run phases in a child that has exited when this returns; its JSON
    lines are echoed and returned."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phases,
         "--work", work, "--size", json.dumps(dataclasses.asdict(size))],
        stdout=subprocess.PIPE, text=True, cwd=HERE, env=child_env(env))
    lines = []
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            lines.append(json.loads(line))
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc == NO_CHIP:
        raise NoChip(f"phase {phases}")
    check(rc == 0, f"phase {phases} exited {rc}")
    return lines


# ----------------------------------------------------------------- main

def run_one_chip(size: Size, work: str) -> dict:
    device = child("device1,kernels", size, work)[0]
    cache_dir = platform.place_compile_cache()
    before = cache_entries(cache_dir)
    phase_native()
    first = child("train", size, work)[-1]
    child("reference", size, work)
    phase_serve_predict(size, work)
    phase_serve_generate(size, work)
    second = child("train_again", size, work)[-1]
    phase_cache(cache_dir, before, first, second)
    return device


def run_four_chips(size: Size, work: str) -> dict:
    device = child("device4", size, work)[0]
    child("mesh_one", size, work, env=platform.chip_env(0))
    child("mesh_four,mesh_serve", size, work)
    phase_replicas(size, work)
    # the two together: each replica's mesh on two chips of its own
    phase_replicas(size, work, n=2, mesh="batch=2", chips_each=2)
    return device


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the paths that exist across four "
                         "chips (the builder runs it; the driver does not)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    ap.add_argument("--size", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return run_child(args.phase, Size(**json.loads(args.size)),
                         args.work)
    size = Size(seed=args.seed)
    work = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        run = run_one_chip if args.chips == 1 else run_four_chips
        device = run(size, work)
    except NoChip as e:
        print(f"chip_smoke: no TPU ({e})", file=sys.stderr, flush=True)
        return NO_CHIP
    except Exception:  # noqa: BLE001 — boundary: report and fail the run
        traceback.print_exc()
        print(json.dumps({"ok": False}), flush=True)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        k: device[k] for k in ("platform", "kind", "count")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
