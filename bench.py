"""Benchmark suite — the full BASELINE matrix + transformer MFU.

Emits ONE JSON line per metric:
  {"metric", "value", "unit", "vs_baseline", ...}

Metrics (BASELINE.json):
  configs[0]  LeNet-5 MNIST          train samples/sec/chip
  configs[1]  char-LSTM (PTB-style)  train chars/sec/chip
  configs[3]  Word2Vec skip-gram     words/sec
  configs[4]  data-parallel MLP      all-reduce step time (ms)
  flagship    char-transformer LM    MFU (model FLOPs utilization)

The reference publishes no numbers (BASELINE.md); each `vs_baseline` is
against an *assumed* figure for the 2015 CPU-jblas ND4J stack, labelled in
the `baseline_note` field — indicative, not a measured A/B.

Runs in one process, on the chip: it refuses to start on any other
platform (`DL4J_BENCH_SMALL=1` is the one way to run it on the CPU, at
tiny shapes, for the tests), stops at the first bench that fails with a
non-zero exit, and every line names the platform it ran on.  Getting a
chip is the job of whoever starts this program.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np

# wall-clock bound on a bench's own child processes
PER_BENCH_BUDGET_S = 300
# smoke-test mode: tiny shapes/steps so the suite runs in seconds on CPU
SMALL = os.environ.get("DL4J_BENCH_SMALL") == "1"


def _emit(metric: str, value: float, unit: str, vs_baseline, **extra) -> None:
    line = {"metric": metric, "value": round(float(value), 4), "unit": unit,
            "vs_baseline": (round(float(vs_baseline), 4)
                            if vs_baseline is not None else None)}
    line.update(extra)
    if "platform" not in line:  # host-only benches stamp "cpu" themselves
        from deeplearning4j_tpu.nd import platform

        line.update(platform=platform.default_platform(),
                    device_kind=platform.devices()[0].device_kind)
    print(json.dumps(line), flush=True)


def _host_sync(tree) -> float:
    """Close an async dispatch chain with a host read of a value that is
    data-dependent on the chain: the timed region ends when the device
    has finished, not when the work was enqueued."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(tree)
    return float(jnp.sum(leaves[0]))


def _mixed(conf):
    """bf16 MXU operands / f32 master weights (+23% measured on LeNet)."""
    return conf.replace(confs=tuple(c.replace(compute_dtype="bfloat16")
                                    for c in conf.confs))


# ---------------------------------------------------------------------------
# configs[0] — LeNet-5 MNIST
# ---------------------------------------------------------------------------

def bench_lenet(devs) -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import lenet5
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh, shard_batch

    batch, warmup, steps = (64, 1, 4) if SMALL else (4096, 2, 30)
    n_dev = len(devs)
    mesh = make_mesh({"dp": n_dev})
    conf = _mixed(lenet5())
    net = MultiLayerNetwork(conf, seed=0).init()
    trainer = DataParallelTrainer(net, mesh, mode="sync")

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 784), jnp.float32)
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)])
    x, y = shard_batch(mesh, (x, y), "dp")

    key = jax.random.PRNGKey(0)
    tw = time.perf_counter()
    for _ in range(warmup):
        trainer.state, _ = trainer._step(trainer.state, x, y, key)
    _host_sync(trainer.state.params)
    warm_s = time.perf_counter() - tw

    t0 = time.perf_counter()
    for _ in range(steps):
        trainer.state, _ = trainer._step(trainer.state, x, y, key)
    _host_sync(trainer.state.params)
    dt = time.perf_counter() - t0

    per_chip = steps * batch / dt / n_dev
    assumed = 500.0
    _emit("LeNet5-MNIST train samples/sec/chip", per_chip,
          "samples/sec/chip", per_chip / assumed,
          warmup_seconds=round(warm_s, 1),
          baseline_note=f"assumed {assumed:g} samples/sec, 2015 CPU-jblas")


# ---------------------------------------------------------------------------
# configs[1] — char-LSTM (PTB-style)
# ---------------------------------------------------------------------------

def _char_lstm_throughput(devs, n_layers: int):
    """Returns (chars/sec/chip, warmup seconds)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import char_lstm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh, shard_batch

    vocab, hidden, seq, batch = ((50, 32, 16, 8) if SMALL else
                                 (50, 256, 64, 256))  # PTB-ish char setup
    warmup, steps = (1, 2) if SMALL else (2, 18)
    n_dev = len(devs)
    mesh = make_mesh({"dp": n_dev})
    # int char ids in, int class-id targets out (ROADMAP item 2): the
    # embedding gather replaces the [B,S,vocab] one-hot input and
    # sparse_labels replaces the [B*S,vocab] one-hot loss gemm
    conf = _mixed(char_lstm(vocab, hidden=hidden, n_layers=n_layers,
                            sparse_labels=True, embed=hidden))
    net = MultiLayerNetwork(conf, seed=0).init()
    trainer = DataParallelTrainer(net, mesh, mode="sync")

    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq + 1))
    x = jnp.asarray(ids[:, :-1], jnp.int32)
    y = jnp.asarray(ids[:, 1:].reshape(batch * seq), jnp.int32)
    x, y = shard_batch(mesh, (x, y), "dp")

    key = jax.random.PRNGKey(0)
    tw = time.perf_counter()
    for _ in range(warmup):
        trainer.state, _ = trainer._step(trainer.state, x, y, key)
    _host_sync(trainer.state.params)
    warm_s = time.perf_counter() - tw

    t0 = time.perf_counter()
    for _ in range(steps):
        trainer.state, _ = trainer._step(trainer.state, x, y, key)
    _host_sync(trainer.state.params)
    dt = time.perf_counter() - t0
    return steps * batch * seq / dt / n_dev, warm_s


def bench_char_lstm(devs) -> None:
    chars_per_sec, warm_s = _char_lstm_throughput(devs, n_layers=1)
    # reference LSTM.java:161-228 is a scalar per-timestep java loop;
    # era-typical full BPTT on CPU ~ a few k chars/sec
    assumed = 5000.0
    _emit("charLSTM-PTB train chars/sec/chip", chars_per_sec,
          "chars/sec/chip", chars_per_sec / assumed,
          warmup_seconds=round(warm_s, 1),
          baseline_note=f"assumed {assumed:g} chars/sec, 2015 CPU scalar "
                        "BPTT loop")


def bench_char_lstm4(devs) -> None:
    """BASELINE north-star: the 4-layer LSTM trained end-to-end on TPU."""
    chars_per_sec, warm_s = _char_lstm_throughput(devs, n_layers=4)
    assumed = 1500.0  # 4x the BPTT work of the 1-layer CPU loop
    _emit("charLSTM-4layer (north-star) train chars/sec/chip", chars_per_sec,
          "chars/sec/chip", chars_per_sec / assumed,
          warmup_seconds=round(warm_s, 1),
          baseline_note=f"assumed {assumed:g} chars/sec, 2015 CPU scalar "
                        "BPTT loop x4 layers")


# ---------------------------------------------------------------------------
# configs[2] — VGG-style ConvNet on CIFAR-10 (BatchNorm-heavy conv stack)
# ---------------------------------------------------------------------------

def bench_vgg_cifar10(devs) -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import vgg_cifar10
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh, shard_batch

    width, batch, warmup, steps = ((8, 16, 1, 2) if SMALL else
                                   (64, 512, 2, 12))
    n_dev = len(devs)
    mesh = make_mesh({"dp": n_dev})
    conf = _mixed(vgg_cifar10(width=width))
    net = MultiLayerNetwork(conf, seed=0).init()
    trainer = DataParallelTrainer(net, mesh, mode="sync")

    # real CIFAR-10 when a local copy/source exists, class-separable
    # synthetic otherwise (datasets/cifar.py) — not pure noise
    from deeplearning4j_tpu.datasets.fetchers import Cifar10DataFetcher

    data = Cifar10DataFetcher().fetch(batch)
    x = jnp.asarray(data.features[:batch], jnp.float32)
    y = jnp.asarray(data.labels[:batch], jnp.float32)
    x, y = shard_batch(mesh, (x, y), "dp")

    key = jax.random.PRNGKey(0)
    tw = time.perf_counter()
    for _ in range(warmup):
        trainer.state, _ = trainer._step(trainer.state, x, y, key)
    _host_sync(trainer.state.params)
    warm_s = time.perf_counter() - tw

    t0 = time.perf_counter()
    for _ in range(steps):
        trainer.state, _ = trainer._step(trainer.state, x, y, key)
    _host_sync(trainer.state.params)
    dt = time.perf_counter() - t0

    per_chip = steps * batch / dt / n_dev
    # VGG-depth convnets on 2015 CPUs ran a few tens of images/sec
    assumed = 30.0
    _emit("VGG-CIFAR10 train samples/sec/chip", per_chip,
          "samples/sec/chip", per_chip / assumed,
          warmup_seconds=round(warm_s, 1),
          baseline_note=f"assumed {assumed:g} samples/sec, 2015 CPU conv")


# ---------------------------------------------------------------------------
# configs[3] — Word2Vec skip-gram + negative sampling
# ---------------------------------------------------------------------------

def bench_word2vec(devs) -> None:
    from deeplearning4j_tpu.models.word2vec import Word2Vec

    rng = np.random.RandomState(0)
    # realistic scale: word2vec corpora are millions of tokens over
    # several passes (word2vec.c defaults to multi-epoch runs), so the
    # one-time epoch-scan XLA compile — the dominant fixed cost — is
    # amortized over n_tokens * epochs trained words
    vocab_n, n_tokens, sent_len, epochs = ((200, 4000, 20, 1) if SMALL else
                                           (10_000, 1_200_000, 20, 6))
    # zipf-ish unigram draw: realistic subsampling + negative table shape
    freq = 1.0 / np.arange(1, vocab_n + 1)
    probs = freq / freq.sum()
    tokens = rng.choice(vocab_n, size=n_tokens, p=probs)
    words = np.array([f"w{i}" for i in range(vocab_n)])
    sents = [list(words[tokens[i:i + sent_len]])
             for i in range(0, n_tokens, sent_len)]

    w2v = Word2Vec(vector_length=128, window=5, negative=5,
                   min_word_frequency=1, epochs=epochs, seed=0,
                   batch_size=64 if SMALL else 32_768)
    t0 = time.perf_counter()
    w2v.fit(sents)
    _host_sync(w2v.table.syn0)
    dt = time.perf_counter() - t0

    words_per_sec = n_tokens * epochs / dt
    # word2vec.c on a 2015 multicore CPU: ~100k words/sec; DL4J's java
    # HogWild (InMemoryLookupTable.iterateSample) era-typical ~50k
    assumed = 50_000.0
    _emit("Word2Vec skipgram words/sec", words_per_sec, "words/sec",
          words_per_sec / assumed,
          baseline_note=f"assumed {assumed:g} words/sec, 2015 CPU HogWild")


# ---------------------------------------------------------------------------
# configs[4] — data-parallel MLP all-reduce step time
# ---------------------------------------------------------------------------

def bench_dp_allreduce(devs) -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh, shard_batch

    batch, warmup, steps = (64, 1, 4) if SMALL else (8192, 2, 24)
    n_dev = len(devs)
    mesh = make_mesh({"dp": n_dev})
    conf = mlp(784, [512, 512], 10)
    net = MultiLayerNetwork(conf, seed=0).init()
    trainer = DataParallelTrainer(net, mesh, mode="sync")

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 784), jnp.float32)
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)])
    x, y = shard_batch(mesh, (x, y), "dp")

    key = jax.random.PRNGKey(0)
    tw = time.perf_counter()
    for _ in range(warmup):
        trainer.state, _ = trainer._step(trainer.state, x, y, key)
    _host_sync(trainer.state.params)
    warm_s = time.perf_counter() - tw

    t0 = time.perf_counter()
    for _ in range(steps):
        trainer.state, _ = trainer._step(trainer.state, x, y, key)
    _host_sync(trainer.state.params)
    ms = (time.perf_counter() - t0) / steps * 1e3

    # reference round = broadcast whole params + fit + shuffle-average on
    # Spark local[8] (SparkDl4jMultiLayer.java:157-210); era-typical ~1s
    assumed_ms = 1000.0
    note = (f"assumed {assumed_ms:g} ms/round, Spark local[8]; "
            "vs_baseline = speedup")
    if n_dev == 1:
        # honesty (VERDICT r2 weak #4): pmean over a 1-device mesh is a
        # no-op — this measures the full train step, not a collective.
        # The 8-device collective path is validated by dryrun_multichip
        # (MULTICHIP artifact) and tests/test_parallel.py equivalences.
        note += ("; SINGLE-DEVICE mesh: no collective crosses a link, "
                 "metric = full step time only")
    _emit("DP-MLP all-reduce step time", ms, "ms/step",
          assumed_ms / ms,  # >1 = faster than baseline
          n_devices=n_dev, warmup_seconds=round(warm_s, 1),
          baseline_note=note)


def bench_elastic_resume(devs) -> None:
    """Cost of crash-resumable mesh training (ISSUE 10): steady-state
    step time with checkpointing off vs on (one atomic write every 5
    steps), seconds per checkpoint write, and the restore-and-reshard
    latency of an elastic N -> N/2 resume."""
    import shutil
    import tempfile

    import jax

    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    batch, steps, every_n = (64, 10, 5) if SMALL else (4096, 40, 5)
    n_dev = len(devs)
    mesh = make_mesh({"dp": n_dev})
    rng = np.random.RandomState(0)
    x = rng.rand(batch, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)]
    batches = [(x, y)] * steps

    def run(ckpt_dir, every):
        net = MultiLayerNetwork(mlp(784, [512, 512], 10), seed=0).init()
        t = DataParallelTrainer(net, mesh, mode="sync")
        t.fit(batches[:2], epochs=1)  # compile outside the timed window
        t0 = time.perf_counter()
        t.fit(batches, epochs=1, checkpoint_dir=ckpt_dir,
              checkpoint_every_n_batches=every, auto_resume=False)
        _host_sync(t.state.params)
        return (time.perf_counter() - t0) / steps * 1e3, t

    work = tempfile.mkdtemp(prefix="dl4j-bench-elastic-")
    try:
        off_ms, _ = run(None, 0)
        ck = os.path.join(work, "ck")
        on_ms, trainer = run(ck, every_n)
        per_write_s = (trainer.checkpoint_write_seconds /
                       max(trainer.checkpoints_written, 1))
        _emit("elastic ckpt steady-state step overhead", on_ms - off_ms,
              "ms/step", off_ms / on_ms,  # ~1 = checkpointing is free
              n_devices=n_dev, every_n_batches=every_n,
              step_ms_off=round(off_ms, 3), step_ms_on=round(on_ms, 3),
              writes=trainer.checkpoints_written,
              baseline_note="vs_baseline = off/on step-time ratio "
                            "(1.0 = zero overhead)")
        _emit("elastic ckpt write time", per_write_s, "s/write", None,
              n_devices=n_dev)

        # elastic restore: the checkpoint written on n_dev chips re-places
        # on an n_dev/2 mesh (host materialize + device_put per leaf)
        half = max(1, n_dev // 2)
        mesh_half = make_mesh({"dp": half}, devices=jax.devices()[:half])
        net2 = MultiLayerNetwork(mlp(784, [512, 512], 10), seed=0).init()
        t2 = DataParallelTrainer(net2, mesh_half, mode="sync")
        t0 = time.perf_counter()
        t2.restore(ck)
        _host_sync(t2.state.params)
        restore_s = time.perf_counter() - t0
        _emit("elastic restore+reshard latency", restore_s * 1e3, "ms", None,
              from_devices=n_dev, to_devices=half)
    finally:
        shutil.rmtree(work, ignore_errors=True)


# ---------------------------------------------------------------------------
# flagship — char-transformer MFU
# ---------------------------------------------------------------------------

_PEAK_BF16_FLOPS = (  # per chip; substring-matched against device_kind
    ("v6", 918e12), ("v5p", 459e12), ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5", 459e12), ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
)


def _peak_flops(device_kind: str):
    kind = device_kind.lower()
    for tag, peak in _PEAK_BF16_FLOPS:
        if tag in kind:
            return peak
    raise ValueError(f"no peak FLOP/s on record for device kind "
                     f"{device_kind!r}: add it to _PEAK_BF16_FLOPS with "
                     "its source")


def bench_transformer_mfu(devs) -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh, shard_batch

    from deeplearning4j_tpu.optimize import profiling

    # MXU-filling config (VERDICT r2 weak #2): d_model=2048, 8 blocks,
    # seq=512, bf16 operands everywhere, dense attention (measured faster
    # than the Pallas flash path below S~2048 — see nn/layers/attention.py).
    # MFU-campaign hot paths ON: sparse int labels (no [B*S, V] one-hot
    # gemm), fused flat-buffer updater, causal block-skip for any flash
    # dispatch — each bitwise-f32-identical to the path it replaces
    # (tests/test_mfu_paths.py).
    vocab, d_model, blocks, heads, seq = ((64, 64, 1, 4, 32) if SMALL else
                                          (256, 2048, 8, 16, 512))
    batch, warmup, steps = ((2 * len(devs), 1, 2) if SMALL
                            else (32 * len(devs), 2, 20))
    mesh = make_mesh({"dp": len(devs)})
    conf = _mixed(char_transformer(vocab, d_model=d_model, n_blocks=blocks,
                                   n_heads=heads, max_seq_len=seq,
                                   sparse_labels=True, fused_updater=True,
                                   attention_block_skip=True,
                                   attention_fused_bwd=True))
    net = MultiLayerNetwork(conf, seed=0).init()
    trainer = DataParallelTrainer(net, mesh, mode="sync")

    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq + 1))
    x = jnp.asarray(ids[:, :-1], jnp.int32)
    y = jnp.asarray(ids[:, 1:].reshape(batch * seq), jnp.int32)
    x, y = shard_batch(mesh, (x, y), "dp")

    # AOT-compile ONCE; the same executable serves warmup, the timed loop
    # and cost_analysis (r3 re-lowered + re-compiled the d2048xL8 step a
    # second time just to read the FLOP count — minutes of wasted budget)
    key = jax.random.PRNGKey(0)
    tc = time.perf_counter()
    compiled = trainer._step.lower(trainer.state, x, y, key).compile()
    compile_s = time.perf_counter() - tc
    for _ in range(warmup):
        trainer.state, _ = compiled(trainer.state, x, y, key)
    _host_sync(trainer.state.params)

    # optional op-level timeline (Perfetto-loadable); TPU only
    trace_dir = os.environ.get("DL4J_BENCH_TRACE_DIR")
    t0 = time.perf_counter()
    with profiling.maybe_trace(trace_dir):
        for _ in range(steps):
            trainer.state, _ = compiled(trainer.state, x, y, key)
        _host_sync(trainer.state.params)
    dt_step = (time.perf_counter() - t0) / steps

    # analytic train FLOPs: 6*P*tokens for matmul params + attention
    # scores/values (12*S^2*d per token per block, fwd+bwd)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(trainer.state.params))
    tokens = batch * seq
    flops = 6.0 * n_params * tokens + 12.0 * blocks * tokens * seq * d_model
    # per-op cost accounting (optimize/profiling.py): analytic category
    # split cross-checked against XLA's own executable totals; the
    # breakdown rides the metric line so every artifact shows WHERE the
    # step spends, not just the headline utilization
    totals = profiling.compiled_totals(compiled)
    # at this config auto dispatches dense attention (scores fit HBM), so
    # the backward is XLA autodiff -> "dense" accounting; the fused-bwd
    # flag stays on so any flash dispatch (longer S, smaller HBM) takes
    # the fused kernels — bench_attention_fused_bwd times that path
    costs = profiling.transformer_step_costs(
        batch=batch, seq=seq, d_model=d_model, n_blocks=blocks, vocab=vocab,
        n_params=n_params, dtype_bytes=2, sparse_labels=True,
        attention_bwd_mode="dense")
    op_breakdown = profiling.breakdown(costs, totals, step_seconds=dt_step)
    # satellite cross-check: the analytic attention-bwd flops vs XLA's own
    # executable total — rides the metric line so a chip run can spot an
    # accounting drift without re-deriving anything
    attention_bwd_check = {
        "analytic_flops": costs["attention_bwd"].flops,
        "measured_total_flops": totals["flops"] if totals else None,
        "share_of_measured": (round(
            costs["attention_bwd"].flops / totals["flops"], 4)
            if totals and totals["flops"] else None),
    }
    if totals is not None:
        # XLA counts fwd+bwd of the compiled program directly (no remat
        # here, so the compiled-program count is the model count)
        flops = totals["flops"]

    achieved = flops / dt_step
    if SMALL:
        # tiny test shapes: a utilization would mean nothing
        _emit("charTransformer train FLOPs/sec", achieved, "FLOP/s", None,
              tokens_per_sec=round(tokens / dt_step, 1),
              compile_seconds=round(compile_s, 1),
              op_breakdown=op_breakdown,
              attention_bwd_check=attention_bwd_check)
        return
    peak = _peak_flops(devs[0].device_kind)
    mfu = achieved / (peak * len(devs))
    _emit("charTransformer train MFU", mfu, "fraction of peak", None,
          achieved_tflops=round(achieved / 1e12, 2),
          peak_tflops_per_chip=round(peak / 1e12, 1),
          tokens_per_sec=round(tokens / dt_step, 1),
          compile_seconds=round(compile_s, 1),
          op_breakdown=op_breakdown,
          attention_bwd_check=attention_bwd_check,
          config=f"d{d_model}xL{blocks}xS{seq}xB{batch} bf16 "
                 "sparse-labels fused-updater block-skip fused-bwd")


# ---------------------------------------------------------------------------
# attention — fused-bwd kernels + measured auto-crossover (MFU round 2)
# ---------------------------------------------------------------------------

def _timed_calls(fn, args, reps: int) -> float:
    """Steady-state seconds/call: one compile+warm call, then a timed loop
    closed by a host read (same honesty fence as every other bench)."""
    _host_sync(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*args)
    _host_sync(out)
    return (time.perf_counter() - t0) / reps


def bench_attention_fused_bwd(devs) -> None:
    """Fused flash backward vs the jax-level recompute VJP it replaces.

    Two levels: (1) raw kernel microbench — flash fwd alone, grad with
    `fused_bwd=True` (delta + dK/dV + dQ Pallas kernels) and with
    `fused_bwd=False` (blockwise recompute VJP); (2) a charTransformer
    train step through the compiled step cache with `attention_impl`
    pinned to flash, fused on vs off.  vs_baseline on both lines is
    recompute_time / fused_time (>1 = fused faster) — the acceptance gate
    is that the fused path is no slower.  The analytic attention-bwd
    flops for both modes ride along, showing the recompute term
    (4 extra S*d flops per token per block) eliminated.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nd.pallas_kernels import (flash_attention,
                                                      pick_attention_blocks)
    from deeplearning4j_tpu.nd.platform import is_tpu
    from deeplearning4j_tpu.optimize import profiling

    B, S, H, D = (2, 64, 2, 8) if SMALL else (4, 1024, 8, 64)
    reps = 2 if SMALL else 10
    rng = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
                  for _ in range(4))
    bq, bk = pick_attention_blocks(S, D)
    # on the CPU fallback, pin interpret so the FUSED kernels are what
    # gets timed (auto-detect would take the jax-level fallback there and
    # this arm would time recompute vs recompute)
    interp = None if is_tpu() else True

    def make_grad(fused):
        def loss(q, k, v):
            o = flash_attention(q, k, v, True, bq, bk, interpret=interp,
                                block_skip=True, fused_bwd=fused)
            return jnp.sum(o * g)

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, bq, bk,
                                                  interpret=interp,
                                                  block_skip=True))
    fwd_s = _timed_calls(fwd, (q, k, v), reps)
    fused_s = _timed_calls(make_grad(True), (q, k, v), reps)
    recomp_s = _timed_calls(make_grad(False), (q, k, v), reps)
    _emit("attention fused-bwd kernel grad", fused_s * 1e3, "ms",
          recomp_s / max(fused_s, 1e-12),
          fwd_ms=round(fwd_s * 1e3, 3),
          recompute_bwd_ms=round(recomp_s * 1e3, 3),
          shape=f"B{B}xS{S}xH{H}xD{D} causal block-skip",
          blocks_fwd=[bq, bk],
          blocks_bwd=list(pick_attention_blocks(S, D, bwd=True)),
          interpret=bool(interp),
          baseline_note="vs_baseline = recompute-bwd / fused-bwd grad "
                        "time (>1 = fused faster); interpret=true means "
                        "emulated kernels on the CPU fallback — only the "
                        "TPU number scores the fused path")

    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    vocab, d_model, blocks, heads, seq, batch = (
        (32, 32, 1, 2, 32, 4) if SMALL else (64, 128, 2, 4, 128, 8))
    steps = 2 if SMALL else 10
    ids = rng.randint(0, vocab, (batch, seq + 1))
    x = jnp.asarray(ids[:, :-1], jnp.int32)
    y = jnp.asarray(ids[:, 1:].reshape(batch * seq), jnp.int32)

    def build(fused):
        conf = char_transformer(vocab, d_model=d_model, n_blocks=blocks,
                                n_heads=heads, max_seq_len=seq,
                                sparse_labels=True,
                                attention_block_skip=True,
                                attention_fused_bwd=fused)
        # pin flash so the fused-vs-recompute bwd is what gets timed
        # (auto never picks flash at these shapes, by design)
        conf = conf.replace(confs=tuple(c.replace(attention_impl="flash")
                                        for c in conf.confs))
        net = MultiLayerNetwork(conf, seed=0).init()
        net.finetune(x, y)  # compile once through the step cache
        _host_sync(net.params)
        return net

    def steady(net):
        t0 = time.perf_counter()
        for _ in range(steps):
            net.finetune(x, y)
        _host_sync(net.params)
        return (time.perf_counter() - t0) / steps

    # compile both before timing either; interleave rounds and keep the
    # min so drift/ordering can't masquerade as a kernel difference
    net_fused, net_recomp = build(True), build(False)
    fused_step = min(steady(net_fused), steady(net_fused))
    recomp_step = min(steady(net_recomp), steady(net_recomp))
    fused_step = min(fused_step, steady(net_fused))
    recomp_step = min(recomp_step, steady(net_recomp))
    n_params_proxy = d_model * d_model * 12 * blocks + d_model * vocab
    mode_flops = {
        mode: profiling.transformer_step_costs(
            batch=batch, seq=seq, d_model=d_model, n_blocks=blocks,
            vocab=vocab, n_params=n_params_proxy, sparse_labels=True,
            attention_bwd_mode=mode)["attention_bwd"].flops
        for mode in ("fused", "recompute")}
    _emit("attention fused-bwd train step", fused_step * 1e3, "ms/step",
          recomp_step / max(fused_step, 1e-12),
          recompute_ms_per_step=round(recomp_step * 1e3, 2),
          config=f"d{d_model}xL{blocks}xS{seq}xB{batch} flash block-skip",
          attention_bwd_flops_fused=mode_flops["fused"],
          attention_bwd_flops_recompute=mode_flops["recompute"],
          baseline_note="vs_baseline = recompute-bwd / fused-bwd step "
                        "time (>1 = fused faster); flops extras show the "
                        "recompute term the fused path eliminates. On the "
                        "CPU fallback both arms take the jax-level VJP "
                        "(fused kernels are TPU-gated) so ~1.0 is "
                        "expected there — only the TPU number scores the "
                        "fused step")


def bench_attention_crossover(devs) -> None:
    """Measured `attention_impl="auto"` crossover: full vs flash, forward
    and gradient, over an S sweep — the data the analytic score-bytes
    bound in nn/layers/attention.py (8 GiB, halved per flash-side
    improvement) gets checked against on the next chip run.  Metric value
    is the first swept S where flash wins the gradient; 0 = full won the
    whole sweep (crossover beyond it)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nd.attention import full_attention
    from deeplearning4j_tpu.nd.pallas_kernels import (flash_attention,
                                                      pick_attention_blocks)

    B, H, D = (1, 2, 8) if SMALL else (2, 8, 64)
    seqs = (32, 64) if SMALL else (256, 512, 1024)
    reps = 2 if SMALL else 8
    rng = np.random.RandomState(0)
    rows = []
    crossover_fwd = crossover_grad = 0
    for S in seqs:
        q, k, v, g = (jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
                      for _ in range(4))
        bq, bk = pick_attention_blocks(S, D)

        def flash_f(q, k, v, bq=bq, bk=bk):
            return flash_attention(q, k, v, True, bq, bk, block_skip=True,
                                   fused_bwd=True)

        def full_f(q, k, v):
            return full_attention(q, k, v, causal=True)

        def grad_of(fn, g=g):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) * g),
                argnums=(0, 1, 2)))

        t_full_fwd = _timed_calls(jax.jit(full_f), (q, k, v), reps)
        t_flash_fwd = _timed_calls(jax.jit(flash_f), (q, k, v), reps)
        t_full_grad = _timed_calls(grad_of(full_f), (q, k, v), reps)
        t_flash_grad = _timed_calls(grad_of(flash_f), (q, k, v), reps)
        rows.append({"seq": S,
                     "full_fwd_ms": round(t_full_fwd * 1e3, 3),
                     "flash_fwd_ms": round(t_flash_fwd * 1e3, 3),
                     "full_grad_ms": round(t_full_grad * 1e3, 3),
                     "flash_grad_ms": round(t_flash_grad * 1e3, 3),
                     "scores_bytes": 4 * B * H * S * S})
        if not crossover_fwd and t_flash_fwd < t_full_fwd:
            crossover_fwd = S
        if not crossover_grad and t_flash_grad < t_full_grad:
            crossover_grad = S
    _emit("attention auto-crossover sweep", crossover_grad, "seq", None,
          crossover_fwd_seq=crossover_fwd,
          sweep=rows, shape=f"B{B}xH{H}xD{D} causal fused-bwd block-skip",
          analytic_bound_bytes=2 << 30,  # block-skip + fused-bwd halvings
          baseline_note="value = first swept S where flash grad wins "
                        "(0 = full won the sweep); checks the auto bound "
                        "in nn/layers/attention.py against data")


# ---------------------------------------------------------------------------
# step cache — steady-state single-chip fit() throughput, compile excluded
# ---------------------------------------------------------------------------

def bench_step_cache(devs) -> None:
    """Single-chip `MultiLayerNetwork.fit` through the compiled train-step
    cache (optimize/step_cache.py): the warm-up batch pays the one compile,
    the timed loop is pure cache hits, so samples/sec is steady-state
    execution with compile time excluded.  The cache's compile-seconds
    total goes out as its own metric line so the perf trajectory tracks
    compile overhead separately from throughput."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    batch, warmup, batches = (32, 1, 4) if SMALL else (1024, 2, 30)
    conf = mlp(784, [512, 512], 10)
    net = MultiLayerNetwork(conf, seed=0).init()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 784), jnp.float32)
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)])

    tw = time.perf_counter()
    for _ in range(warmup):  # first fit compiles; the rest prove the hits
        net.fit(x, y)
    _host_sync(net.params)
    warm_s = time.perf_counter() - tw

    t0 = time.perf_counter()
    for _ in range(batches):
        net.fit(x, y)
    _host_sync(net.params)
    dt = time.perf_counter() - t0

    st = net.step_cache.stats
    _emit("step-cache steady-state fit samples/sec", batches * batch / dt,
          "samples/sec", None,
          cache_hits=st.hits, cache_misses=st.misses,
          solver_iterations_per_fit=conf.conf(conf.n_layers - 1).num_iterations,
          warmup_seconds=round(warm_s, 1))
    _emit("step-cache compile seconds total", st.total_compile_seconds,
          "seconds", None, entries=len(st.compile_seconds),
          baseline_note="one-time cost; steady-state line above excludes it")


# ---------------------------------------------------------------------------
# infer cache — steady-state serve-path output() latency, compile excluded
# ---------------------------------------------------------------------------

def bench_infer_latency(devs) -> None:
    """Single-chip `MultiLayerNetwork.output` through the serve-path AOT
    cache (optimize/infer_cache.py): the warm-up call pays the one compile,
    then every timed call is a cache hit on the same executable.  Reports
    p50 per-call latency and steady-state throughput, plus the cache's
    compile-seconds total as its own line (mirrors bench_step_cache)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    batch, warmup, calls = (32, 2, 8) if SMALL else (1024, 4, 60)
    conf = mlp(784, [512, 512], 10)
    net = MultiLayerNetwork(conf, seed=0).init()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 784), jnp.float32)

    tw = time.perf_counter()
    for _ in range(warmup):  # first call compiles; the rest prove the hits
        _host_sync(net.output(x))
    warm_s = time.perf_counter() - tw

    lat = []
    for _ in range(calls):
        t0 = time.perf_counter()
        _host_sync(net.output(x))
        lat.append(time.perf_counter() - t0)
    p50_ms = float(np.percentile(lat, 50)) * 1e3

    st = net.infer_cache.stats
    _emit("infer-cache steady-state output p50 latency", p50_ms, "ms/call",
          None, batch=batch,
          samples_per_sec=round(calls * batch / sum(lat), 1),
          cache_hits=st.hits, cache_misses=st.misses,
          warmup_seconds=round(warm_s, 1))
    _emit("infer-cache compile seconds total", st.total_compile_seconds,
          "seconds", None, entries=len(st.compile_seconds),
          baseline_note="one-time cost; p50 line above excludes it")


# ---------------------------------------------------------------------------
# serve — closed-loop concurrent clients through the micro-batching gateway
# ---------------------------------------------------------------------------

def bench_serve(devs) -> None:
    """Closed-loop concurrent clients against the micro-batching gateway
    (serving/batcher.py): each client loops `predict(1 row)` and issues
    the next request only after the previous answer lands.  Batching ON
    coalesces the fleet into one bucketed infer-cache call per flush;
    batching OFF is the same fleet calling `net.output` directly (one
    device program dispatch per request — the pre-gateway serving path).
    Headline = the batched/unbatched rows/s multiple; p99 per-request
    latency goes out for both arms."""
    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import MicroBatcher

    clients, secs, hidden = (8, 1.0, [64]) if SMALL else (32, 6.0, [512, 512])
    conf = mlp(784, hidden, 10)
    net = MultiLayerNetwork(conf, seed=0).init()
    rng = np.random.RandomState(0)
    xs = [rng.rand(1, 784).astype(np.float32) for _ in range(clients)]
    # warm the coalesced bucket AND the single-row bucket so neither arm
    # pays a compile inside its timed window
    net.warmup([clients, 1])

    def closed_loop(predict_fn):
        from deeplearning4j_tpu.reliability import DeadlineExceeded

        lat = [[] for _ in range(clients)]
        rows = [0] * clients
        misses = [0] * clients
        errors = [0] * clients
        start_evt = threading.Event()
        stop_t = [0.0]

        def client(i):
            start_evt.wait()
            while time.perf_counter() < stop_t[0]:
                t0 = time.perf_counter()
                try:
                    predict_fn(xs[i])
                    lat[i].append(time.perf_counter() - t0)
                    rows[i] += 1
                except DeadlineExceeded:  # before TimeoutError: subclass
                    misses[i] += 1
                except Exception:
                    errors[i] += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        t_begin = time.perf_counter()
        stop_t[0] = t_begin + secs
        start_evt.set()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t_begin
        all_lat = sorted(v for per in lat for v in per)
        p99 = all_lat[min(len(all_lat) - 1,
                          int(0.99 * (len(all_lat) - 1)))] if all_lat else 0.0
        total = max(sum(rows) + sum(misses) + sum(errors), 1)
        return (sum(rows) / dt, p99 * 1e3,
                sum(misses) / total, sum(errors) / total)

    # batching OFF first (its numbers are the baseline of the headline)
    off_rows_s, off_p99_ms, off_miss_rate, off_err_rate = closed_loop(
        lambda x: np.asarray(net.output(x)))

    misses_before = net.infer_cache.stats.misses  # warmup's prepaid compiles
    batcher = MicroBatcher(net, max_delay_ms=2.0).start()
    on_rows_s, on_p99_ms, on_miss_rate, on_err_rate = closed_loop(
        lambda x: batcher.predict(x, timeout=60.0, deadline_ms=1000.0))
    st = batcher.stats()
    batcher.stop()

    multiple = on_rows_s / max(off_rows_s, 1e-9)
    _emit("serve gateway batched rows/sec", on_rows_s, "rows/sec", multiple,
          clients=clients,
          rows_per_sec_unbatched=round(off_rows_s, 1),
          p99_ms_batched=round(on_p99_ms, 2),
          p99_ms_unbatched=round(off_p99_ms, 2),
          deadline_miss_rate_batched=round(on_miss_rate, 4),
          error_rate_batched=round(on_err_rate, 4),
          error_rate_unbatched=round(off_err_rate + off_miss_rate, 4),
          mean_batch_rows=round(st["rows"] / max(
              sum(st["batch_rows_hist"].values()), 1), 2),
          fresh_compiles_during_serving=st["fresh_compiles"] - misses_before,
          baseline_note=f"vs_baseline = rows/s multiple vs batching OFF, "
                        f"same {clients} closed-loop clients")


# ---------------------------------------------------------------------------
# serve precision — the same closed loop under each f32/bf16/int8 policy
# ---------------------------------------------------------------------------

def bench_serve_precision(devs) -> None:
    """Closed-loop clients through the micro-batching gateway under each
    serve-precision policy (optimize/quantize.py) on the charTransformer:
    f32 is the baseline arm, then bf16 and int8 rerun the SAME client
    fleet on the same bucket.  The policy is part of the infer-cache
    key, so each arm's programs are warmed before its timed window and
    `fresh_compiles_during_serving` must stay 0 — the low-precision path
    never pays a compile at traffic time.  Every arm emits its own line
    with rows/s, p50/p99, and the accuracy delta `set_serve_precision`
    measured against f32 on a held-out batch; vs_baseline on the
    bf16/int8 lines is the rows/s multiple over the f32 arm.  On CPU
    XLA emulates bf16 in float32, so the multiple only means something
    on an accelerator — every line names its platform."""
    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import MicroBatcher

    if SMALL:
        clients, secs, vocab, seq = 4, 0.6, 32, 16
        conf = char_transformer(vocab, d_model=16, n_blocks=1, n_heads=2,
                                max_seq_len=seq)
    else:
        clients, secs, vocab, seq = 16, 4.0, 96, 64
        conf = char_transformer(vocab, d_model=128, n_blocks=2, n_heads=4,
                                max_seq_len=seq)
    net = MultiLayerNetwork(conf, seed=0).init()
    rng = np.random.RandomState(0)
    xs = [rng.randint(0, vocab, size=(1, seq)).astype(np.int32)
          for _ in range(clients)]

    def closed_loop(batcher):
        lat = []
        rows = [0] * clients
        lock = threading.Lock()
        start_evt = threading.Event()
        stop_t = [0.0]

        def client(i):
            start_evt.wait()
            while time.perf_counter() < stop_t[0]:
                t0 = time.perf_counter()
                try:
                    batcher.predict(xs[i], timeout=60.0, deadline_ms=2000.0)
                except Exception:
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    lat.append(dt)
                rows[i] += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        t_begin = time.perf_counter()
        stop_t[0] = t_begin + secs
        start_evt.set()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t_begin

        def pct(q):
            vals = sorted(lat)
            if not vals:
                return 0.0
            return vals[min(len(vals) - 1, int(q * (len(vals) - 1)))] * 1e3

        return sum(rows) / dt, pct(0.50), pct(0.99)

    f32_rows_s = None
    for policy in ("f32", "bf16", "int8"):
        report = net.set_serve_precision(policy)
        # warm the coalesced bucket AND the single-row bucket under THIS
        # policy (the policy is a cache-key dimension) so the timed
        # window is pure hits
        net.warmup([np.zeros((clients, seq), np.int32),
                    np.zeros((1, seq), np.int32)])
        misses_before = net.infer_cache.stats.misses
        batcher = MicroBatcher(net, max_delay_ms=2.0).start()
        rows_s, p50_ms, p99_ms = closed_loop(batcher)
        st = batcher.stats()
        batcher.stop()
        if policy == "f32":
            f32_rows_s = rows_s
        delta = (report or {}).get("accuracy_delta") or {}
        _emit(f"serve precision {policy} rows/sec", rows_s, "rows/sec",
              None if policy == "f32" else rows_s / max(f32_rows_s, 1e-9),
              clients=clients, seq_len=seq,
              p50_ms=round(p50_ms, 2), p99_ms=round(p99_ms, 2),
              top1_delta_vs_f32=delta.get("top1_delta"),
              rel_mse_vs_f32=delta.get("rel_mse"),
              fresh_compiles_during_serving=(
                  st["fresh_compiles"] - misses_before),
              baseline_note="vs_baseline = rows/s multiple vs the f32 arm, "
                            "same closed-loop clients and bucket")


# ---------------------------------------------------------------------------
# serve router — closed-loop HTTP clients across {1, 2} replica processes
# ---------------------------------------------------------------------------

def bench_serve_router(devs) -> None:
    """Closed-loop HTTP clients against the multi-replica router
    (serving/router.py): replica subprocesses share one pre-warmed disk
    compile cache, the router spreads /v1/predict across them, and the
    client fleet is split between "interactive" and "batch" priority
    classes.  Headline = 2-replica rows/s; vs_baseline = the 2-replica /
    1-replica throughput multiple (per-priority p50/p99 go out for the
    2-replica arm).  CPU-bound by design: the bench measures the fabric
    (routing, coalescing, priorities), not the chip."""
    import json as json_mod
    import shutil
    import signal
    import subprocess
    import tempfile
    import urllib.request

    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import checkpoint

    clients, secs, hidden = (4, 1.0, [32]) if SMALL else (16, 4.0, [256])
    n_in = 64
    tmp = tempfile.mkdtemp(prefix="dl4j-bench-router-")
    try:
        net = MultiLayerNetwork(mlp(n_in, hidden, 10), seed=0).init()
        ckpt = os.path.join(tmp, "model")
        cache = os.path.join(tmp, "cache")
        checkpoint.save(ckpt, net.params, conf=net.conf)
        # host-only: children forced to CPU
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        shapes = f"1,{clients}"
        subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu.cli", "warmup",
             "--model", ckpt, "--compile-cache", cache, "--shapes", shapes],
            check=True, capture_output=True, env=env)
        rng = np.random.RandomState(0)
        xs = [rng.rand(1, n_in).astype(np.float32).tolist()
              for _ in range(clients)]

        def closed_loop(url):
            lat = {"interactive": [], "batch": []}
            counts = {"rows": 0, "errors": 0}
            start_evt = threading.Event()
            stop_t = [0.0]
            lock = threading.Lock()

            def client(i):
                prio = "interactive" if i % 2 == 0 else "batch"
                body = json_mod.dumps(
                    {"features": xs[i], "priority": prio}).encode()
                start_evt.wait()
                while time.perf_counter() < stop_t[0]:
                    t0 = time.perf_counter()
                    try:
                        req = urllib.request.Request(
                            url + "/v1/predict", data=body,
                            headers={"Content-Type": "application/json"})
                        with urllib.request.urlopen(req, timeout=30) as r:
                            r.read()
                        dt = time.perf_counter() - t0
                        with lock:
                            lat[prio].append(dt)
                            counts["rows"] += 1
                    except Exception:
                        with lock:
                            counts["errors"] += 1

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            t_begin = time.perf_counter()
            stop_t[0] = t_begin + secs
            start_evt.set()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t_begin

            def pct(vals, q):
                vals = sorted(vals)
                if not vals:
                    return 0.0
                return vals[min(len(vals) - 1,
                                int(q * (len(vals) - 1)))] * 1e3

            return (counts["rows"] / dt, counts["errors"], {
                p: {"p50_ms": round(pct(v, 0.50), 2),
                    "p99_ms": round(pct(v, 0.99), 2)}
                for p, v in lat.items()})

        results = {}
        for n_replicas in (1, 2):
            proc = subprocess.Popen(
                [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve",
                 "--model", ckpt, "--compile-cache", cache,
                 "--shapes", shapes, "--replicas", str(n_replicas),
                 "--max-delay-ms", "2", "--drain-timeout", "10"],
                stdout=subprocess.PIPE, text=True, env=env)
            try:
                summary = json_mod.loads(proc.stdout.readline())
                results[n_replicas] = closed_loop(summary["url"]) + (
                    summary["fresh_compiles"],)
            finally:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.communicate(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.communicate()

        one_rows_s = results[1][0]
        two_rows_s, two_errors, two_lat, two_fresh = results[2]
        _emit("serve router 2-replica rows/sec", two_rows_s, "rows/sec",
              two_rows_s / max(one_rows_s, 1e-9),
              clients=clients,
              rows_per_sec_1replica=round(one_rows_s, 1),
              errors_2replica=two_errors,
              latency_interactive=two_lat["interactive"],
              latency_batch=two_lat["batch"],
              fresh_compiles_per_replica=two_fresh,
              baseline_note="vs_baseline = rows/s multiple vs a 1-replica "
                            "router, same closed-loop client fleet, shared "
                            "warmed disk compile cache", platform="cpu")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# fleet SLO — open-loop Poisson load vs a supervised fleet, + kill-and-heal
# ---------------------------------------------------------------------------

def bench_fleet_slo(devs) -> None:
    """Max sustained rows/s under a fixed p99 SLO, measured OPEN-LOOP
    (Poisson arrivals, heavy-tailed row mix): closed-loop clients slow
    down with the server and hide queueing collapse, an open-loop
    generator keeps offering load and exposes it (the TPU paper's
    datacenter framing — the fleet is judged at its latency bound, not
    its best case).  Arms: 1 vs 2 supervised replicas climbing a rate
    ladder, then a kill-and-heal timeline — SIGKILL one of 2 replicas
    mid-window and report error count, heal time (supervisor respawn to
    healthy fleet), and fresh compiles on the respawned replica (0 =
    the shared disk cache made the restart seconds, not compiles).
    CPU-bound by design: it measures the fabric, not the chip."""
    import json as json_mod
    import random as random_mod
    import shutil
    import subprocess
    import tempfile
    import urllib.request

    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import checkpoint

    if SMALL:
        hidden, level_s, rates = [32], 1.0, (20.0, 50.0)
        heal_s, heal_rate, heal_wait_s = 6.0, 10.0, 20.0
    else:
        hidden, level_s, rates = [256], 3.0, (25.0, 50.0, 100.0, 200.0)
        heal_s, heal_rate, heal_wait_s = 12.0, 25.0, 45.0
    slo_p99_ms = 250.0
    n_in = 64
    #: heavy-tailed row mix: mostly single rows, a tail of coalescable
    #: bursts — every size pre-warmed so the fleet never compiles
    row_mix = (1, 1, 1, 1, 1, 1, 2, 2, 4, 8)
    tmp = tempfile.mkdtemp(prefix="dl4j-bench-fleet-")
    try:
        net = MultiLayerNetwork(mlp(n_in, hidden, 10), seed=0).init()
        ckpt = os.path.join(tmp, "model")
        cache = os.path.join(tmp, "cache")
        checkpoint.save(ckpt, net.params, conf=net.conf)
        # host-only: children forced to CPU
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        shapes = "1,2,4,8"
        subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu.cli", "warmup",
             "--model", ckpt, "--compile-cache", cache, "--shapes", shapes],
            check=True, capture_output=True, env=env)
        rng = np.random.RandomState(0)
        bodies = {
            rows: json_mod.dumps(
                {"features": rng.rand(rows, n_in).astype(
                    np.float32).tolist()}).encode()
            for rows in sorted(set(row_mix))}

        def open_loop(url, rate_rps, duration_s, seed=0, ramp=1.0,
                      detail=None):
            """Poisson arrivals at `rate_rps` for `duration_s`; every
            arrival fires regardless of how the fleet is doing (that is
            the open-loop point).  When `ramp` > 1 the arrival rate
            climbs linearly to ramp*rate_rps over the run (the diurnal
            arm), and a `detail` dict gets per-segment timelines so the
            caller can find the highest offered rate the fleet sustained
            inside the SLO.  Returns (rows/s completed, p99 ms, errors,
            offered requests)."""
            arr_rng = random_mod.Random(seed)
            lock = threading.Lock()
            lat, rows_done, errors, offered = [], [0], [0], [0]
            done = []  # (t_done_rel_s, latency_s, nrows)
            threads = []
            t_begin = time.perf_counter()

            def one(body, nrows):
                t0 = time.perf_counter()
                try:
                    req = urllib.request.Request(
                        url + "/v1/predict", data=body,
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=30) as r:
                        r.read()
                    dt = time.perf_counter() - t0
                    with lock:
                        lat.append(dt)
                        rows_done[0] += nrows
                        done.append((time.perf_counter() - t_begin,
                                     dt, nrows))
                except Exception:
                    with lock:
                        errors[0] += 1

            t_next = t_begin
            deadline = t_begin + duration_s
            while t_next < deadline:
                now = time.perf_counter()
                if now < t_next:
                    time.sleep(t_next - now)
                nrows = arr_rng.choice(row_mix)
                t = threading.Thread(target=one,
                                     args=(bodies[nrows], nrows))
                t.start()
                threads.append(t)
                offered[0] += 1
                frac = min(max((t_next - t_begin) / duration_s, 0.0), 1.0)
                t_next += arr_rng.expovariate(
                    rate_rps * (1.0 + (ramp - 1.0) * frac))
            for t in threads:
                t.join(timeout=35.0)
            dt = time.perf_counter() - t_begin
            if detail is not None:
                n_seg = 4
                seg_len = duration_s / n_seg
                segs = []
                for i in range(n_seg):
                    lo = i * seg_len
                    hi = (i + 1) * seg_len if i < n_seg - 1 else float("inf")
                    ds = [(d, r) for t_d, d, r in done if lo <= t_d < hi]
                    vals = sorted(d for d, _ in ds)
                    p99 = (vals[min(len(vals) - 1,
                                    int(0.99 * (len(vals) - 1)))] * 1e3
                           if vals else None)
                    segs.append({
                        "t_s": [round(lo, 2),
                                round(min((i + 1) * seg_len, duration_s),
                                      2)],
                        "offered_rps": round(
                            rate_rps * (1.0 + (ramp - 1.0)
                                        * (i + 0.5) / n_seg), 1),
                        "rows_per_sec": round(
                            sum(r for _, r in ds) / seg_len, 1),
                        "p99_ms": (round(p99, 2) if p99 is not None
                                   else None),
                    })
                detail["segments"] = segs

            def pct(q):
                vals = sorted(lat)
                if not vals:
                    return float("inf")
                return vals[min(len(vals) - 1,
                                int(q * (len(vals) - 1)))] * 1e3

            return rows_done[0] / dt, pct(0.99), errors[0], offered[0]

        def start_fleet(n, extra=()):
            proc = subprocess.Popen(
                [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve",
                 "--model", ckpt, "--compile-cache", cache,
                 "--shapes", shapes, "--replicas", str(n),
                 "--max-delay-ms", "2", "--drain-timeout", "10",
                 *extra],
                stdout=subprocess.PIPE, text=True, env=env)
            return proc, json_mod.loads(proc.stdout.readline())

        def stop_fleet(proc):
            proc.send_signal(signal.SIGTERM)
            try:
                proc.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.communicate()

        # -- arm 1: the rate ladder, 1 vs 2 replicas ------------------------
        sustained = {}
        for n_replicas in (1, 2):
            proc, summary = start_fleet(n_replicas)
            best = {"rows_s": 0.0, "rate": 0.0, "p99_ms": None}
            try:
                for rate in rates:
                    rows_s, p99_ms, errors, offered = open_loop(
                        summary["url"], rate, level_s, seed=int(rate))
                    if p99_ms <= slo_p99_ms and errors == 0:
                        best = {"rows_s": rows_s, "rate": rate,
                                "p99_ms": round(p99_ms, 2)}
                    else:
                        break  # the ladder found the knee; stop offering
            finally:
                stop_fleet(proc)
            sustained[n_replicas] = best
        _emit("fleet SLO-sustained rows/sec (2 replicas)",
              sustained[2]["rows_s"], "rows/sec",
              sustained[2]["rows_s"] / max(sustained[1]["rows_s"], 1e-9),
              slo_p99_ms=slo_p99_ms,
              sustained_1replica=sustained[1],
              sustained_2replica=sustained[2],
              open_loop="poisson", row_mix=list(row_mix),
              baseline_note="vs_baseline = 2-replica / 1-replica max "
                            "open-loop rows/s with p99 under the SLO and "
                            "zero errors, same Poisson generator",
              platform="cpu")

        # -- arm 2: kill-and-heal timeline ----------------------------------
        proc, summary = start_fleet(
            2, extra=("--min-replicas", "2", "--max-replicas", "2"))
        try:
            url = summary["url"]
            victim_pid = summary["replica_pids"][0]
            result = {}

            def load_then_report():
                result["load"] = open_loop(url, heal_rate, heal_s, seed=7)

            loader = threading.Thread(target=load_then_report)
            loader.start()
            time.sleep(heal_s * 0.25)  # mid-window, load in flight
            t_kill = time.perf_counter()
            os.kill(victim_pid, signal.SIGKILL)
            healed_at = None
            fresh_after = None
            while time.perf_counter() - t_kill < heal_wait_s:
                try:
                    with urllib.request.urlopen(url + "/v1/stats",
                                                timeout=5) as r:
                        st = json_mod.loads(r.read())
                except Exception:
                    time.sleep(0.2)
                    continue
                fleet = st.get("fleet", {})
                if (st.get("healthy_replicas", 0) >= 2
                        and fleet.get("restarts_total", 0) >= 1):
                    healed_at = time.perf_counter() - t_kill
                    fresh_after = [s.get("fresh_compiles")
                                   for s in fleet.get("slots", [])]
                    break
                time.sleep(0.2)
            loader.join()
            rows_s, p99_ms, errors, offered = result["load"]
            _emit("fleet kill-and-heal time", healed_at or heal_wait_s,
                  "sec", None,
                  healed=healed_at is not None,
                  errors_during_heal=errors,
                  offered_requests=offered,
                  rows_per_sec_during=round(rows_s, 1),
                  p99_ms_during=round(p99_ms, 2),
                  fresh_compiles_after_heal=fresh_after,
                  baseline_note="SIGKILL one of 2 replicas under open-loop "
                                "load; time until the supervisor restored "
                                "a 2-healthy fleet (fresh_compiles 0 = "
                                "warm-cache respawn)", platform="cpu")
        finally:
            stop_fleet(proc)

        # -- arm 3: diurnal ramp, 1 host vs 2 simulated agent hosts ---------
        # the arrival rate doubles over the run (the diurnal morning).
        # Both fleets start at 1 replica with the autoscaler allowed to
        # grow to 2; the 2-host arm places replicas through two local
        # ReplicaAgent processes (simulated hosts), so a scale-up crosses
        # the agent control plane and warms from the cachesync wire.
        if SMALL:
            ramp_s, ramp_rate = 8.0, 10.0
        else:
            ramp_s, ramp_rate = 20.0, 20.0

        def start_agent():
            p = subprocess.Popen(
                [sys.executable, "-m", "deeplearning4j_tpu.cli", "agent",
                 "--port", "0", "--compile-cache", cache,
                 "--max-replicas", "2"],
                stdout=subprocess.PIPE, text=True, env=env)
            return p, json_mod.loads(p.stdout.readline())["url"]

        def stop_agent(p):
            p.send_signal(signal.SIGTERM)
            try:
                p.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.communicate()

        diurnal = {}
        for label, n_agents in (("1_host", 0), ("2_agent_hosts", 2)):
            agent_procs = []
            extra = ["--min-replicas", "1", "--max-replicas", "2",
                     "--slo-p99-ms", str(slo_p99_ms / 5.0)]
            for _ in range(n_agents):
                p, u = start_agent()
                agent_procs.append(p)
                extra += ["--agent", u]
            proc, summary = start_fleet(1, extra=tuple(extra))
            timeline = []
            stop_poll = threading.Event()

            def poll_timeline(url=summary["url"], timeline=timeline):
                t0 = time.perf_counter()
                last_n = None
                while not stop_poll.wait(0.5):
                    try:
                        with urllib.request.urlopen(url + "/v1/stats",
                                                    timeout=5) as r:
                            st = json_mod.loads(r.read())
                    except Exception:
                        continue
                    n = st.get("healthy_replicas", 0)
                    if n != last_n:
                        timeline.append({
                            "t_s": round(time.perf_counter() - t0, 1),
                            "healthy_replicas": n,
                            "decisions": (st.get("autoscaler") or {})
                                .get("decisions", {})})
                        last_n = n
            poller = threading.Thread(target=poll_timeline)
            poller.start()
            detail = {}
            try:
                rows_s, p99_ms, errors, offered = open_loop(
                    summary["url"], ramp_rate, ramp_s, seed=11,
                    ramp=2.0, detail=detail)
            finally:
                stop_poll.set()
                poller.join()
                stop_fleet(proc)
                for p in agent_procs:
                    stop_agent(p)
            inside = [s for s in detail.get("segments", [])
                      if s["p99_ms"] is not None
                      and s["p99_ms"] <= slo_p99_ms]
            best = max(inside, key=lambda s: s["rows_per_sec"],
                       default=None)
            diurnal[label] = {
                "sustained_rows_per_sec": (best or {}).get("rows_per_sec",
                                                           0.0),
                "sustained_offered_rps": (best or {}).get("offered_rps"),
                "overall_rows_per_sec": round(rows_s, 1),
                "overall_p99_ms": round(p99_ms, 2),
                "errors": errors,
                "offered_requests": offered,
                "zero_drop": errors == 0,
                "segments": detail.get("segments", []),
                "scale_events": timeline,
            }
        _emit("fleet diurnal-ramp sustained rows/sec (2 agent hosts)",
              diurnal["2_agent_hosts"]["sustained_rows_per_sec"],
              "rows/sec",
              diurnal["2_agent_hosts"]["sustained_rows_per_sec"]
              / max(diurnal["1_host"]["sustained_rows_per_sec"], 1e-9),
              slo_p99_ms=slo_p99_ms, ramp="2x over the run",
              open_loop="poisson", row_mix=list(row_mix),
              diurnal_1_host=diurnal["1_host"],
              diurnal_2_agent_hosts=diurnal["2_agent_hosts"],
              baseline_note="vs_baseline = 2-agent-host / 1-host best "
                            "ramp segment rows/s with p99 under the SLO; "
                            "scale_events shows autoscaler decisions and "
                            "healthy-replica transitions (zero_drop = no "
                            "request errored across the whole ramp)",
              platform="cpu")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# prefetch — LeNet mini-batch fit with the async device_put pipeline on/off
# ---------------------------------------------------------------------------

def bench_prefetch(devs) -> None:
    """LeNet train epoch over host-resident mini-batches, with and without
    the async host->device prefetch pipeline (datasets/iterator.py
    PrefetchIterator).  Both passes run after a compile warm-up epoch, so
    the delta isolates the input feed: transfer overlapped with compute
    vs transfer serialized before each step."""
    import jax.numpy as jnp  # noqa: F401 — backend init before timing

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import PrefetchIterator
    from deeplearning4j_tpu.models.zoo import lenet5
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    batch, n_batches = (32, 3) if SMALL else (1024, 12)
    conf = _mixed(lenet5())
    net = MultiLayerNetwork(conf, seed=0).init()
    rng = np.random.RandomState(0)
    eye = np.eye(10, dtype=np.float32)
    batches = [DataSet(rng.rand(batch, 784).astype(np.float32),
                       eye[rng.randint(0, 10, batch)])
               for _ in range(n_batches)]

    tw = time.perf_counter()
    net.fit(batches)  # warm-up epoch: pays the one solver compile
    _host_sync(net.params)
    warm_s = time.perf_counter() - tw

    t0 = time.perf_counter()
    net.fit(batches)  # host-synchronous feed: device_put blocks each step
    _host_sync(net.params)
    plain_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    net.fit(PrefetchIterator(batches))  # transfer one batch ahead
    _host_sync(net.params)
    prefetch_s = time.perf_counter() - t0

    n = n_batches * batch
    _emit("prefetch LeNet train samples/sec", n / prefetch_s, "samples/sec",
          None, samples_per_sec_no_prefetch=round(n / plain_s, 1),
          speedup_vs_no_prefetch=round(plain_s / prefetch_s, 3),
          warmup_seconds=round(warm_s, 1),
          baseline_note="vs same loop without the async device_put pipeline")


# ---------------------------------------------------------------------------
# north_star — LeNet-MNIST and the 4-layer char-LSTM end-to-end FROM THE CLI
# ---------------------------------------------------------------------------

def bench_north_star_cli(devs) -> None:
    """BASELINE north_star: both flagship models trained via cli/driver.py.

    The reference's `cli/subcommands/Train.java:55-57` exec() is an empty
    stub; here the CLI really trains on the chip and logs its own
    throughput + final score, which this bench re-emits as metric lines.
    Numbers are END-TO-END (data load + XLA compile + train + eval), the
    honest 'user types one command' cost — lower than steady-state.
    """
    import contextlib
    import io
    import tempfile

    from deeplearning4j_tpu.cli.driver import main as cli_main

    def run(argv):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli_main(argv)
        if rc:
            raise RuntimeError(f"CLI rc={rc} for {argv}")
        return json.loads(out.getvalue().strip().splitlines()[-1])

    with tempfile.TemporaryDirectory() as td:
        n, batch, epochs = (256, 64, 1) if SMALL else (8192, 1024, 2)
        info = run(["train", "--input", f"mnist:{n}", "--zoo", "lenet5",
                    "--runtime", "mesh", "--output", f"{td}/lenet",
                    "--normalize",
                    "--properties", f"epochs={epochs},batch={batch}"])
        _emit("north-star CLI LeNet-MNIST samples/sec", info["examples_per_sec"],
              "samples/sec", info["examples_per_sec"] / 500.0,
              final_score=round(info["score"], 4),
              train_seconds=info["train_seconds"],
              compile_seconds=info.get("compile_seconds"),
              baseline_note="one CLI command, end-to-end incl. compile; "
                            "assumed 500 samples/sec 2015 CPU-jblas")

        # 4-layer char-LSTM over a real text file through the text: scheme
        seq = 16 if SMALL else 32
        chars = 2_000 if SMALL else 65_536
        rng = np.random.RandomState(0)
        words = ["the", "quick", "brown", "fox", "jumps", "over", "lazy",
                 "dogs", "and", "cats", "read", "write", "code", "tpu"]
        corpus = " ".join(rng.choice(words) for _ in range(chars // 5))
        with open(f"{td}/corpus.txt", "w") as f:
            f.write(corpus[:chars])
        # local runtime: char-LM labels are [B*T, V] which the mesh
        # runtime's row-wise batching doesn't slice; on the one real
        # chip local == mesh throughput anyway
        info = run(["train", "--input", f"text:{td}/corpus.txt:{seq}",
                    "--zoo", "char_lstm:layers=4,hidden=128",
                    "--output", f"{td}/lstm4",
                    "--properties", "epochs=1"])
        chars_per_sec = info["examples_per_sec"] * seq
        _emit("north-star CLI charLSTM-4layer chars/sec", chars_per_sec,
              "chars/sec", chars_per_sec / 1500.0,
              final_score=round(info["score"], 4),
              train_seconds=info["train_seconds"],
              compile_seconds=info.get("compile_seconds"),
              baseline_note="one CLI command, end-to-end incl. compile; "
                            "assumed 1500 chars/sec 2015 CPU BPTT x4 layers")


# ---------------------------------------------------------------------------
# cold_start — first-step latency: cold vs warm-disk vs warm-memory cache
# ---------------------------------------------------------------------------

def bench_cold_start(devs) -> None:
    """First train step + first `output()` with a cold, warm-disk, and
    warm-memory compile cache (optimize/persist.py).  Cold pays the full
    trace+lower+compile; warm-disk is what a RESTARTED process pointed at
    a populated --compile-cache dir pays (deserialize + AOT-compile of the
    stored StableHLO — no trace); warm-memory is the steady-state hit."""
    import tempfile

    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    batch, hidden = (32, [64]) if SMALL else (1024, [512, 512])
    conf = mlp(784, hidden, 10)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 784), jnp.float32)
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)])

    with tempfile.TemporaryDirectory() as td:
        # cold: empty store — trace + compile + write-back
        net = MultiLayerNetwork(conf, seed=0).init()
        net.set_compile_cache(td)
        t0 = time.perf_counter()
        net.fit(x, y)
        _host_sync(net.params)
        cold_fit_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        _host_sync(net.output(x))
        cold_out_s = time.perf_counter() - t0

        # warm-memory: same process, same cache — pure hit
        t0 = time.perf_counter()
        net.fit(x, y)
        _host_sync(net.params)
        mem_fit_s = time.perf_counter() - t0

        # warm-disk: fresh net (empty memory cache) on the populated dir —
        # the restarted-process path
        net2 = MultiLayerNetwork(conf, seed=0).init()
        net2.set_compile_cache(td)
        t0 = time.perf_counter()
        net2.fit(x, y)
        _host_sync(net2.params)
        disk_fit_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        _host_sync(net2.output(x))
        disk_out_s = time.perf_counter() - t0
        st = net2.step_cache.stats
        it = net2.infer_cache.stats

    cold_s, disk_s = cold_fit_s + cold_out_s, disk_fit_s + disk_out_s
    _emit("cold-start first fit+output seconds", cold_s, "seconds", None,
          warm_disk_seconds=round(disk_s, 3),
          warm_memory_step_seconds=round(mem_fit_s, 4),
          speedup_disk_vs_cold=round(cold_s / max(disk_s, 1e-9), 2),
          disk_hits=st.disk_hits + it.disk_hits,
          fresh_compiles=st.misses + it.misses,
          deserialize_seconds=round(
              st.deserialize_seconds + it.deserialize_seconds, 3),
          baseline_note="warm-disk = restarted process on a populated "
                        "--compile-cache dir; trace+lower skipped")


def bench_generate(devs) -> None:
    """Autoregressive generation: continuous batching (freed decode
    slots refilled every step) vs sequential batching (admissions wait
    for the WHOLE table to drain — the barrier on the longest sequence).
    Same model, same compiled decode/prefill programs, same
    deterministic open-loop arrival schedule with mixed prompt/output
    lengths; reports tokens/sec and TTFT p50/p99 per arm.  CPU-bound by
    design: it measures the serving loop around the compiled step, not
    the chip."""
    import random as random_mod

    from deeplearning4j_tpu.models.zoo import char_lstm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

    # arrival rate deliberately outpaces decode capacity: a backlogged
    # queue is where the sequential barrier's idle slots cost real
    # throughput (an arrival-limited run hides it — both arms just
    # keep up)
    if SMALL:
        n_requests, rate_rps, slots, max_seq = 16, 400.0, 4, 32
    else:
        n_requests, rate_rps, slots, max_seq = 64, 400.0, 8, 64
    vocab = 24
    net = MultiLayerNetwork(char_lstm(vocab, hidden=32, n_layers=1),
                            seed=0).init()
    # both arms replay the same programs: zero compiles inside the
    # measured window
    net.warmup_generate(slots=slots, max_seq=max_seq, prompt_buckets=(8,))

    # one deterministic schedule both arms replay: Poisson arrivals,
    # prompts of 2-6 tokens, outputs of 4-16 tokens
    arr = random_mod.Random(0)
    schedule = []
    t_at = 0.0
    for _ in range(n_requests):
        prompt = [arr.randrange(1, vocab)
                  for _ in range(arr.randrange(2, 7))]
        schedule.append((t_at, prompt, arr.randrange(4, 17)))
        t_at += arr.expovariate(rate_rps)

    def run_arm(continuous: bool):
        cb = ContinuousBatcher(net, n_slots=slots, max_seq=max_seq,
                               prompt_buckets=(8,),
                               max_pending=n_requests + 1,
                               continuous=continuous)
        lock = threading.Lock()
        done: list = []

        def consume(stream):
            try:
                toks = list(stream.tokens(timeout=120.0))
            except Exception:
                toks = []
            with lock:
                done.append((len(toks), stream.ttft_s))

        threads = []
        t_begin = time.perf_counter()
        try:
            for at, prompt, n_new in schedule:
                now = time.perf_counter() - t_begin
                if now < at:
                    time.sleep(at - now)
                s = cb.submit(prompt, max_new_tokens=n_new)
                th = threading.Thread(target=consume, args=(s,))
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=150.0)
            dt = time.perf_counter() - t_begin
        finally:
            cb.stop()
        tokens = sum(n for n, _ in done)
        ttfts = sorted(t for _, t in done if t is not None)

        def pct(q):
            if not ttfts:
                return float("inf")
            return ttfts[min(len(ttfts) - 1,
                             int(q * (len(ttfts) - 1)))] * 1e3

        return tokens / max(dt, 1e-9), pct(0.5), pct(0.99), tokens

    seq_tps, seq_p50, seq_p99, seq_tokens = run_arm(False)
    cont_tps, cont_p50, cont_p99, cont_tokens = run_arm(True)
    _emit("generate sequential tokens/sec", seq_tps, "tokens/sec", None,
          ttft_p50_ms=round(seq_p50, 2), ttft_p99_ms=round(seq_p99, 2),
          tokens=seq_tokens, requests=n_requests, slots=slots,
          baseline_note="admission barrier: the slot table drains to "
                        "empty before the next batch admits")
    _emit("generate continuous tokens/sec", cont_tps, "tokens/sec",
          cont_tps / max(seq_tps, 1e-9),
          ttft_p50_ms=round(cont_p50, 2), ttft_p99_ms=round(cont_p99, 2),
          tokens=cont_tokens, requests=n_requests, slots=slots,
          baseline_note="vs_baseline = continuous / sequential tokens/sec "
                        "on the identical arrival schedule")

    # fused multi-step dispatch: K decode steps per host round-trip,
    # measured on a slot-stable table (every slot admitted up front, no
    # arrivals mid-run — the regime where the adaptive ramp reaches
    # K_max).  The K=1 arm is the classic step-at-a-time loop; the K
    # arm amortises the host-side dispatch/readback over K tokens, so
    # on CPU — where the host loop, not the chip, dominates each step —
    # tokens/sec must come out strictly above K=1.
    net.warmup_generate(slots=slots, max_seq=max_seq, prompt_buckets=(8,),
                        steps_per_dispatch=8)  # lint: allow(hardcoded-tunable)

    def run_fused(steps):
        cb = ContinuousBatcher(net, n_slots=slots, max_seq=max_seq,
                               prompt_buckets=(8,),
                               max_pending=slots + 1,
                               steps_per_dispatch=steps)
        gen = random_mod.Random(1)
        n_new = max_seq - 8
        prompts = [[gen.randrange(1, vocab) for _ in range(4)]
                   for _ in range(slots)]
        t_begin = time.perf_counter()
        try:
            streams = [cb.submit(p, max_new_tokens=n_new)
                       for p in prompts]
            toks = [list(s.tokens(timeout=150.0)) for s in streams]
            dt = time.perf_counter() - t_begin
            st = cb.stats()
        finally:
            cb.stop()
        tokens = sum(len(t) for t in toks)
        ttfts = sorted(s.ttft_s for s in streams
                       if s.ttft_s is not None)
        p99 = (ttfts[min(len(ttfts) - 1, int(0.99 * (len(ttfts) - 1)))]
               * 1e3 if ttfts else float("inf"))
        return (tokens / max(dt, 1e-9), p99,
                st.get("host_overhead_fraction", 0.0), tokens)

    k1_tps, k1_p99, k1_hof, k1_tokens = run_fused(1)
    k8_tps, k8_p99, k8_hof, k8_tokens = run_fused(8)
    _emit("generate fused K=1 tokens/sec", k1_tps, "tokens/sec", None,
          ttft_p99_ms=round(k1_p99, 2),
          host_overhead_fraction=round(k1_hof, 4),
          tokens=k1_tokens, slots=slots, steps_per_dispatch=1,
          baseline_note="one host dispatch + readback per token")
    _emit("generate fused K=8 tokens/sec", k8_tps, "tokens/sec",
          k8_tps / max(k1_tps, 1e-9),
          ttft_p99_ms=round(k8_p99, 2),
          host_overhead_fraction=round(k8_hof, 4),
          tokens=k8_tokens, slots=slots, steps_per_dispatch=8,
          baseline_note="vs_baseline = fused K=8 / K=1 tokens/sec on "
                        "identical slot-stable work; token trajectories "
                        "are identical by construction")


def bench_generate_accel(devs) -> None:
    """The three ISSUE-16 decode accelerators, each against its own
    off-switch on identical work: (a) paged KV vs dense slabs under the
    SAME KV token budget — the paged pool admits more concurrent streams
    because short streams only hold the pages they touched; (b) prefix
    cache on vs off on a repeated long prompt — a hit skips the prefill
    program entirely, so TTFT collapses; (c) speculative decoding on vs
    off with a draft finetuned alongside the target on a cyclic corpus —
    agreeing drafts land > 1 accepted token per verify step.  All three
    arms are greedy and token-parity-checked in tests/test_generate.py;
    here we only measure.  CPU-bound by design, like bench_generate."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import char_lstm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

    # ---- (a) paged vs dense under one KV token budget --------------------
    vocab, hidden = 24, 32
    slots_dense, max_seq, page_size = (2, 16, 4) if SMALL else (4, 32, 4)
    budget_tokens = slots_dense * max_seq          # what dense reserves
    n_pages = budget_tokens // page_size           # same budget, paged
    slots_paged = slots_dense * 2                  # overcommit the table
    n_streams = 8 if SMALL else 16
    out_lo, out_hi = 4, max(5, max_seq // 4)       # short streams: the
    # overcommit case — nobody ever grows near max_seq, so dense slabs
    # reserve ~4x what the workload touches

    net = MultiLayerNetwork(char_lstm(vocab, hidden=hidden, n_layers=1),
                            seed=0).init()
    net.warmup_generate(slots=slots_dense, max_seq=max_seq,
                        prompt_buckets=(8,))
    net.warmup_generate(slots=slots_paged, max_seq=max_seq,
                        prompt_buckets=(8,), page_size=page_size,
                        n_pages=n_pages)

    arr = np.random.RandomState(0)
    prompts = [[int(t) for t in arr.randint(1, vocab, arr.randint(2, 7))]
               for _ in range(n_streams)]
    n_new = [int(arr.randint(out_lo, out_hi + 1)) for _ in range(n_streams)]

    def run_pool(paged: bool):
        cb = ContinuousBatcher(
            net, n_slots=slots_paged if paged else slots_dense,
            max_seq=max_seq, prompt_buckets=(8,),
            max_pending=n_streams + 1,
            page_size=page_size if paged else 0,
            n_pages=n_pages if paged else 0)
        peak = {"active": 0, "live_tokens": 0}
        stop_poll = threading.Event()

        def poll():
            while not stop_poll.is_set():
                st = cb.stats()
                sts = st["streams"]
                active = (sts["admitted"] - sts["completed"]
                          - sts["failed"])
                peak["active"] = max(peak["active"], active)
                kv = st.get("kv_pages")
                if kv:
                    peak["live_tokens"] = max(peak["live_tokens"],
                                              kv["live_tokens"])
                time.sleep(0.002)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        t0 = time.perf_counter()
        try:
            streams = [cb.submit(p, max_new_tokens=k)
                       for p, k in zip(prompts, n_new)]
            toks = sum(len(list(s.tokens(timeout=120.0)))
                       for s in streams)
            dt = time.perf_counter() - t0
        finally:
            stop_poll.set()
            poller.join(timeout=5.0)
            cb.stop()
        # dense slabs hold max_seq tokens per occupied slot whether the
        # stream uses them or not; the paged pool only holds live pages
        reserved = (peak["live_tokens"] if paged
                    else peak["active"] * max_seq)
        return toks / max(dt, 1e-9), peak["active"], reserved

    dense_tps, dense_peak, dense_tokens = run_pool(False)
    paged_tps, paged_peak, paged_tokens = run_pool(True)
    _emit("generate paged-KV admitted slots (same budget)", paged_peak,
          "slots", paged_peak / max(dense_peak, 1),
          dense_peak_slots=dense_peak,
          kv_budget_tokens=budget_tokens, page_size=page_size,
          dense_peak_reserved_tokens=dense_tokens,
          paged_peak_live_tokens=paged_tokens,
          paged_tokens_per_sec=round(paged_tps, 1),
          dense_tokens_per_sec=round(dense_tps, 1),
          baseline_note="same KV token budget; paged overcommits the "
                        "slot table and short streams only pin the "
                        "pages they touched")

    # ---- (b) prefix cache on/off: repeated long prompt TTFT --------------
    # a model where prefill actually costs something: the hit skips that
    # whole program, so the deeper the net and the longer the prompt, the
    # wider the gap (the hit pays only the admission + first-step floor)
    bucket = 128 if SMALL else 256
    long_prompt = [int(t) for t in arr.randint(1, vocab, bucket - 16)]
    reps = 6 if SMALL else 10
    pnet = MultiLayerNetwork(char_lstm(vocab, hidden=192, n_layers=2),
                             seed=0).init()
    pnet.warmup_generate(slots=2, max_seq=bucket + 16,
                         prompt_buckets=(bucket,), prefix_cache=True)

    def run_prefix(on: bool):
        cb = ContinuousBatcher(pnet, n_slots=2, max_seq=bucket + 16,
                               prompt_buckets=(bucket,),
                               prefix_cache=on)
        ttfts = []
        try:
            for _ in range(reps):
                stream = cb.submit(long_prompt, max_new_tokens=2)
                list(stream.tokens(timeout=60.0))
                ttfts.append(stream.ttft_s * 1e3)
        finally:
            cb.stop()
        # with the cache on, request 0 is the one cold miss that fills
        # it; every later identical prompt is a hit
        hits = sorted(ttfts[1:]) if on else sorted(ttfts)

        def pct(q):
            return hits[min(len(hits) - 1, int(q * (len(hits) - 1)))]

        return pct(0.5), pct(0.99)

    cold_p50, cold_p99 = run_prefix(False)
    hit_p50, hit_p99 = run_prefix(True)
    _emit("generate prefix-cache hit TTFT p99 ms", hit_p99, "ms",
          cold_p99 / max(hit_p99, 1e-9),
          hit_ttft_p50_ms=round(hit_p50, 3),
          cold_ttft_p50_ms=round(cold_p50, 3),
          cold_ttft_p99_ms=round(cold_p99, 3),
          prompt_tokens=len(long_prompt), requests=reps,
          baseline_note="vs_baseline = cold p99 / hit p99 on the "
                        "identical repeated prompt; a hit skips the "
                        "prefill program")

    # ---- (c) speculative decoding on/off ---------------------------------
    # finetune target AND draft on the same cyclic corpus so the greedy
    # draft actually agrees with the greedy target — acceptance is what
    # buys throughput, and it has to be earned, not faked with a clone
    cyc_vocab, cycle = 9, [1, 2, 3, 4, 5, 6, 7, 8]
    seq, batch_n, steps = (8, 8, 60) if SMALL else (8, 16, 150)
    stream_ids = [cycle[i % len(cycle)]
                  for i in range(batch_n * (seq + 1) + len(cycle))]

    def cyclic_batch(off):
        rows_x, rows_y = [], []
        for b in range(batch_n):
            start = (off + b) % len(cycle)
            window = stream_ids[start:start + seq + 1]
            rows_x.append(np.eye(cyc_vocab, dtype=np.float32)[window[:-1]])
            rows_y.append(np.eye(cyc_vocab, dtype=np.float32)[window[1:]])
        x = jnp.asarray(np.stack(rows_x))
        y = jnp.asarray(np.concatenate(rows_y))
        return x, y

    target = MultiLayerNetwork(char_lstm(cyc_vocab, hidden=32, n_layers=1),
                               seed=0).init()
    draft = MultiLayerNetwork(char_lstm(cyc_vocab, hidden=16, n_layers=1),
                              seed=1).init()
    for i in range(steps):
        x, y = cyclic_batch(i)
        target.fit(x, y)
        draft.fit(x, y)
    _host_sync(target.params)

    spec_k = 4
    gen_seq, gen_new, gen_streams = 48, 32, 4 if SMALL else 8
    target.warmup_generate(slots=2, max_seq=gen_seq, prompt_buckets=(8,))
    target.warmup_generate(slots=2, max_seq=gen_seq, prompt_buckets=(8,),
                           draft_net=draft, spec_k=spec_k)

    def run_spec(on: bool):
        cb = ContinuousBatcher(target, n_slots=2, max_seq=gen_seq,
                               prompt_buckets=(8,),
                               max_pending=gen_streams + 1,
                               draft_net=draft if on else None,
                               spec_k=spec_k if on else 0)
        t0 = time.perf_counter()
        try:
            streams = [cb.submit(cycle[:4], max_new_tokens=gen_new)
                       for _ in range(gen_streams)]
            outs = [list(s.tokens(timeout=120.0)) for s in streams]
            dt = time.perf_counter() - t0
            st = cb.stats()
        finally:
            cb.stop()
        toks = sum(len(o) for o in outs)
        acc = (st.get("speculative") or {}).get("accepted_per_step", 0.0)
        return toks / max(dt, 1e-9), acc, outs

    plain_tps, _, plain_out = run_spec(False)
    spec_tps, accepted, spec_out = run_spec(True)
    assert spec_out == plain_out, "speculative greedy parity broke"
    _emit("generate speculative tokens/sec", spec_tps, "tokens/sec",
          spec_tps / max(plain_tps, 1e-9),
          plain_tokens_per_sec=round(plain_tps, 1),
          accepted_tokens_per_step=accepted, spec_k=spec_k,
          finetune_steps=steps,
          baseline_note="vs_baseline = speculative / plain tokens/sec, "
                        "identical greedy trajectories; draft finetuned "
                        "on the same cyclic corpus as the target")


# ---------------------------------------------------------------------------
# tp_serve — 1-D (replicated params) vs 2-D tensor-parallel serving
# ---------------------------------------------------------------------------

_TP_SERVE_CHILD = r"""
import json, time
import numpy as np
import jax
from deeplearning4j_tpu.models.zoo import char_transformer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

SMALL = %(small)r
if SMALL:
    vocab, d_model, blocks, heads, seq = 32, 32, 2, 4, 32
    rows, iters, n_new, slots = 16, 3, 8, 2
else:
    vocab, d_model, blocks, heads, seq = 64, 128, 2, 8, 64
    rows, iters, n_new, slots = 64, 10, 24, 4
conf = char_transformer(vocab, d_model=d_model, n_blocks=blocks,
                        n_heads=heads, max_seq_len=seq)
out = {"devices": jax.device_count()}
for tag, spec in (("1d", "batch=8"), ("2d", "batch=2,model=4")):
    net = MultiLayerNetwork(conf, seed=0).init()
    net.set_serve_mesh(spec=spec)
    rng = np.random.RandomState(0)
    x = rng.randint(1, vocab, size=(rows, 16)).astype(np.int32)
    jax.block_until_ready(net.output(x))  # compile outside the window
    t0 = time.perf_counter()
    for _ in range(iters):
        y = net.output(x)
    jax.block_until_ready(y)
    serve_rps = rows * iters / (time.perf_counter() - t0)
    net.warmup_generate(slots=slots, max_seq=seq, prompt_buckets=(8,))
    cb = ContinuousBatcher(net, n_slots=slots, max_seq=seq,
                           prompt_buckets=(8,))
    try:
        t0 = time.perf_counter()
        streams = [cb.submit([1 + i, 2, 3], max_new_tokens=n_new)
                   for i in range(slots)]
        toks = [list(s.tokens(timeout=240.0)) for s in streams]
        dt = time.perf_counter() - t0
    finally:
        cb.stop()
    mem = {}
    for row in net.infer_cache.program_memory():
        e = row["entry"]
        if e in ("output", "decode") and e not in mem:
            mem[e] = {"per_device": row["per_device_argument_bytes"],
                      "replicated": row["replicated_argument_bytes"],
                      "analysis": row["memory_analysis"]}
    out[tag] = {"serve_rows_per_sec": serve_rps,
                "decode_tokens_per_sec": sum(map(len, toks))
                / max(dt, 1e-9),
                "tokens": sum(map(len, toks)), "mem": mem}
print("TPRESULT " + json.dumps(out), flush=True)
"""


def bench_tp_serve(devs) -> None:
    """Tensor-parallel serving (ISSUE 17): 1-D Mesh(('batch',)) with
    replicated params vs the 2-D ('batch','model') ShardPlan on the
    SAME transformer — serve rows/sec, decode tokens/sec, and the
    per-chip argument bytes `program_memory()` attributes to each plan
    (the pair that proves a model-sharded plan fits where a replicated
    one cannot).  Runs in a child forced to 8 host-CPU devices so the
    collectives are real regardless of what this process holds —
    every line is stamped platform "cpu" because those numbers are NOT
    accelerator numbers (collective cost on host CPU is a different
    regime; the memory split, however, is backend-independent)."""
    env = dict(os.environ)
    # host-only: children forced to CPU
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _TP_SERVE_CHILD % {"small": SMALL}],
        env=env, capture_output=True, text=True,
        timeout=PER_BENCH_BUDGET_S - 10)
    line = next((ln for ln in proc.stdout.splitlines()
                 if ln.startswith("TPRESULT ")), None)
    if line is None:
        raise RuntimeError(f"tp_serve child produced no result: "
                           f"{proc.stderr[-2000:]}")
    res = json.loads(line[len("TPRESULT "):])
    d1, d2 = res["1d"], res["2d"]
    note = ("8 forced host-CPU devices; vs_baseline = 2-D / 1-D on "
            "identical work (host-CPU collectives, NOT an accelerator "
            "number)")
    _emit("tp-serve 1-D rows/sec", d1["serve_rows_per_sec"], "rows/sec",
          None, platform="cpu", mesh="batch=8",
          baseline_note="1-D control arm: rows split, params replicated")
    _emit("tp-serve 2-D rows/sec", d2["serve_rows_per_sec"], "rows/sec",
          d2["serve_rows_per_sec"] / max(d1["serve_rows_per_sec"], 1e-9),
          platform="cpu", mesh="batch=2,model=4",
          baseline_note=note)
    _emit("tp-serve 1-D decode tokens/sec", d1["decode_tokens_per_sec"],
          "tokens/sec", None, platform="cpu", mesh="batch=8",
          tokens=d1["tokens"],
          baseline_note="1-D control arm: decode state replicated")
    _emit("tp-serve 2-D decode tokens/sec", d2["decode_tokens_per_sec"],
          "tokens/sec",
          d2["decode_tokens_per_sec"]
          / max(d1["decode_tokens_per_sec"], 1e-9),
          platform="cpu", mesh="batch=2,model=4",
          tokens=d2["tokens"], baseline_note=note)
    for entry in ("output", "decode"):
        m1 = d1["mem"].get(entry)
        m2 = d2["mem"].get(entry)
        if not (m1 and m2):
            continue
        _emit(f"tp-serve {entry} per-chip argument bytes",
              m2["per_device"], "bytes",
              m1["per_device"] / max(m2["per_device"], 1),
              platform="cpu", mesh="batch=2,model=4",
              replicated_bytes=m2["replicated"],
              one_d_per_device_bytes=m1["per_device"],
              memory_analysis=m2["analysis"],
              baseline_note="vs_baseline = 1-D per-chip bytes / 2-D "
                            "per-chip bytes (the model-axis shrink); "
                            "memory_analysis attached when the backend "
                            "exposes compiled.memory_analysis()")


def bench_tune(devs) -> None:
    """Search-based autotuning (ROADMAP 6): registry defaults vs the
    `tune` search's winning table on the SAME charTransformer — the
    attention microbench at the picked blocks, serve rows/sec through
    the infer cache, and decode tokens/sec through the compiled decode
    step.  The search's MIN_GAIN rule keeps ties on the defaults, so a
    tuned table is never slower than stock within noise; on CPU most
    groups tie (Pallas runs interpret mode, blocks don't differ) and
    the lines say platform "cpu".  Also reports the
    tuning wall-clock and the measured/pruned candidate counts."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nd.pallas_kernels import (flash_attention,
                                                      pick_attention_blocks)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize import tunables
    from deeplearning4j_tpu.optimize import tune as tune_mod

    vocab, seq = 24, (16 if SMALL else 32)
    d_model, n_heads = 32, 2
    net = MultiLayerNetwork(
        char_transformer(vocab, d_model=d_model, n_blocks=1,
                         n_heads=n_heads, max_seq_len=seq),
        seed=0).init()
    rng = np.random.default_rng(0)
    decode_steps = 8

    def timed(step):
        step()  # warm: compile outside the timed region
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            step()
            dt = time.perf_counter() - t0
            best = dt if best is None or dt < best else best
        return best

    def steady():
        """One measurement pass under whatever table is installed:
        every knob resolves through `tunables`, so the same code path
        is the default arm (no table) and the tuned arm (table)."""
        hd = d_model // n_heads
        bq, bk = pick_attention_blocks(seq, hd)
        q = np.asarray(rng.standard_normal((1, seq, 2, hd)), np.float32)
        t_attn = timed(lambda: jax.block_until_ready(
            flash_attention(q, q, q, True, bq, bk)))
        rows = int(tunables.resolve("batcher.target_rows"))
        batch = rng.integers(0, vocab, size=(rows, seq)).astype(np.int32)
        t_serve = timed(lambda: np.asarray(net.output(batch)))
        slots = int(tunables.resolve("decode.slots"))
        ic = net.infer_cache

        def dec():
            state = ic.init_decode_state(net.conf, slots, seq)
            tok = jnp.zeros((slots,), jnp.int32)
            pos = jnp.zeros((slots,), jnp.int32)
            keys = jnp.zeros((slots, 2), jnp.uint32)
            temps = jnp.zeros((slots,), jnp.float32)
            # decode donates its state buffers: thread the returned state
            for _ in range(decode_steps):
                tok, keys, state = ic.decode(net.conf, net.params, state,
                                             tok, pos, keys, temps)
                pos = pos + 1
            np.asarray(tok)

        t_dec = timed(dec)
        return {"attn_s": t_attn, "blocks": (bq, bk),
                "rows": rows, "rows_per_sec": rows / max(t_serve, 1e-9),
                "slots": slots,
                "tokens_per_sec": slots * decode_steps / max(t_dec, 1e-9)}

    tunables.clear()
    try:
        base = steady()
        t0 = time.perf_counter()
        report = tune_mod.tune_model(net, rounds=2 if SMALL else 3,
                                     seed=0, max_seq=seq)
        tune_s = time.perf_counter() - t0
        table = tunables.TunedTable(report["entries"],
                                    device_kind=tune_mod._device_kind(),
                                    fingerprint=report["fingerprint"])
        tunables.install(table, source="fresh")
        tuned = steady()
    finally:
        tunables.clear()

    note = ("vs_baseline = tuned / default on identical work; the "
            "search's 2% win margin keeps ties on the defaults, so "
            "tuned >= default within noise")
    _emit("tune attention step time", tuned["attn_s"] * 1e3, "ms",
          base["attn_s"] / max(tuned["attn_s"], 1e-12),
          default_ms=round(base["attn_s"] * 1e3, 4),
          blocks_default=list(base["blocks"]),
          blocks_tuned=list(tuned["blocks"]),
          baseline_note="vs_baseline = default / tuned step time "
                        "(speedup; 1.0 = table kept the defaults)")
    _emit("tune serve rows/sec", tuned["rows_per_sec"], "rows/sec",
          tuned["rows_per_sec"] / max(base["rows_per_sec"], 1e-9),
          default_rows_per_sec=round(base["rows_per_sec"], 4),
          target_rows_default=base["rows"], target_rows_tuned=tuned["rows"],
          baseline_note=note)
    _emit("tune decode tokens/sec", tuned["tokens_per_sec"], "tokens/sec",
          tuned["tokens_per_sec"] / max(base["tokens_per_sec"], 1e-9),
          default_tokens_per_sec=round(base["tokens_per_sec"], 4),
          slots_default=base["slots"], slots_tuned=tuned["slots"],
          baseline_note=note)
    _emit("tune search wall-clock", tune_s, "sec", None,
          candidates_measured=report["candidates_measured"],
          candidates_pruned=report["candidates_pruned"],
          measure_failures=report["measure_failures"],
          entries=len(report["entries"]),
          baseline_note="one full search over the attention/serve/decode "
                        "groups on the bench model")


# ---------------------------------------------------------------------------

BENCHES = [bench_lenet, bench_char_lstm, bench_vgg_cifar10, bench_word2vec,
           bench_dp_allreduce,
           bench_elastic_resume,
           bench_char_lstm4, bench_step_cache, bench_infer_latency,
           bench_serve, bench_serve_precision, bench_tp_serve,
           bench_serve_router,
           bench_fleet_slo, bench_generate, bench_generate_accel,
           bench_prefetch,
           bench_cold_start, bench_north_star_cli, bench_tune,
           bench_attention_fused_bwd, bench_attention_crossover,
           bench_transformer_mfu]


def main(benches=None) -> int:
    from deeplearning4j_tpu.nd import platform

    found = platform.default_platform()
    if found != "tpu" and not SMALL:
        print(f"bench: platform is {found!r}, not 'tpu'; refusing to "
              "measure (DL4J_BENCH_SMALL=1 runs the tiny CPU shapes for "
              "tests)", file=sys.stderr, flush=True)
        return 2
    devs = platform.devices()
    print(f"bench: {len(devs)} {found} device(s), "
          f"kind={devs[0].device_kind}", file=sys.stderr, flush=True)
    for b in BENCHES if benches is None else benches:
        t0 = time.perf_counter()
        try:
            b(devs)
        except Exception:  # noqa: BLE001 — boundary: report and stop
            import traceback

            traceback.print_exc()
            print(f"bench: {b.__name__} failed after "
                  f"{time.perf_counter() - t0:.1f}s; stopping",
                  file=sys.stderr, flush=True)
            return 1
        print(f"bench: {b.__name__} ok in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
