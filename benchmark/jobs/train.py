"""Training job: the window drives `DataParallelTrainer.fit(stream)` on a
`dp` mesh, the path of `cli train --runtime mesh`."""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import correct, families, program, reference, trace_reduce, traffic


class TokenStream:
    """The harness's iterator: batches from the seed, each hand-over
    stamped.  It stops at `limit` batches or at the deadline, and in a
    traced run it opens and closes the profiler around its own sub-window
    (the thread that runs `fit` is inside the program the whole time)."""

    def __init__(self, batches, clock=time.perf_counter):
        self.batches = batches
        self.clock = clock
        self.stamps = []
        self.limit = None
        self.deadline = None
        self.trace = None           # (dir, start at, seconds)
        self.trace_stamps = None    # hand-overs inside the traced window

    def __iter__(self):
        import jax

        given, window = 0, None
        try:
            while self.limit is None or given < self.limit:
                now = self.clock()
                if self.deadline is not None and now >= self.deadline:
                    return
                if self.trace and self.trace_stamps is None and now >= self.trace[1]:
                    jax.profiler.start_trace(self.trace[0],
                                             profiler_options=trace_reduce.quiet_profile())
                    window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
                    window.__enter__()
                    self.trace_stamps = [len(self.stamps), None]
                    now = self.clock()
                    self.trace = (self.trace[0], now, self.trace[2])
                if window is not None and now >= self.trace[1] + self.trace[2]:
                    window = _close(window, self)
                with jax.profiler.TraceAnnotation("bench:data"):
                    batch = next(self.batches)
                self.stamps.append(self.clock())
                given += 1
                yield batch
        finally:
            if window is not None:
                _close(window, self)


def _close(window, stream):
    import jax

    window.__exit__(None, None, None)
    jax.profiler.stop_trace()
    stream.trace_stamps[1] = len(stream.stamps)
    return None


class Waited:
    """Times what `fit` waits in `next()` of the iterator it is given."""

    def __init__(self, inner):
        self.inner = inner
        self.waited_s = 0.0

    def __iter__(self):
        it = iter(self.inner)
        while True:
            t = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                return
            finally:
                self.waited_s += time.perf_counter() - t
            yield item


def build(ctx):
    """The compiled step with its state, as the window will drive it."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    net = MultiLayerNetwork(families.of(ctx.cfg).program.build_conf(ctx.cfg),
                            seed=ctx.seed & 0x7FFFFFFF)
    net.params = program.program_weights(ctx.cfg, ctx.seed)
    return DataParallelTrainer(net, make_mesh({"dp": ctx.chips}), mode="sync")


def fit(trainer, stream):
    """The window's own call and feed."""
    from deeplearning4j_tpu.datasets.iterator import PrefetchIterator

    feed = Waited(PrefetchIterator(stream))
    loss = trainer.fit(feed)
    return loss, feed.waited_s


def host_sync(trainer) -> float:
    """The window closes on a host read that depends on the last state."""
    import jax
    import jax.numpy as jnp

    return float(jnp.sum(jax.tree_util.tree_leaves(trainer.state.params)[0]))


def first_steps(ctx, trainer, stream) -> dict:
    """Drive the first steps through `fit`, one batch a call, and read what
    `correct` compares from the state they leave."""
    out = {"losses": []}
    for step in range(int(ctx.mix["first_steps"])):
        stream.limit = 1
        loss, _ = fit(trainer, stream)
        out["losses"].append(float(loss))
        if step == 0:
            moment = program.norms_of(ctx.cfg, trainer.state.updater.velocity)
            scale = 1.0 - reference.ADAM["beta1"]    # m1 = (1 - beta1) g1
            out["grad_norms"] = [n / scale for n in moment]
            out["grad_projections"] = [[x / scale for x in n]
                                       for n in program.projections_of(
                ctx.cfg, ctx.seed, trainer.state.updater.velocity)]
    out["change_norms"] = program.change_norms(ctx.cfg, ctx.seed,
                                               trainer.state.params)
    stream.limit = None
    host_sync(trainer)
    return out


def run(ctx) -> dict:
    import jax

    mix = ctx.mix
    model = families.of(ctx.cfg).reference
    vocab = model.sizes(ctx.cfg)["vocab"]
    rows, seq = int(mix["rows"]) * ctx.chips, int(mix["seq"])
    mix = dict(mix, rows=rows)
    stream = TokenStream(traffic.token_batches(mix, vocab, ctx.seed))
    trainer = build(ctx)
    seen = first_steps(ctx, trainer, stream)
    misses = trainer.compile_cache.stats.misses
    warm = len(stream.stamps)

    t0 = time.perf_counter()
    stream.deadline = t0 + ctx.seconds
    if ctx.trace_dir:
        stream.trace = (ctx.trace_dir, t0 + ctx.seconds / 3.0,
                        float(mix["trace_seconds"]))
    with ctx.watch_compiles() as compiles:
        with jax.profiler.TraceAnnotation("bench:fit"):
            _, waited_s = fit(trainer, stream)
        with jax.profiler.TraceAnnotation("bench:sync"):
            host_sync(trainer)
    t1 = time.perf_counter()
    steps = len(stream.stamps) - warm
    stamps = np.asarray(stream.stamps[warm:])
    counters = {
        "steps": steps, "window_s": t1 - t0, "rows": rows, "seq": seq,
        "stamps_s": (stamps - t0).tolist(),
        "data_wait_s": waited_s,
        "program_compiles": trainer.compile_cache.stats.misses - misses,
        "xla_compiles": compiles["count"],
        "traced_steps": (None if stream.trace_stamps is None else
                         stream.trace_stamps[1] - stream.trace_stamps[0]),
    }
    peak = ctx.memory_peak()

    # the program's state goes before the reference comes
    trainer.net.params = None
    trainer.state = None
    del trainer
    gc.collect()
    batches = traffic.token_batches(mix, vocab, ctx.seed)
    firsts = [(x, y.reshape(rows, seq)) for x, y in
              (next(batches) for _ in range(len(seen["losses"])))]
    t_ref = time.perf_counter()
    ref = model.first_steps(ctx.cfg, ctx.seed, firsts)
    numbers = correct.train_numbers(seen, ref)
    counters["reference_s"] = time.perf_counter() - t_ref
    return {
        "t_first": t0,
        "end_to_end": {"train_tokens_per_s": steps * rows * seq / (t1 - t0)},
        "attempted": steps, "failed": 0,
        "counters": counters, "numbers": numbers,
        "memory_peak_bytes": peak,
    }


def readings(ctx, seeds, control_seeds) -> list:
    """For `calibrate`: what sound runs of the program read on each seed
    (the lower reading), and on `control_seeds` what the int8 control and
    the half-batch fault read, each put in the program's place against the
    float32 reference.  One trainer serves every seed: its state is set
    back to the seed's weights."""
    import types

    from deeplearning4j_tpu.parallel.data_parallel import init_train_state

    model = families.of(ctx.cfg).reference
    vocab = model.sizes(ctx.cfg)["vocab"]
    rows, seq = int(ctx.mix["rows"]) * ctx.chips, int(ctx.mix["seq"])
    mix = dict(ctx.mix, rows=rows)
    trainer, seen = None, {}
    for seed in seeds:
        one = types.SimpleNamespace(**{**vars(ctx), "seed": seed})
        if trainer is None:
            trainer = build(one)
        else:
            trainer.net.params = program.program_weights(ctx.cfg, seed)
            trainer.state = init_train_state(trainer.net)
        stream = TokenStream(traffic.token_batches(mix, vocab, seed))
        seen[seed] = first_steps(one, trainer, stream)
    trainer.net.params = trainer.state = None
    del trainer
    gc.collect()
    out = []
    for seed in seeds:
        batches = traffic.token_batches(mix, vocab, seed)
        firsts = [(x, y.reshape(rows, seq)) for x, y in
                  (next(batches) for _ in range(len(seen[seed]["losses"])))]
        ref = model.first_steps(ctx.cfg, seed, firsts)
        rec = {"seed": seed, "program": correct.train_numbers(seen[seed], ref),
               "leaves": {"grad": correct.leaf_gaps(
                   seen[seed]["grad_norms"], ref["grad_norms"]).tolist(),
                   "change": correct.leaf_gaps(
                   seen[seed]["change_norms"], ref["change_norms"]).tolist(),
                   "moving": correct.moving_leaves(ref["grad_norms"]).tolist()}}
        if seed in control_seeds:
            rec["control_int8"] = correct.train_numbers(
                model.first_steps(ctx.cfg, seed, firsts, precision="int8"), ref)
            rec["fault_half_batch"] = correct.train_numbers(
                model.first_steps(ctx.cfg, seed, firsts, rows=rows // 2), ref)
        out.append(rec)
    return out
