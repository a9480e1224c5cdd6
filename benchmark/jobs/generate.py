"""Generation job: the window drives `ContinuousBatcher.submit()` and
`GenerationStream.tokens()`, the engine entry that `POST /v1/generate`
hands to.  Closed loop (each caller sends its next request when the last
has finished) or open loop (requests sent when they are due, and timed
from then), as the traffic file says."""

from __future__ import annotations

import gc
import sys
import threading
import time

import numpy as np

from benchmark import families, program, trace_reduce, traffic

_TOKEN_TIMEOUT_S = 60.0     # an answer may come a minute late; later is never


class Sent:
    """One request as its caller saw it."""

    def __init__(self, request: dict, due: float):
        self.prompt = request["prompt"]
        self.max_new = request["max_new"]
        self.due = due              # when it was (to be) sent
        self.stamps = []            # arrival of every token at the caller
        self.tokens = []
        self.error = None
        self.done = False


class Load:
    """Callers in threads of their own, one request source, one clock."""

    def __init__(self, batcher, mix: dict, source):
        self.batcher = batcher
        self.mix = mix
        self.source = source
        self.lock = threading.Lock()
        self.sent = []
        self.stop_at = None
        self.threads = []
        self.next_due = None
        self.first_error = None

    def _take(self):
        """The next request and when it is due, or None once load has ended."""
        with self.lock:
            now = time.perf_counter()
            if self.stop_at is not None and now >= self.stop_at:
                return None
            request = next(self.source)
            if self.mix["arrival"]["loop"] == "open":
                self.next_due = (now if self.next_due is None
                                 else self.next_due + request["gap_s"])
                if self.stop_at is not None and self.next_due >= self.stop_at:
                    return None
                due = self.next_due
            else:
                due = now
            sent = Sent(request, due)
            self.sent.append(sent)
            return sent

    def _caller(self, start_at: float):
        time.sleep(max(0.0, start_at - time.perf_counter()))
        while True:
            sent = self._take()
            if sent is None:
                return
            time.sleep(max(0.0, sent.due - time.perf_counter()))
            try:
                stream = self.batcher.submit(
                    sent.prompt, max_new_tokens=sent.max_new,
                    temperature=float(self.mix["temperature"]))
                for tok in stream.tokens(timeout=_TOKEN_TIMEOUT_S):
                    sent.stamps.append(time.perf_counter())
                    sent.tokens.append(int(tok))
            except Exception as e:  # noqa: BLE001 — a failed request is counted
                sent.error = e
                with self.lock:
                    if self.first_error is None:
                        self.first_error = e
                        print(f"first failed request: {e!r}"[:2000],
                              file=sys.stderr, flush=True)
                time.sleep(0.05)    # a server that refuses is not hammered
            sent.done = True

    def start(self, t0: float):
        arrival = self.mix["arrival"]
        n = int(arrival["clients"])
        for i in range(n):
            th = threading.Thread(
                target=self._caller, daemon=True, name=f"bench-caller-{i}",
                args=(t0 + float(arrival.get("ramp_s", 0.0)) * i / n,))
            th.start()
            self.threads.append(th)

    def live(self) -> list:
        """The position of every row in flight, as the callers see it."""
        with self.lock:
            open_ = [s for s in self.sent if not s.done and s.stamps]
        return [len(s.prompt) + len(s.stamps) for s in open_]

    def join(self, timeout: float) -> bool:
        end = time.perf_counter() + timeout
        for th in self.threads:
            th.join(max(0.0, end - time.perf_counter()))
        return not any(th.is_alive() for th in self.threads)


def build(ctx):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

    srv = ctx.mix["server"]
    net = MultiLayerNetwork(families.of(ctx.cfg).program.build_conf(ctx.cfg),
                            seed=ctx.seed & 0x7FFFFFFF)
    net.params = program.program_weights(ctx.cfg, ctx.seed)
    net.warmup_generate(slots=int(srv["n_slots"]), max_seq=int(srv["max_seq"]),
                        prompt_buckets=tuple(srv["prompt_buckets"]))
    batcher = ContinuousBatcher(
        net, n_slots=int(srv["n_slots"]), max_seq=int(srv["max_seq"]),
        prompt_buckets=tuple(srv["prompt_buckets"]),
        max_pending=int(srv["max_pending"]))
    return net, batcher.start()


def traced_program_seconds(seen: dict, which: str):
    """For the per-layer readers: median device seconds of the decode step or
    of the prefill in the traced window.  A prefill ran once for every
    admission, the decode step once for every other call of the cache."""
    c, trace = seen["counters"], seen["trace"]
    if not trace or not c.get("traced_admitted"):
        return None
    runs = trace_reduce.program_runs(
        trace, c["traced_admitted"] if which == "prefill"
        else c["traced_calls"] - c["traced_admitted"])
    return float(np.median(runs)) if runs else None


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def served_gaps(cfg: dict, seed: int, sample: list, precisions=("f32",)) -> dict:
    """For each precision, how far tokens lie below the float32 reference's
    best logit, over every served position of `sample`: the widest gap, the
    mean gap, and the share of positions whose token is not the reference's
    first.  Under "f32" the tokens are the served ones; under another
    precision they are the tokens that precision puts first at the same
    positions (the control)."""
    import jax.numpy as jnp

    longest = max(len(s.prompt) + len(s.tokens) for s in sample)
    ids = np.zeros((len(sample), longest), np.int32)
    mask = np.zeros((len(sample), longest), bool)
    for r, s in enumerate(sample):
        n, k = len(s.prompt), len(s.tokens)
        ids[r, :n] = s.prompt
        ids[r, n:n + k] = s.tokens
        mask[r, n - 1:n + k - 1] = True       # logits that chose a served token
    logits = families.of(cfg).reference.teacher_forced_logits(
        cfg, seed, ids, precisions)
    ref = logits["f32"]
    best = jnp.max(ref, axis=-1)
    nxt = jnp.asarray(np.roll(ids, -1, axis=1))
    out = {"positions": int(mask.sum())}
    for p in precisions:
        chosen = nxt if p == "f32" else jnp.argmax(logits[p], axis=-1)
        gap = best - jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
        gap = np.asarray(gap)[mask]
        out[p] = {"served_gap": float(gap.max()),
                  "served_gap_mean": float(gap.mean()),
                  "served_off_best_share": float((gap > 0).mean())}
    return out


def pick_sample(finished: list, n: int, seed: int) -> list:
    """`n` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i].prompt) + len(finished[i].tokens)))
    rng = np.random.default_rng([int(seed), 3])
    rest = rng.permutation(order[1:])[: max(0, n - 1)]
    return [finished[order[0]]] + [finished[i] for i in rest]


def run(ctx) -> dict:
    import jax

    mix = ctx.mix
    vocab = families.of(ctx.cfg).reference.sizes(ctx.cfg)["vocab"]
    net, batcher = build(ctx)
    load = Load(batcher, mix, traffic.requests(mix, vocab, ctx.seed))
    ramp = float(mix["arrival"].get("ramp_s", 0.0))
    load.start(time.perf_counter())
    time.sleep(ramp)                    # callers join one by one: set-up

    ic = net.infer_cache
    stats0 = batcher.stats()
    misses0, calls0 = ic.stats.misses, ic.stats.steps
    t0 = time.perf_counter()
    t1 = t0 + ctx.seconds
    load.stop_at = t1
    trace_at = t0 + ctx.seconds / 3.0 if ctx.trace_dir else None
    trace_end, window, traced = None, None, {}
    samples, positions = [], []
    with ctx.watch_compiles() as compiles:
        while True:
            now = time.perf_counter()
            if now >= t1:
                break
            if trace_at is not None and window is None and now >= trace_at:
                jax.profiler.start_trace(ctx.trace_dir,
                                         profiler_options=trace_reduce.quiet_profile())
                window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
                window.__enter__()
                traced = {"calls": ic.stats.steps,
                          "admitted": batcher.stats()["streams"]["admitted"],
                          "samples": len(samples)}
                trace_end = now + float(mix["trace_seconds"])
            if trace_end is not None and now >= trace_end:
                window.__exit__(None, None, None)
                traced = {"calls": ic.stats.steps - traced["calls"],
                          "admitted": batcher.stats()["streams"]["admitted"]
                          - traced["admitted"],
                          "samples": (traced["samples"], len(samples))}
                jax.profiler.stop_trace()
                trace_at, trace_end = None, None
            rows = load.live()
            positions.append(rows)
            samples.append((len(rows), sum(rows), batcher.stats()["slots"]["active"]))
            time.sleep(0.05)
        if trace_end is not None:           # a window shorter than the trace
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced = {}
    stats1 = batcher.stats()
    calls1, misses1 = ic.stats.steps, ic.stats.misses
    drained = load.join(_TOKEN_TIMEOUT_S + 30.0)
    batcher.stop()
    peak = ctx.memory_peak()

    in_window = [s for s in load.sent if t0 <= s.due < t1]
    tokens = sum(1 for s in load.sent for t in s.stamps if t0 <= t < t1)
    ttfts = [(s.stamps[0] - s.due) * 1e3 for s in in_window if s.stamps]
    missed = [s for s in in_window
              if s.error is not None or not s.done or not s.stamps]
    gaps = [(b - a) * 1e3 for s in load.sent
            for a, b in zip(s.stamps, s.stamps[1:]) if t0 <= b < t1]
    finished = [s for s in load.sent if s.done and s.error is None and s.tokens]
    short = sum(1 for s in finished if len(s.tokens) != s.max_new)
    live = np.asarray(samples, float).reshape(-1, 3)
    lo, hi = traced.get("samples", (0, len(live))) if traced else (0, len(live))
    counters = {
        "window_s": ctx.seconds, "requests": len(in_window),
        "slots": int(mix["server"]["n_slots"]),
        "host_overhead_fraction": stats1["host_overhead_fraction"],
        "occupancy_samples": live[:, 2].tolist(),
        "program_compiles": misses1 - misses0,
        "xla_compiles": compiles["count"],
        "calls": calls1 - calls0,
        "admitted": stats1["streams"]["admitted"] - stats0["streams"]["admitted"],
        "traced_calls": traced.get("calls"),
        "traced_admitted": traced.get("admitted"),
        "traced_live_rows": float(np.mean(live[lo:hi, 0])) if hi > lo else None,
        "traced_live_positions": float(np.mean(live[lo:hi, 1])) if hi > lo else None,
        "traced_live_row_positions": [p for rows in positions[lo:hi] for p in rows],
        "drained": drained,
        "ttfts_ms": ttfts, "gaps_ms": gaps,
    }

    # the program's state goes before the reference comes
    load.batcher = None
    net.params = None
    del batcher, net
    gc.collect()
    t_ref = time.perf_counter()
    sample = pick_sample(finished, int(mix["check_requests"]), ctx.seed)
    numbers = {"short_streams": float(short),
               "lost_requests": float(len(missed))}
    if sample:
        got = served_gaps(ctx.cfg, ctx.seed, sample)
        numbers.update(got["f32"])
        counters["checked_positions"] = got["positions"]
    else:
        numbers["served_gap_mean"] = float("nan")
    counters["reference_s"] = time.perf_counter() - t_ref
    return {
        "t_first": t0,
        "end_to_end": {
            "serve_tokens_per_s": tokens / ctx.seconds,
            "ttft_p50_ms": percentile(ttfts + [float("inf")] * len(missed), 50)
            if ttfts else float("nan"),
            "gap_p50_ms": percentile(gaps, 50) if gaps else float("nan"),
        },
        "attempted": len(in_window), "failed": len(missed),
        "counters": counters, "numbers": numbers,
        "memory_peak_bytes": peak,
    }


def readings(ctx, seeds, control_seeds) -> list:
    """For `calibrate`: on each seed a short window at the cell's own load,
    then the widest served gap (the lower reading) and, on `control_seeds`,
    the gap of the tokens that the int8 control puts first at the same
    positions.  One net serves every seed: its weights are set anew."""
    from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

    mix, srv = ctx.mix, ctx.mix["server"]
    vocab = families.of(ctx.cfg).reference.sizes(ctx.cfg)["vocab"]
    net, out = None, []
    for seed in seeds:
        if net is None:
            one = type(ctx)(**{**vars(ctx), "seed": seed})
            net, batcher = build(one)
        else:
            net.params = program.program_weights(ctx.cfg, seed)
            batcher = ContinuousBatcher(
                net, n_slots=int(srv["n_slots"]), max_seq=int(srv["max_seq"]),
                prompt_buckets=tuple(srv["prompt_buckets"]),
                max_pending=int(srv["max_pending"])).start()
        load = Load(batcher, mix, traffic.requests(mix, vocab, seed))
        load.start(time.perf_counter())
        load.stop_at = time.perf_counter() + ctx.seconds
        time.sleep(ctx.seconds)
        load.join(_TOKEN_TIMEOUT_S + 30.0)
        batcher.stop()
        load.batcher = None
        del batcher
        gc.collect()
        finished = [s for s in load.sent if s.done and s.error is None and s.tokens]
        sample = pick_sample(finished, int(mix["check_requests"]), seed)
        control = seed in control_seeds
        got = served_gaps(ctx.cfg, seed, sample,
                          ("f32", "int8") if control else ("f32",))
        rec = {"seed": seed, "finished": len(finished),
               "program": got["f32"],
               "positions": got["positions"]}
        if control:
            rec["control_int8"] = got["int8"]
        out.append(rec)
    return out
