"""Serve-path AOT compile cache — inference compiles once, serves many.

PR 1 (`optimize/step_cache.py`) gave the *training* step compile-once
semantics, but the serve path still re-traced `network_output` /
`network_loss` on every `output()` / `score()` call and every shape —
exactly the per-call graph construction cost TensorFlow (Abadi et al.,
2016) and the TPU datacenter analysis (Jouppi et al., 2017) identify as
the dominant non-compute overhead of accelerator inference.

`InferCache` reuses the `CompiledProgramCache` machinery:

  key schema    (entry point in {output, loss, feed_forward},
                 conf fingerprint, arg shapes/dtypes, sharding tag)
                 -> AOT executable.
  batch args    (params, x[, y, w]) are explicit jit arguments — params
                 can keep training between serve calls without retraces.
  bucketing     ragged final batches zero-pad up to the smallest known
                 row bucket; `output`/`feed_forward` slice the pad rows
                 back off (inference is row-independent, so real rows
                 are bit-identical), and `loss` masks pad rows out of
                 the weighted mean via the same gemm-contraction form as
                 training (`dot(rows, w)` is bit-invariant to trailing
                 zero-weight rows) — padded evaluation matches unpadded
                 evaluation bit-for-bit in f32.
  mesh sharding `set_mesh(Mesh(('batch',)))` shards the padded batch's
                 rows across the mesh with params replicated (the GSPMD
                 pattern: jit inserts the collectives, the same code
                 runs on 1 chip or a pod).  The sharding is a KEY
                 dimension, so single-chip and mesh programs for the
                 same (entry, fingerprint, bucket) coexist in memory and
                 in the disk cache; buckets round up to a multiple of
                 the mesh size so every shard gets equal rows.  Row
                 independence makes mesh outputs bitwise-identical to
                 the single-chip program's.
  precision     `set_policy("bf16"|"int8")` (optimize/quantize.py) adds
                 a `("policy", name)` element to the key — f32 keys are
                 UNCHANGED (and so stay valid against pre-policy disk
                 stores and stay bitwise-identical in behavior), while
                 bf16/int8 programs coexist per policy in memory and on
                 disk, composing with the sharding tag.  bf16 params
                 are cast once on the host (memoized per tree); int8
                 serves the fixed quantized snapshot installed with the
                 policy and dequantizes to bf16 in-graph.
  operands      a generation program (the decode family, `_run_decode`)
                 is handed the params with every leaf its layer declares
                 a weight product's operand (`OPERANDS`) held in the
                 compute dtype where it is stored wider
                 (`nn.decode.operand_tree`): the same products from half
                 the bytes of float32 masters.  Cast once a params tree
                 (memoized on its identity, dropped with the net's
                 params: `forget_params`) beside the masters, which
                 `output`, `feed_forward` and `loss` keep taking;
                 the narrowed leaves' signature joins the program's key,
                 so a master tree's program is never handed an operand
                 tree.  Where nothing is wider the tree and the key are
                 the masters' own.
  no donation   unlike the train cache, inference programs NEVER donate
                 their params buffer: the same params serve every call.
  observability `cache.stats` (hits / misses / steps / compile seconds)
                 sits alongside the train cache's stats; the CLI
                 `test`/`predict` commands report it in their JSON.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.optimize.step_cache import (CompiledProgramCache,
                                                    arg_signature,
                                                    conf_fingerprint)
from deeplearning4j_tpu.utils import profiling


def pad_rows(x, bucket: int):
    """Zero-pad `x` with rows up to `bucket` (feature rows = axis 0)."""
    pad = bucket - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x


def truncate_rows(arr, bucket: int, n: int):
    """Slice a program output back to the `n` real input rows.

    Activations may carry `bucket` rows or a whole multiple (B*T rows
    for sequence stages whose rnn_to_ff preprocessor flattened time into
    the batch); pad batch entries occupy the trailing block either way.
    Outputs whose leading dim is not tied to the batch pass through."""
    if getattr(arr, "ndim", 0) and arr.shape[0] and arr.shape[0] % bucket == 0:
        ratio = arr.shape[0] // bucket
        return arr[: n * ratio]
    return arr


class InferCache(CompiledProgramCache):
    """Keyed AOT-compile cache for the inference entry points."""

    kind = "infer-cache"

    #: key element for programs compiled without a mesh
    SINGLE = "single"

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._mesh = None
        self._replicated = None       # params sharding under the mesh
        self._batch_sharding = None   # row sharding under the mesh
        # memoized replicated placement of the last-served params tree
        # (holds the original tree so identity can't be recycled)
        self._placed_params: Tuple = (None, None)
        # serve-precision policy (optimize/quantize.py): a cache-key
        # dimension, so per-policy programs coexist like mesh ones do
        self._policy = "f32"
        self._qparams = None          # int8: fixed quantized snapshot
        # memoized bf16 cast of the last-served params tree (same
        # identity discipline as _placed_params)
        self._policy_params: Tuple = (None, None)
        # (params, conf, operand tree, its key) of the last tree a
        # generation program was handed (`_operand_params`, same identity
        # discipline), and what `stats()` of the batcher reports of it:
        # the trees cast so far, and the bytes of the served tree's
        # narrowed leaves
        self._operands: Tuple = (None,) * 4
        self.operand_casts = 0
        self.operand_bytes_narrowed = 0

    def _donate_argnums(self) -> Tuple[int, ...]:
        # serve-path params are reused by every subsequent call (and by
        # training) — donation would invalidate live buffers
        return ()

    def _fingerprint(self, conf) -> str:
        # attention_fused_bwd only changes the backward pass: serving
        # programs are gradient-free, so the flag is normalized out of the
        # inference fingerprint.  Flipping it for training therefore never
        # re-keys (or invalidates on-disk) serving programs — the training
        # step cache keeps the base fingerprint and re-keys as it should.
        with self._lock:
            fp = self._fingerprints.get(id(conf))
            if fp is None:
                norm = conf
                confs = getattr(conf, "confs", None)
                if confs and any(c.attention_fused_bwd for c in confs):
                    norm = conf.replace(confs=tuple(
                        c.replace(attention_fused_bwd=False)
                        for c in confs))
                elif getattr(conf, "attention_fused_bwd", False):
                    norm = conf.replace(attention_fused_bwd=False)
                fp = conf_fingerprint(norm)
                self._fingerprints[id(conf)] = fp
            return fp

    # -- mesh / plan ---------------------------------------------------------
    def set_mesh(self, mesh) -> None:
        """Shard every subsequent serve call's rows across `mesh`:
        `Mesh(('batch',))` keeps params replicated (`parallel.mesh.
        serve_mesh()` builds it), a 2-D `Mesh(('batch','model'))`
        additionally tensor-shards params — and decode state — per the
        plan's per-leaf specs; None reverts to single-chip programs.
        Already-compiled programs stay cached under their own sharding
        tag, so flipping back and forth never evicts or recompiles."""
        from deeplearning4j_tpu.parallel.mesh import infer_shardings

        with self._lock:
            self._mesh = mesh
            self._placed_params = (None, None)
            if mesh is None:
                self._replicated = self._batch_sharding = None
            else:
                self._replicated, self._batch_sharding = infer_shardings(mesh)

    @property
    def mesh(self):
        return self._mesh

    @property
    def plan(self):
        """The cache's current `ShardPlan` — derived from (mesh,
        policy) so there is exactly one source of truth.  Every cache
        key element (`sharding_tag`, policy suffix, decode tag) and
        every placement routes through it."""
        from deeplearning4j_tpu.parallel.plan import ShardPlan

        with self._lock:
            return ShardPlan(mesh=self._mesh, policy=self._policy)

    def set_plan(self, plan) -> None:
        """Install a `ShardPlan` wholesale: mesh and precision policy in
        one call.  int8 plans need the quantized tree installed first
        via `set_policy` (the plan carries the policy NAME, not the
        snapshot)."""
        if plan.policy != self._policy:
            self.set_policy(plan.policy,
                            qparams=self._qparams
                            if plan.policy == "int8" else None)
        self.set_mesh(plan.mesh)

    # -- precision policy ---------------------------------------------------
    def set_policy(self, policy: str, qparams=None) -> None:
        """Serve every subsequent call under `policy` ("f32" | "bf16" |
        "int8").  int8 needs the prepared quantized tree (quantization +
        calibration are the caller's job — `MultiLayerNetwork.
        set_serve_precision` owns that, including disk persistence).
        Like `set_mesh`, already-compiled programs stay cached under
        their own policy tag: flipping between policies re-hits, never
        evicts or recompiles."""
        from deeplearning4j_tpu.optimize.quantize import validate_policy

        validate_policy(policy)
        if policy == "int8" and qparams is None:
            raise ValueError("int8 policy needs the quantized params tree "
                             "(use MultiLayerNetwork.set_serve_precision)")
        with self._lock:
            self._policy = policy
            self._qparams = qparams if policy == "int8" else None
            self.forget_params()

    def forget_params(self) -> None:
        """Drop what the cache derived from the last params tree (its
        policy cast, its placement, its operand tree): the net calls it
        when its params are replaced or dropped, so no copy outlives
        them."""
        with self._lock:
            self._policy_params = self._placed_params = (None, None)
            self._operands = (None,) * 4

    @property
    def policy(self) -> str:
        return self._policy

    def _policy_suffix(self) -> Tuple:
        """Cache-key elements the policy contributes (the plan's
        `policy_suffix`).  f32 contributes NOTHING — its keys (and
        therefore its disk-store paths and its outputs) are
        byte-identical to the pre-policy serve path."""
        return self.plan.policy_suffix()

    def _serve_params(self, params):
        """The params tree the policy's programs take as argument: f32
        passes through; bf16 is a memoized cast-on-load of the incoming
        tree (tracks training — a new tree re-casts); int8 is the fixed
        snapshot `set_policy` installed (requantization is deliberate,
        never implicit)."""
        policy = self._policy
        if policy == "f32":
            return params
        if policy == "int8":
            return self._qparams
        with self._lock:
            held, cast = self._policy_params
        if held is not params:
            from deeplearning4j_tpu.optimize.quantize import cast_params_bf16

            cast = cast_params_bf16(params)
            with self._lock:
                self._policy_params = (params, cast)
        return cast

    def _operand_params(self, conf, params) -> Tuple:
        """(tree, key) of a generation program's params: `params` (as the
        policy serves them) with their declared operands narrowed to the
        compute dtype (`nn.decode.operand_tree`), and the signature of the
        narrowed leaves, which the program's key takes (empty, and the
        tree `params` itself, where nothing is wider).  Cast once a tree,
        memoized on its identity as `_serve_params` is: a new tree (after
        `fit`) is cast again."""
        with self._lock:
            held, held_conf, tree, key = self._operands
        if held is params and held_conf is conf:
            return tree, key
        from deeplearning4j_tpu.nn.decode import operand_tree

        tree, narrowed = operand_tree(_policy_conf(conf, self._policy), params)
        key = tuple((tuple(a.shape), str(a.dtype)) for a in narrowed)
        with self._lock:
            self._operands = (params, conf, tree, key)
            self.operand_bytes_narrowed = sum(a.nbytes for a in narrowed)
            self.operand_casts += bool(narrowed)
        return tree, key

    def programs_summary(self):
        """Resident compiled programs as (entry, bucket, sharding,
        policy) rows — the `/v1/stats` `programs` block operators use to
        verify warmup coverage across every cache-key dimension."""
        with self._lock:
            keys = list(self._programs)
        rows = []
        for k in keys:
            entry, _, sig, tag = k[0], k[1], k[2], k[3]
            policy = k[4][1] if len(k) > 4 else "f32"
            bucket = int(sig[0][0][0]) if sig and sig[0] and sig[0][0] else 0
            sharding = (tag if isinstance(tag, str)
                        else "mesh:" + "x".join(str(d) for d in tag[2]))
            rows.append({"entry": entry, "bucket": bucket,
                         "sharding": sharding, "policy": policy})
        return sorted(rows, key=lambda r: (r["entry"], r["bucket"],
                                           r["sharding"], r["policy"]))

    def _mesh_rows(self) -> int:
        """Row-divisibility the current plan demands (1 = no mesh; 2-D
        meshes only need the BATCH axis to divide the rows)."""
        return self.plan.rows

    def sharding_tag(self):
        """The sharding dimension of the cache key (the plan's
        `sharding_tag`): 'single' or a (mesh, axis names, mesh shape)
        tuple.  Distinct tags can never alias — single-chip and mesh
        programs coexist."""
        return self.plan.sharding_tag()

    def _decode_tag(self):
        """Sharding key element for decode/prefill/verify entries (the
        plan's `decode_tag`): generation stays single-chip — and its
        keys stay byte-identical to pre-plan disk artifacts — unless
        the plan carries a `model` axis, which genuinely re-keys the
        programs (sharded KV tables, jit-inserted collectives)."""
        return self.plan.decode_tag()

    def _serve_bucket(self, n: int) -> int:
        """Bucket for `n` rows.  Under a mesh the bucket must divide
        evenly across the 'batch' axis, so pick the smallest known
        divisible bucket >= n, else grow a new one at the next multiple
        (single-chip buckets stay visible to mesh calls only when they
        happen to divide — no eviction, just separate buckets)."""
        m = self._mesh_rows()
        if m == 1:
            return self.bucket_rows(n)
        target = -(-n // m) * m
        with self._lock:
            for b in self._buckets:
                if b >= n and b % m == 0:
                    return b
            if not self._fixed_buckets:
                self._buckets.append(target)
                self._buckets.sort()
            return target

    def _shardings(self, sp, n_batch_args: int) -> Optional[Tuple]:
        """(params sharding(s), batch shardings...) under the mesh; None
        single-chip.  1-D meshes replicate params (one Sharding covers
        the whole subtree — the pre-plan placement, byte-identical
        keys); a `model` axis switches the params entry to the plan's
        per-leaf sharding tree."""
        if self._mesh is None:
            return None
        plan = self.plan
        if plan.has_model_axis:
            return ((plan.param_shardings(sp),)
                    + (plan.batch_sharding(),) * int(n_batch_args))
        from deeplearning4j_tpu.parallel.mesh import serve_placements

        return serve_placements(self._mesh, n_batch_args)

    def _place_params(self, params):
        """Mesh placement of the params tree, memoized per tree
        identity (serving reuses one tree for every request):
        replicated under a 1-D plan, per-leaf tensor-sharded under a
        `model` axis."""
        with self._lock:
            held, placed = self._placed_params
            if held is params:
                return placed
        plan = self.plan
        if plan.has_model_axis:
            placed = jax.tree_util.tree_map(
                jax.device_put, params, plan.param_shardings(params))
        else:
            placed = jax.device_put(params, self._replicated)
        with self._lock:
            self._placed_params = (params, placed)
        return placed

    def _place(self, params, *batch_args) -> Tuple:
        """Device placement for execution under the mesh: params per
        `_place_params`, batch args row-sharded."""
        if self._mesh is None:
            return (params,) + batch_args
        return (self._place_params(params),) + tuple(
            jax.device_put(a, self._batch_sharding) for a in batch_args)

    # -- entry points -------------------------------------------------------
    def output(self, conf, params, x, compile_only: bool = False):
        """`network_output` through the cache: returns the output
        activations for the `x.shape[0]` real rows.  compile_only=True
        (warmup) registers the bucket and compiles — or disk-restores —
        the program without executing it."""
        n = int(x.shape[0])
        bucket = self._serve_bucket(n)
        xp = pad_rows(x, bucket)
        policy, sp = self._policy, self._serve_params(params)
        key = ("output", self._fingerprint(conf), arg_signature(xp),
               self.sharding_tag()) + self._policy_suffix()
        fn = self._get(key, lambda: _output_program(conf, policy), (sp, xp),
                       shardings=self._shardings(sp, 1))
        if compile_only:
            return None
        with self._lock:
            self.stats.steps += 1
        return truncate_rows(fn(*self._place(sp, xp)), bucket, n)

    def feed_forward(self, conf, params, x, compile_only: bool = False):
        """`feed_forward` through the cache: the per-layer activation
        list, each sliced back to the real rows."""
        n = int(x.shape[0])
        bucket = self._serve_bucket(n)
        xp = pad_rows(x, bucket)
        policy, sp = self._policy, self._serve_params(params)
        key = ("feed_forward", self._fingerprint(conf), arg_signature(xp),
               self.sharding_tag()) + self._policy_suffix()
        fn = self._get(key, lambda: _feed_forward_program(conf, policy),
                       (sp, xp), shardings=self._shardings(sp, 1))
        if compile_only:
            return None
        with self._lock:
            self.stats.steps += 1
        return [truncate_rows(a, bucket, n)
                for a in fn(*self._place(sp, xp))]

    # -- autoregressive generation (ISSUE 14) --------------------------------
    def _decode_donate(self) -> Tuple[int, ...]:
        """Decode-entry donation: the state tuple (arg 1) is consumed
        every step — its K/V caches and LSTM carries keep their shapes
        and dtypes, so jit aliases them in place instead of allocating a
        fresh [B, max_S, n] table per token.  Params (arg 0) are NEVER
        donated (shared with every other serve call).  CPU skips
        donation like the train cache does (buffer donation is a no-op
        warning there)."""
        from deeplearning4j_tpu.nd.platform import default_backend

        return (1,) if default_backend() != "cpu" else ()

    def _run_decode(self, entry: str, program, conf, head, state, rest: Tuple,
                    keyed: Optional[Tuple] = None, head_is_row: bool = False,
                    compile_only: bool = False):
        """What every decode-family entry does, in one place: the key, the
        program, the step count, the placement and the call.

        `program(conf, policy)` builds the function to compile.  Its
        arguments are `(head, state) + rest`: `head` the params as the
        policy serves them, their declared operands narrowed
        (`_operand_params`; for `write_row` the row: `head_is_row`),
        `state` the decode state or slot table — argument 1, donated
        off-CPU, and last among the outputs — and `rest` the small host
        arguments.  The key is (entry, fingerprint, the signature of
        `keyed` (default: `rest`), then of the state's leaves and then of
        the narrowed operands, the decode tag) + the policy suffix.  Under
        a plan with a `model` axis the head and the state are sharded leaf
        by leaf per the plan and the rest replicated, at compile time and
        at the call (params memoized; a no-op for the state in the steady
        loop, whose output the program pins to the same specs); without
        one, generation stays a single-chip program and nothing is placed.
        compile_only=True (warmup) compiles, or restores from disk, and
        returns None."""
        plan, policy = self.plan, self._policy
        operands = ()
        if not head_is_row:
            head, operands = self._operand_params(conf, self._serve_params(head))
        key = (entry, self._fingerprint(conf),
               arg_signature(*(rest if keyed is None else keyed),
                             *jax.tree_util.tree_leaves(state)) + operands,
               self._decode_tag()) + self._policy_suffix()
        shardings = None
        if plan.has_model_axis:
            rep = plan.replicated()
            shardings = ((plan.state_shardings(head, conf) if head_is_row
                          else plan.param_shardings(head),
                          plan.state_shardings(state, conf))
                         + (rep,) * len(rest))
        fn = self._get(key, self._tp_build(conf, lambda: program(conf, policy)),
                       (head, state) + rest, donate=self._decode_donate(),
                       shardings=shardings)
        if compile_only:
            return None
        with self._lock:
            self.stats.steps += 1
        if shardings is not None:
            head = (self._place_decode_state(conf, head) if head_is_row
                    else self._place_params(head))
            state = self._place_decode_state(conf, state)
            rest = tuple(jax.device_put(a, rep) for a in rest)
        return fn(head, state, *rest)

    def _tp_build(self, conf, build):
        """Wrap a decode-family program builder for a tensor-parallel
        plan: the returned program pins its (donated, state-last)
        output state to the plan's per-leaf specs with
        `with_sharding_constraint` INSIDE the traced function — so the
        compiled executable's output layout provably matches its input
        layout and the next step's call is a pure hit, never a
        reshard."""
        plan = self.plan
        if not plan.has_model_axis:
            return build
        mesh = plan.mesh

        def wrapped():
            base = build()

            def program(*args):
                out = base(*args)
                *rest, st = out
                st = jax.tree_util.tree_map(
                    lambda a, s: jax.lax.with_sharding_constraint(
                        a, jax.sharding.NamedSharding(mesh, s)),
                    st, plan.state_pspecs(st, conf))
                return tuple(rest) + (st,)

            return program

        return wrapped

    def _place_decode_state(self, conf, state):
        """Plan placement for a fresh decode state of `conf`'s stack
        (no-op without a `model` axis)."""
        plan = self.plan
        if not plan.has_model_axis:
            return state
        return jax.tree_util.tree_map(jax.device_put, state,
                                      plan.state_shardings(state, conf))

    def init_decode_state(self, conf, batch: int, max_seq: int):
        """Fresh decode state shaped for the active policy's programs,
        placed per the active plan (a `model` axis shards the K/V
        feature dims so the cache itself can exceed one chip's HBM)."""
        from deeplearning4j_tpu.nn import decode as decode_mod

        return self._place_decode_state(conf, decode_mod.init_state(
            _policy_conf(conf, self._policy), batch, max_seq))

    def decode(self, conf, params, state, tok, pos, keys, temps,
               page_table=None, compile_only: bool = False):
        """One compiled KV-cache decode step over the whole slot table:
        tok/pos [B] int32, keys [B, 2] uint32 per-row PRNG keys, temps
        [B] f32 (<= 0 rows decode greedily).  Returns (next_tok [B]
        int32, advanced keys, new state); the state argument is donated
        off-CPU.  A stack with expert layers (`nn.decode.has_experts`)
        returns their counts of the step, [2] int32, before the state.
        Under a 1-D (or no) mesh generation is single-chip and the key
        carries the SINGLE tag exactly as before; a plan with a `model`
        axis re-keys the program by its sharding tag and shards params +
        KV state per the plan.

        Over a paged state (ISSUE 16, `init_paged_decode_state`),
        `page_table` [B, pages_per_slot] int32 is a tiny per-call host
        argument, the program's last, routing each row through the shared
        physical pool; the key entry is then "decode-paged", so paged and
        dense programs coexist."""
        return self._run_decode(
            "decode" if page_table is None else "decode-paged",
            _decode_program, conf, params, state,
            _with_pages((tok, pos, keys, temps), page_table),
            compile_only=compile_only)

    def init_paged_decode_state(self, conf, batch: int, n_pages: int,
                                page_size: int):
        """Fresh paged decode state (shared K/V page pool) shaped for
        the active policy's programs, placed per the active plan (the
        page pool's feature dim shards over a `model` axis by head)."""
        from deeplearning4j_tpu.nn import decode as decode_mod

        return self._place_decode_state(conf, decode_mod.init_paged_state(
            _policy_conf(conf, self._policy), batch, n_pages, page_size))

    def verify(self, conf, params, state, toks, pos, keys, temps,
               page_table=None, compile_only: bool = False):
        """Speculative verification step: toks [B, K] int32 (column 0 is
        each row's current token, columns 1..K-1 the draft
        continuations), pos [B] int32 the position of column 0.  One
        program advances every row K positions and chain-samples K
        tokens with the row's key stream — exactly the splits K
        sequential `decode` calls would burn — returning (sampled
        [B, K] int32, keys_after [B, K, 2] uint32 (the key state after
        accepting 1..K tokens), new state).  The host accepts the
        longest prefix where draft and sample agree; mis-speculated
        cache rows are rewritten by the next call before being read, so
        rollback is free.  Over a paged state, with its `page_table`, the
        key entry is "verify-paged"."""
        return self._run_decode(
            "verify" if page_table is None else "verify-paged",
            _verify_program, conf, params, state,
            _with_pages((toks, pos, keys, temps), page_table),
            compile_only=compile_only)

    def prefill(self, conf, params, state, prompt, length, keys, temps,
                compile_only: bool = False):
        """Compiled prompt prefill: prompt [B, T_bucket] int32
        (zero-padded), length [B] int32.  Fills the decode state and
        samples each row's FIRST generated token (time-to-first-token is
        one program execution).  Same donation/key contract as
        `decode`; one program per (fingerprint, rows, prompt bucket,
        max_seq) via the state leaves in the signature."""
        return self._run_decode(
            "prefill", _prefill_program, conf, params, state,
            (prompt, length, keys, temps), compile_only=compile_only)

    def prefill_logp(self, conf, params, state, prompt, length,
                     compile_only: bool = False):
        """Prefix-cacheable prompt prefill: fills the state exactly like
        `prefill` but returns (logp [B, vocab] f32, state) WITHOUT
        sampling — the serving layer caches the pair by prompt digest
        and samples each stream's first token on the host with the
        stream's own key (the eager sampler's discipline, which the
        compiled samplers reproduce exactly), so one cold prefill serves
        every later stream sharing the prompt regardless of key or
        temperature.  Only the prefix-cache flag routes admissions here;
        with the flag off this program is never built."""
        return self._run_decode(
            "prefill-logp", _prefill_logp_program, conf, params, state,
            (prompt, length), compile_only=compile_only)

    # -- admission into the slot table: one program a stream -----------------
    def prefill_slot(self, conf, params, table, slot: int, prompt, length,
                     keys, temps, compile_only: bool = False):
        """A dense admission in ONE program: prefill prompt [1, T_bucket]
        into a zero row made inside the trace, sample the stream's first
        token as `prefill` does, and write the row into row `slot` of the
        slots-wide `table`.  Returns (tok0 [1], advanced keys [1, 2],
        table).  The decode family's contract: the table is argument 1,
        donated off-CPU (the write is in place: no second table, no
        other row touched), and last in the output; `slot` is a traced
        int32 scalar outside the key, so one program a prompt bucket
        serves every slot."""
        keyed = (prompt, length, keys, temps)
        return self._run_decode(
            "prefill-slot", _prefill_slot_program, conf, params, table,
            (np.asarray(slot, np.int32),) + keyed, keyed=keyed,
            compile_only=compile_only)

    def prefill_logp_slot(self, conf, params, table, slot: int, prompt,
                          length, compile_only: bool = False):
        """`prefill_slot` for the prefix cache: no sampling (see
        `prefill_logp`), and the filled B=1 row comes back beside the
        table it was written into, for the cache to keep.  Returns
        (logp [1, vocab] f32, row, table)."""
        keyed = (prompt, length)
        return self._run_decode(
            "prefill-logp-slot", _prefill_logp_slot_program, conf, params,
            table, (np.asarray(slot, np.int32),) + keyed, keyed=keyed,
            compile_only=compile_only)

    def write_row(self, conf, table, row, slot: int,
                  compile_only: bool = False):
        """`nn.decode.write_row` as a program of its own, for the
        admissions that already hold a row (a prefix-cache hit): B=1
        `row` (device or host tree) into row `slot` of `table`, which is
        donated off-CPU and returned.  The row goes first so that the
        table is argument 1, as in the rest of the decode family; the
        program takes no params, and its key is the row's leaves and then
        the table's."""
        out = self._run_decode(
            "write-row", _write_row_program, conf, row, table,
            (np.asarray(slot, np.int32),),
            keyed=tuple(jax.tree_util.tree_leaves(row)), head_is_row=True,
            compile_only=compile_only)
        return None if out is None else out[0]

    def loss(self, conf, params, x, y, compile_only: bool = False):
        """`network_loss(training=False)` through the cache: the
        row-weighted mean loss over the real rows plus regularization.
        Pad rows carry weight 0 and the mean is a gemm contraction, so a
        bucket-padded tail scores bit-identically to the unpadded batch."""
        n = int(x.shape[0])
        bucket = self._serve_bucket(n)
        xp, yp, w = self.pad_batch(x, y, bucket)
        policy, sp = self._policy, self._serve_params(params)
        key = ("loss", self._fingerprint(conf), arg_signature(xp, yp, w),
               self.sharding_tag()) + self._policy_suffix()
        fn = self._get(key, lambda: _loss_program(conf, policy),
                       (sp, xp, yp, w), shardings=self._shardings(sp, 3))
        if compile_only:
            return None
        with self._lock:
            self.stats.steps += 1
        return fn(*self._place(sp, xp, yp, w))


def _policy_conf(conf, policy: str):
    """The conf a policy's programs trace against (f32: the original —
    byte-for-byte the pre-policy program)."""
    if policy == "f32":
        return conf
    from deeplearning4j_tpu.optimize.quantize import serve_conf

    return serve_conf(conf, policy)


def _policy_args(params, policy: str):
    """In-graph view of the program's params argument: int8 sub-dicts
    dequantize to bf16 right here, inside the traced program."""
    if policy == "f32":
        return params
    from deeplearning4j_tpu.optimize.quantize import runtime_params

    return runtime_params(params, policy)


def _sample_tokens(logp, keys, temps):
    """On-device sampling with the eager sampler's exact PRNG
    discipline: every row splits its key once per step (`key, sub =
    split(key)`), rows with temperature <= 0 take argmax, the rest draw
    `categorical(sub, logp / temperature)`.  Returns (tok [B] int32,
    advanced keys [B, 2])."""
    with profiling.scope("sample"):
        ks = jax.vmap(jax.random.split)(keys)          # [B, 2, 2]
        new_keys, subs = ks[:, 0], ks[:, 1]
        greedy = jnp.argmax(logp, axis=-1).astype(jnp.int32)
        safe = jnp.where(temps > 0, temps, jnp.ones_like(temps))
        sampled = jax.vmap(jax.random.categorical)(
            subs, logp / safe[:, None]).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy), new_keys


def _sample_chain(logp, keys, temps):
    """Chain-sample one token per chunk position: position i consumes
    logp[:, i] with the key state left by position i-1 — the identical
    split sequence K sequential `_sample_tokens` calls would produce, so
    an accepted chunk's tokens AND advanced keys match sequential decode
    exactly.  Returns (toks [B, K] int32, keys_after [B, K, 2])."""
    toks, keys_after = [], []
    for i in range(logp.shape[1]):
        t, keys = _sample_tokens(logp[:, i], keys, temps)
        toks.append(t)
        keys_after.append(keys)
    return jnp.stack(toks, axis=1), jnp.stack(keys_after, axis=1)


def _with_pages(rest: Tuple, page_table) -> Tuple:
    """A decode-family program's host arguments: over a paged state the
    page table is the last of them."""
    return rest if page_table is None else rest + (page_table,)


def _accepted_len(toks, sampled):
    """Acceptance length per row, in-program: e = 1 + the number of
    leading draft proposals toks[:, 1:] that equal the target's own
    chain samples sampled[:, :-1] (the guaranteed first token plus the
    agreeing prefix).  Integer comparisons — bit-identical to the host
    loop the serving batcher runs on the fetched arrays."""
    b, kk = toks.shape
    if kk == 1:
        return jnp.ones((b,), jnp.int32)
    agree = (toks[:, 1:] == sampled[:, :-1]).astype(jnp.int32)
    return 1 + jnp.sum(jnp.cumprod(agree, axis=1), axis=1)


def _rollback_carries(state, carries, e):
    """Replace each recurrent layer's final carry in `state` with the
    intermediate carry after the e-th verified token (index e-1 of the
    [B, K, hidden] stacks): attention K/V self-heals on mis-speculation
    (rejected positions are overwritten before they are read) but a
    recurrent carry advanced past the accepted prefix would poison
    every later token."""
    rows = jnp.arange(e.shape[0])
    out = []
    for lay, car in zip(state, carries):
        if car:
            out.append({k: v[rows, e - 1] for k, v in car.items()})
        else:
            out.append(lay)
    return tuple(out)


def _verify_program(conf, policy: str = "f32") -> Callable:
    from deeplearning4j_tpu.nn import decode as decode_mod

    pconf = _policy_conf(conf, policy)

    def program(params, state, toks, pos, keys, temps, page_table=None):
        logp, state, carries = decode_mod.verify_chunk(
            pconf, _policy_args(params, policy), state, toks, pos,
            page_table)
        if policy != "f32":
            logp = logp.astype(jnp.float32)
        sampled, keys_after = _sample_chain(logp, keys, temps)
        state = _rollback_carries(state, carries,
                                  _accepted_len(toks, sampled))
        return sampled, keys_after, state

    return program


def _decode_program(conf, policy: str = "f32") -> Callable:
    from deeplearning4j_tpu.nn import decode as decode_mod

    pconf = _policy_conf(conf, policy)

    def program(params, state, tok, pos, keys, temps, page_table=None):
        logp, state, counts = decode_mod.step(
            pconf, _policy_args(params, policy), state, tok, pos,
            page_table)
        if policy != "f32":
            logp = logp.astype(jnp.float32)
        tok2, keys2 = _sample_tokens(logp, keys, temps)
        if counts is None:
            return tok2, keys2, state
        return tok2, keys2, counts, state   # a stack with expert layers

    return program


def _prefill_program(conf, policy: str = "f32") -> Callable:
    from deeplearning4j_tpu.nn import decode as decode_mod

    pconf = _policy_conf(conf, policy)

    def program(params, state, prompt, length, keys, temps):
        logp, state = decode_mod.prefill(
            pconf, _policy_args(params, policy), state, prompt, length)
        if policy != "f32":
            logp = logp.astype(jnp.float32)
        tok0, keys2 = _sample_tokens(logp, keys, temps)
        return tok0, keys2, state

    return program


def _prefill_logp_program(conf, policy: str = "f32") -> Callable:
    from deeplearning4j_tpu.nn import decode as decode_mod

    pconf = _policy_conf(conf, policy)

    def program(params, state, prompt, length):
        logp, state = decode_mod.prefill(
            pconf, _policy_args(params, policy), state, prompt, length)
        return logp.astype(jnp.float32), state

    return program


def _prefill_slot_program(conf, policy: str = "f32") -> Callable:
    from deeplearning4j_tpu.nn import decode as decode_mod

    prefill = _prefill_program(conf, policy)

    def program(params, table, slot, prompt, length, keys, temps):
        tok0, keys2, row = prefill(params, decode_mod.zero_row(table),
                                   prompt, length, keys, temps)
        return tok0, keys2, decode_mod.write_row(table, row, slot)

    return program


def _prefill_logp_slot_program(conf, policy: str = "f32") -> Callable:
    from deeplearning4j_tpu.nn import decode as decode_mod

    prefill_logp = _prefill_logp_program(conf, policy)

    def program(params, table, slot, prompt, length):
        logp, row = prefill_logp(params, decode_mod.zero_row(table),
                                 prompt, length)
        return logp, row, decode_mod.write_row(table, row, slot)

    return program


def _write_row_program(conf=None, policy: str = "f32") -> Callable:
    # a copy of leaves: neither the conf nor the policy changes it
    from deeplearning4j_tpu.nn import decode as decode_mod

    def program(row, table, slot):
        return (decode_mod.write_row(table, row, slot),)

    return program


def _output_program(conf, policy: str = "f32") -> Callable:
    # local import: nn.multilayer imports this module at top level
    from deeplearning4j_tpu.nn.multilayer import network_output

    pconf = _policy_conf(conf, policy)

    def program(params, x):
        out = network_output(pconf, _policy_args(params, policy), x,
                             key=None, training=False)
        # low-precision programs hand back f32 so every caller — the
        # batcher, eval, bitwise tests — sees one output contract
        return out if policy == "f32" else out.astype(jnp.float32)

    return program


def _feed_forward_program(conf, policy: str = "f32") -> Callable:
    from deeplearning4j_tpu.nn.multilayer import feed_forward

    pconf = _policy_conf(conf, policy)

    def program(params, x):
        acts = feed_forward(pconf, _policy_args(params, policy), x,
                            key=None, training=False)
        if policy != "f32":
            acts = [a.astype(jnp.float32) for a in acts]
        return tuple(acts)

    return program


def _loss_program(conf, policy: str = "f32") -> Callable:
    from deeplearning4j_tpu.nn.multilayer import (network_regularization,
                                                  network_rowwise_loss)

    pconf = _policy_conf(conf, policy)

    def program(params, x, y, w):
        p = _policy_args(params, policy)
        rows = network_rowwise_loss(pconf, p, x, y, key=None,
                                    training=False)
        reg = network_regularization(pconf, p)
        if policy != "f32":
            rows, reg = rows.astype(jnp.float32), reg.astype(jnp.float32)
        # dot, not mean: bit-invariant to trailing zero-weight pad rows
        # (see make_finetune_loss / layers.base.rows_broadcast)
        return (jnp.dot(rows, w)
                / jnp.maximum(jnp.dot(w, jnp.ones_like(w)), 1.0)
                + reg)

    return program
