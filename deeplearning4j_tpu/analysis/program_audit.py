"""Program auditor: static rules over jaxprs of compiled programs.

TVM and TensorFlow both keep a growing compiler stack honest the same
way — by inspecting the lowered program, not by trusting the source
that produced it.  This pass walks the jaxpr (recursively through every
sub-jaxpr: scans, conds, remat, pjit calls, custom-vjp bodies) of any
program and statically flags violations of the repo's hardest-won
invariants:

  f64-op                 a float64/complex128 value anywhere in the
                         program — an x64 leak (the whole stack is
                         bitwise-f32 by contract; see
                         tests/test_dtype_policy.py)
  dtype-promotion        a convert_element_type promoting to a float
                         wider than the active precision policy allows
                         (bf16/int8 programs re-materializing f32
                         compute defeats the policy)
  materialized-scores    an intermediate with two sequence-scale dims —
                         the [S,S] attention-score materialization the
                         flash kernels exist to avoid (generalized out
                         of tests/test_mfu_paths.py)
  undonated-step         a train-step program compiled without donating
                         its params buffer where donation is available
                         (double-buffers every parameter in HBM)
  undonated-kv-cache     a decode/prefill/verify program compiled
                         without donating its decode-state buffers
                         where donation is available — the KV cache is
                         the largest live buffer in a generation
                         server, and an undonated one is
                         double-buffered every single token
  undonated-kv-pages     the paged variant of the same rule: a
                         decode-paged/verify-paged program compiled
                         without donating the shared physical page
                         pool — the pool IS the server's KV memory,
                         so an undonated one doubles the whole
                         generation footprint
  spec-decode-parity     greedy speculative decoding produced a token
                         trajectory different from plain sequential
                         decode on a zoo model — speculation is a
                         THROUGHPUT optimization, never a sampling
                         change, and any divergence is a correctness
                         bug (this rule executes, it does not trace)
  host-callback          a host callback / infeed / outfeed primitive
                         inside a compiled hot path (each one is a
                         device->host round trip per step)
  collective-in-single-chip
                         a cross-device collective in a program whose
                         cache key says single-chip (dead weight at
                         best, a hang on a real single-device mesh at
                         worst)
  folded-constant        a large constant folded into the program
                         (batch data as a closure constant was the
                         original per-batch-recompile sin PR 1 fixed;
                         big consts also poison the persistent cache —
                         the artifact embeds the data)
  replicated-large-leaf  a program compiled on a mesh WITH a `model`
                         axis that still places a >= threshold-byte
                         param leaf fully replicated — the "forgot to
                         shard the embedding" bug: the tensor-parallel
                         plan exists to split exactly these leaves, and
                         a replicated one silently re-caps per-chip
                         memory at the single-chip bound

Programs reach the auditor three ways: `audit_fn` traces any callable,
`audit_cache` walks the audit records a `CompiledProgramCache` keeps
for every program it compiled, and `audit_zoo_models` builds + compiles
the four zoo models' serve and train-step programs and audits the lot
(the CLI `analyze` subcommand and the tier-1 gate run that).
"""

from __future__ import annotations

import functools
from typing import Iterable, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.analysis.report import Finding

#: primitives that cross the device->host boundary inside a program
HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed",
})

#: cross-device collective primitives (meaningless on one chip)
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "axis_index", "pgather", "pdot",
})

#: compute-width ceiling (bits) each serve-precision policy allows
POLICY_WIDTH = {"f32": 32, "bf16": 16, "int8": 16}

#: default byte threshold above which a folded constant is flagged
CONST_BYTES_THRESHOLD = 1 << 20  # 1 MiB

#: default byte threshold above which a fully-replicated param leaf on a
#: model-axis mesh is flagged (replicated-large-leaf)
REPLICATED_LEAF_BYTES = 1 << 20  # 1 MiB

#: default sequence scale for the materialized-scores rule: only shapes
#: with two dims at or above this count as an [S,S] materialization
#: (tiny test models legitimately build [16,16] masks)
SEQ_THRESHOLD = 512


# -- recursive jaxpr walks ----------------------------------------------------
# Generalized from tests/test_mfu_paths.py's `_collect_avals`: every
# eqn param that holds a (Closed)Jaxpr — scan/cond/while bodies, pjit
# and remat calls, custom-vjp closures — is descended into, so nothing
# hides behind a sub-jaxpr boundary.

def _inner_jaxprs(eqn):
    for val in eqn.params.values():
        for sub in (val if isinstance(val, (list, tuple)) else [val]):
            inner = getattr(sub, "jaxpr", None)  # ClosedJaxpr
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(sub, "eqns"):           # raw Jaxpr
                yield sub


def iter_eqns(jaxpr):
    """Yield every eqn of `jaxpr` and of every nested sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _inner_jaxprs(eqn):
            yield from iter_eqns(inner)


def collect_shapes(jaxpr, out: Optional[list] = None) -> List[Tuple]:
    """Every in/out aval shape of every eqn, recursively (the walk
    tests/test_mfu_paths.py's no-[S,S] guard is built on)."""
    if out is None:
        out = []
    for eqn in iter_eqns(jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            if aval is not None and getattr(aval, "shape", None) is not None:
                out.append(tuple(aval.shape))
    return out


def score_scale_shapes(jaxpr, seq_threshold: int) -> List[Tuple]:
    """Shapes with >= 2 dims at sequence scale — the [S,S] offenders."""
    return [s for s in collect_shapes(jaxpr)
            if sum(1 for dim in s if dim >= seq_threshold) >= 2]


def assert_no_materialized_scores(fn, args, seq_threshold: int,
                                  where: str) -> None:
    """Trace `fn(*args)` and assert no [S,S]-scale intermediate exists
    anywhere in the (recursively walked) jaxpr.  Trace-only — nothing
    executes.  This is the library home of the guard that used to live
    inline in tests/test_mfu_paths.py."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args)
    offenders = score_scale_shapes(jaxpr.jaxpr, seq_threshold)
    assert not offenders, (f"[S,S]-scale intermediates in {where}: "
                           f"{sorted(set(offenders))}")


# -- jaxpr-level rules --------------------------------------------------------

def _iter_consts(closed) -> Iterable:
    """Constants of a ClosedJaxpr and of every nested ClosedJaxpr."""
    for c in getattr(closed, "consts", ()) or ():
        yield c
    inner = getattr(closed, "jaxpr", closed)
    for eqn in iter_eqns(inner):
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                for c in getattr(sub, "consts", ()) or ():
                    yield c


def _dtype_name(aval) -> str:
    return str(getattr(aval, "dtype", ""))


def audit_jaxpr(closed, *, where: str, policy: str = "f32",
                seq_threshold: Optional[int] = None,
                single_chip: bool = True,
                const_bytes_threshold: int = CONST_BYTES_THRESHOLD
                ) -> List[Finding]:
    """Run every jaxpr-level rule over one ClosedJaxpr.

    where:          location tag stamped on findings ("program:<where>").
    policy:         active precision policy for the promotion rule.
    seq_threshold:  enable the materialized-scores rule at this scale
                    (None skips it — the rule is only meaningful for
                    attention programs with a known sequence length).
    single_chip:    whether this program's cache key says it runs on one
                    chip (enables the collective rule).
    """
    import numpy as np

    loc = f"program:{where}"
    jaxpr = getattr(closed, "jaxpr", closed)
    findings: List[Finding] = []

    f64_prims = {}
    promo_prims = {}
    host_prims = {}
    coll_prims = {}
    ceiling = POLICY_WIDTH.get(policy, 32)
    for eqn in iter_eqns(jaxpr):
        prim = getattr(getattr(eqn, "primitive", None), "name", "?")
        for var in list(eqn.invars) + list(eqn.outvars):
            dt = _dtype_name(getattr(var, "aval", None))
            if dt in ("float64", "complex128"):
                f64_prims.setdefault(prim, dt)
        if prim == "convert_element_type":
            new = np.dtype(eqn.params.get("new_dtype", np.float32))
            if (np.issubdtype(new, np.floating)
                    and 16 <= new.itemsize * 8 < 64
                    and new.itemsize * 8 > ceiling):
                promo_prims.setdefault(str(new), prim)
        if prim in HOST_CALLBACK_PRIMS:
            host_prims.setdefault(prim, True)
        if single_chip and prim in COLLECTIVE_PRIMS:
            coll_prims.setdefault(prim, True)

    if f64_prims:
        offenders = ", ".join(f"{p} ({d})"
                              for p, d in sorted(f64_prims.items()))
        findings.append(Finding(
            "f64-op", "error", loc,
            f"x64 leak: 64-bit float values flow through {offenders} — "
            f"the stack is bitwise-f32 by contract"))
    if promo_prims:
        offenders = ", ".join(sorted(promo_prims))
        findings.append(Finding(
            "dtype-promotion", "warn", loc,
            f"promotion to {offenders} exceeds the {policy} policy's "
            f"{ceiling}-bit compute ceiling"))
    if host_prims:
        findings.append(Finding(
            "host-callback", "error", loc,
            f"host callback primitive(s) {sorted(host_prims)} inside a "
            f"compiled hot path — a device->host round trip per call"))
    if coll_prims:
        findings.append(Finding(
            "collective-in-single-chip", "error", loc,
            f"collective primitive(s) {sorted(coll_prims)} in a program "
            f"keyed single-chip"))

    if seq_threshold:
        offenders = score_scale_shapes(jaxpr, seq_threshold)
        if offenders:
            findings.append(Finding(
                "materialized-scores", "error", loc,
                f"[S,S]-scale intermediates at S>={seq_threshold}: "
                f"{sorted(set(offenders))[:4]} — full attention scores "
                f"are materialized"))

    for c in _iter_consts(closed):
        try:
            arr = np.asarray(c)
        except Exception:  # noqa: BLE001 — non-array const (e.g. fn)
            continue
        if arr.nbytes >= const_bytes_threshold:
            findings.append(Finding(
                "folded-constant", "error", loc,
                f"constant of shape {tuple(arr.shape)} dtype {arr.dtype} "
                f"({arr.nbytes} bytes) folded into the program — data "
                f"baked into the executable recompiles per value and "
                f"bloats the persistent cache"))
    return findings


def audit_fn(fn, args, **kwargs) -> List[Finding]:
    """Trace `fn(*args)` (nothing executes) and audit the jaxpr.
    Accepts the same rule options as `audit_jaxpr`; `where` defaults to
    the function's name."""
    import jax

    kwargs.setdefault("where", getattr(fn, "__name__", repr(fn)))
    closed = jax.make_jaxpr(fn)(*args)
    return audit_jaxpr(closed, **kwargs)


# -- cache-level audit --------------------------------------------------------

def _donation_expected(expect_donation: Optional[bool]) -> bool:
    if expect_donation is not None:
        return bool(expect_donation)
    from deeplearning4j_tpu.nd.platform import default_backend

    return default_backend() != "cpu"


def _spec_axes(sharding) -> set:
    """Mesh axis names a NamedSharding's PartitionSpec actually uses
    (parts may be a name, a tuple of names, or None)."""
    spec = getattr(sharding, "spec", None)
    axes = set()
    for part in (spec or ()):
        if part is None:
            continue
        for a in (part if isinstance(part, tuple) else (part,)):
            axes.add(a)
    return axes


def _sharding_leaves(shardings) -> list:
    """Every `jax.sharding.Sharding` in a per-arg shardings tuple (each
    entry is one Sharding for the whole arg or a pytree of them)."""
    import jax

    out = []
    for entry in (shardings or ()):
        out.extend(jax.tree_util.tree_leaves(
            entry,
            is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)))
    return [s for s in out if isinstance(s, jax.sharding.Sharding)]


def _replicated_large_leaves(rec, where: str, threshold: int
                             ) -> List[Finding]:
    """The replicated-large-leaf rule body: on a mesh whose shardings
    mention a `model` axis, every abstract-arg leaf >= threshold bytes
    must shard over it."""
    import jax
    import numpy as np

    mesh_axes = set()
    for s in _sharding_leaves(rec.get("shardings")):
        mesh = getattr(s, "mesh", None)
        if mesh is not None:
            mesh_axes.update(mesh.axis_names)
    if "model" not in mesh_axes:
        return []
    findings: List[Finding] = []
    # arg 0 is the params tree in every cached program (batch args are
    # row-sharded by design — only PARAM leaves must carry the model axis)
    params_abstract = rec["abstract"][0] if rec["abstract"] else ()
    for leaf in jax.tree_util.tree_leaves(params_abstract):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            continue
        nbytes = int(np.prod(shape, dtype=np.int64)
                     * np.dtype(dtype).itemsize)
        if nbytes < threshold:
            continue
        if "model" not in _spec_axes(getattr(leaf, "sharding", None)):
            findings.append(Finding(
                "replicated-large-leaf", "error", f"program:{where}",
                f"param leaf {shape}/{dtype} ({nbytes} bytes) is fully "
                f"replicated on a mesh with a 'model' axis — shard it "
                f"(plan.param_pspecs) or it re-caps per-chip memory at "
                f"the single-chip bound"))
    return findings


def audit_cache(cache, *, expect_donation: Optional[bool] = None,
                seq_threshold: Optional[int] = None,
                const_bytes_threshold: int = CONST_BYTES_THRESHOLD,
                replicated_leaf_threshold: int = REPLICATED_LEAF_BYTES
                ) -> List[Finding]:
    """Audit every program a `CompiledProgramCache` has compiled this
    process, via the audit records the cache keeps per key (builder +
    abstract args + donation decision).  Re-traces each builder against
    its abstract args — cheap relative to the compile that already
    happened, and nothing executes.

    expect_donation: whether train-step programs should donate their
    params buffer (None = donate exactly when the backend supports it,
    i.e. off-CPU — the cache's own policy)."""
    import jax

    findings: List[Finding] = []
    for rec in cache.audit_records():
        where = f"{rec['kind']}:{rec['key']}"
        policy = "f32"
        for part in rec["key"]:
            if (isinstance(part, tuple) and len(part) == 2
                    and part[0] == "policy"):
                policy = part[1]
        if (rec["kind"] == "step-cache" and not rec["donate_argnums"]
                and _donation_expected(expect_donation)):
            findings.append(Finding(
                "undonated-step", "error", f"program:{where}",
                "train-step program compiled without donating its params "
                "buffer — every parameter is double-buffered in HBM"))
        if (rec["kind"] == "infer-cache" and rec["key"]
                and rec["key"][0] in ("decode", "prefill", "verify",
                                      "prefill-logp", "prefill-slot",
                                      "prefill-logp-slot", "write-row")
                and not rec["donate_argnums"]
                and _donation_expected(expect_donation)):
            findings.append(Finding(
                "undonated-kv-cache", "error", f"program:{where}",
                f"{rec['key'][0]} program compiled without donating its "
                f"decode-state buffers — the KV cache is double-buffered "
                f"in HBM on every token"))
        if (rec["kind"] == "infer-cache" and rec["key"]
                and rec["key"][0] in ("decode-paged", "verify-paged")
                and not rec["donate_argnums"]
                and _donation_expected(expect_donation)):
            findings.append(Finding(
                "undonated-kv-pages", "error", f"program:{where}",
                f"{rec['key'][0]} program compiled without donating the "
                f"shared KV page pool — the pool is the server's entire "
                f"generation memory, double-buffered on every step"))
        findings.extend(_replicated_large_leaves(
            rec, where, replicated_leaf_threshold))
        closed = jax.make_jaxpr(rec["build"]())(*rec["abstract"])
        findings.extend(audit_jaxpr(
            closed, where=where, policy=policy,
            seq_threshold=seq_threshold,
            single_chip=not rec["mesh"],
            const_bytes_threshold=const_bytes_threshold))
    return findings


# -- the zoo sweep ------------------------------------------------------------

def _zoo_labels(out):
    """A valid labels batch shaped like a model's output activations:
    uniform rows are simultaneously a probability distribution (MCXENT
    softmax heads) and an in-(0,1) target (reconstruction heads)."""
    import jax.numpy as jnp

    return jnp.full(out.shape, 1.0 / out.shape[-1], jnp.float32)


def audit_zoo_models(small: bool = True, rows: int = 4,
                     expect_donation: Optional[bool] = None,
                     seq_threshold: Optional[int] = None
                     ) -> Tuple[List[Finding], int]:
    """Build the four zoo models (LeNet, char-LSTM, charTransformer,
    deep-AE), compile each one's serve `output` program and train step
    through fresh caches, and audit every compiled program.  Returns
    (findings, programs audited).  This is what `cli analyze` and the
    tier-1 gate run: the invariant floor, checked on the programs that
    actually ship."""
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.nn.decode import check_generative
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.quantize import default_calibration

    findings: List[Finding] = []
    n_programs = 0
    for name, conf in zoo.precision_eval_confs(small).items():
        net = MultiLayerNetwork(conf, seed=0).init()
        x = default_calibration(conf, rows)
        out = net.output(x)                    # compiles the serve program
        net.finetune(x, _zoo_labels(out))      # compiles the train step
        try:
            check_generative(conf)
        except ValueError:
            pass
        else:
            # generative models also ship decode + prefill programs —
            # compile them through the same cache so the donation and
            # jaxpr rules see exactly what a generation server runs,
            # including the paged / prefix / speculative variants a
            # flag-enabled server swaps in (the draft's own programs
            # live in the draft's cache; its verify step lives here)
            # fixed audit geometry, NOT a serving default: the
            # auditor pins tiny shapes so every variant compiles
            net.warmup_generate(slots=2, max_seq=8,  # lint: allow(hardcoded-tunable)
                                prompt_buckets=(4,))
            net.warmup_generate(slots=2, max_seq=8,  # lint: allow(hardcoded-tunable)
                                prompt_buckets=(4,),
                                page_size=4, prefix_cache=True)  # lint: allow(hardcoded-tunable)
            draft = MultiLayerNetwork(
                zoo.char_lstm(conf.conf(-1).n_out, hidden=8, n_layers=1),
                seed=0).init()
            net.warmup_generate(slots=2, max_seq=8,  # lint: allow(hardcoded-tunable)
                                prompt_buckets=(4,),
                                draft_net=draft, spec_k=2)
        for cache in (net.step_cache, net.infer_cache):
            recs = cache.audit_records()
            n_programs += len(recs)
            for f in audit_cache(cache, expect_donation=expect_donation,
                                 seq_threshold=seq_threshold):
                findings.append(Finding(f.rule, f.severity,
                                        f"{name}/{f.location}", f.message))
    findings.extend(audit_attention_structure())
    n_programs += 2
    findings.extend(audit_decode_structure())
    n_programs += 4
    findings.extend(audit_spec_decode_parity())
    n_programs += 2
    return findings, n_programs


def audit_attention_structure(S: int = 1024, D: int = 8) -> List[Finding]:
    """Trace-only structural check of the flash-attention forward AND
    backward at a sequence length where an [S,S] materialization is
    unambiguous (the zoo's CPU-sized transformer runs at S=16, far below
    `SEQ_THRESHOLD`, so the zoo sweep alone can't see this class)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nd.pallas_kernels import flash_attention

    q = jax.ShapeDtypeStruct((1, S, 1, D), jnp.float32)

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, 256, 256, interpret=True,
                               block_skip=True)

    findings = audit_fn(fwd, (q, q, q), where=f"flash-fwd:S={S}",
                        seq_threshold=S)
    findings += audit_fn(
        jax.grad(lambda a, b, c: jnp.sum(fwd(a, b, c)), argnums=(0, 1, 2)),
        (q, q, q), where=f"flash-bwd:S={S}", seq_threshold=S)
    return findings


def audit_decode_structure(S: int = 1024) -> List[Finding]:
    """Trace-only structural check of the KV-cache decode step at a
    cache length where an [S,S] materialization is unambiguous: the
    whole point of the decode program is [B,1]-query attention against a
    [B,S] cache, so scores stay [B,H,S] — ONE sequence axis — however
    long the cache grows.  (Prefill is deliberately not checked here:
    it legitimately materializes [T,T] causal scores at prompt-bucket
    scale, which is bounded and paid once per stream.)"""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nn import decode as decode_mod
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = char_transformer(24, d_model=16, n_blocks=1, n_heads=2,
                            max_seq_len=S)
    net = MultiLayerNetwork(conf, seed=0).init()
    state = decode_mod.init_state(conf, 1, S)
    tok = jnp.zeros((1,), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)

    step = functools.partial(decode_mod.decode_step, conf)
    findings = audit_fn(step, (net.params, state, tok, pos),
                        where=f"decode-step:S={S}", seq_threshold=S)

    # the paged step gathers its context through the page table, which
    # must not change the score shape story: scores stay [B,H,1,ctx] —
    # ONE sequence axis — however many physical pages back the slot
    page_size = 128
    n_pages = -(-S // page_size)
    pstate = decode_mod.init_paged_state(conf, 1, n_pages + 1, page_size)
    page_table = jnp.zeros((1, n_pages), jnp.int32)

    findings += audit_fn(step, (net.params, pstate, tok, pos, page_table),
                         where=f"decode-step-paged:S={S}",
                         seq_threshold=S)
    return findings


def audit_spec_decode_parity(n_new: int = 8) -> List[Finding]:
    """Executable parity gate for speculative decoding: greedy decode
    with a draft + verify chunk must emit EXACTLY the tokens plain
    sequential decode emits, on both generative zoo models.  Unlike
    every other rule here this one runs the programs (CPU-sized, a few
    decode steps) — structural audits cannot see a wrong acceptance
    rule, only a divergent trajectory can."""
    from deeplearning4j_tpu.models.zoo import char_lstm, char_transformer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

    vocab = 13
    targets = {
        "char_lstm": char_lstm(vocab, hidden=16, n_layers=2),
        "char_transformer": char_transformer(vocab, d_model=16,
                                             n_blocks=2, n_heads=2,
                                             max_seq_len=32),
    }
    prompts = ([1, 2, 3, 4], [5, 6, 7])
    findings: List[Finding] = []
    for name, conf in targets.items():
        net = MultiLayerNetwork(conf, seed=0).init()

        def _run(**kw):
            b = ContinuousBatcher(net, n_slots=2, max_seq=16,  # lint: allow(hardcoded-tunable)
                                  prompt_buckets=(8,), **kw)
            b.start()
            streams = [b.submit(list(p), max_new_tokens=n_new,
                                temperature=0.0, rng_seed=i)
                       for i, p in enumerate(prompts)]
            toks = [list(s.tokens(timeout=120)) for s in streams]
            b.stop()
            return toks

        plain = _run()
        draft = MultiLayerNetwork(char_lstm(vocab, hidden=8, n_layers=1),
                                  seed=1).init()
        spec = _run(draft_net=draft, spec_k=3)
        if spec != plain:
            findings.append(Finding(
                "spec-decode-parity", "error", f"program:spec:{name}",
                f"greedy speculative decode diverged from sequential "
                f"decode on {name}: {spec} != {plain} — speculation "
                f"changed the sampled trajectory"))
    return findings
