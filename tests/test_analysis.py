"""Static-analysis layer (ISSUE 12): report model, program auditor,
repo-convention linter, fault-point conformance, and the CLI gate.

Fixture philosophy: every rule is proven twice — a seeded violation
produces exactly the expected Finding, and the equivalent clean program
or source produces none.  The repo itself is asserted clean at the end
(the same invariant the tier-1 `analyze` gate enforces).
"""

import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis.program_audit import (
    SEQ_THRESHOLD,
    assert_no_materialized_scores,
    audit_attention_structure,
    audit_cache,
    audit_fn,
    audit_jaxpr,
    collect_shapes,
    iter_eqns,
)
from deeplearning4j_tpu.analysis.report import (
    REPORT_VERSION,
    Finding,
    at_or_above,
    counts,
    to_report,
)
from deeplearning4j_tpu.analysis.repo_lint import (
    lint_file,
    lint_package,
    lint_source,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return [f.rule for f in findings]


# -- report model ------------------------------------------------------------

def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError):
        Finding("r", "fatal", "x:1", "m")


def test_report_schema_and_severity_ordering():
    fs = [Finding("a", "info", "x:1", "m"),
          Finding("b", "error", "y:2", "m"),
          Finding("c", "warn", "z:3", "m")]
    rep = to_report(fs, {"files": 3})
    assert rep["version"] == REPORT_VERSION
    assert rep["counts"] == {"info": 1, "warn": 1, "error": 1}
    assert rep["checked"] == {"files": 3}
    assert [f["severity"] for f in rep["findings"]] == \
        ["error", "warn", "info"]
    assert set(rep["findings"][0]) == {"rule", "severity", "location",
                                       "message"}
    assert _rules(at_or_above(fs, "warn")) == ["b", "c"]
    assert counts([]) == {"info": 0, "warn": 0, "error": 0}


# -- program auditor: seeded violations --------------------------------------

def test_f64_op_detected_and_f32_clean():
    with jax.enable_x64(True):
        bad = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.zeros((3,), jnp.float64))
    assert _rules(audit_jaxpr(bad, where="f64")) == ["f64-op"]
    good = jax.make_jaxpr(lambda x: x * 2.0)(jnp.zeros((3,), jnp.float32))
    assert audit_jaxpr(good, where="f32") == []


def test_dtype_promotion_against_bf16_policy():
    fn = lambda x: x.astype(jnp.float32) * 2  # noqa: E731
    args = (jnp.zeros((2,), jnp.bfloat16),)
    fs = audit_fn(fn, args, where="promo", policy="bf16")
    assert _rules(fs) == ["dtype-promotion"]
    assert fs[0].severity == "warn"
    # the same program is legal under the f32 policy
    assert audit_fn(fn, args, where="promo", policy="f32") == []


def test_materialized_scores_in_full_attention_only():
    S, D = 600, 8
    q = jax.ShapeDtypeStruct((S, D), jnp.float32)

    def full_attention(q, k, v):
        scores = jnp.einsum("sd,td->st", q, k) / np.sqrt(D).astype("f")
        return jax.nn.softmax(scores, axis=-1) @ v

    fs = audit_fn(full_attention, (q, q, q), where="naive",
                  seq_threshold=512)
    assert "materialized-scores" in _rules(fs)
    with pytest.raises(AssertionError):
        assert_no_materialized_scores(full_attention, (q, q, q),
                                      seq_threshold=512, where="naive")
    # the flash kernels at S=1024 (fwd AND bwd) carry no [S,S]
    assert audit_attention_structure(S=1024) == []


def test_host_callback_detected():
    def cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    assert _rules(audit_fn(cb, (jnp.ones(3),), where="cb")) == \
        ["host-callback"]


def test_collective_flagged_only_when_single_chip():
    fn = jax.vmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")
    args = (jnp.ones((2, 3)),)
    assert _rules(audit_fn(fn, args, where="c", single_chip=True)) == \
        ["collective-in-single-chip"]
    assert audit_fn(fn, args, where="c", single_chip=False) == []


def test_folded_constant_detected_above_threshold():
    big = np.zeros((600, 600), np.float32)  # 1.44 MB > 1 MiB
    fs = audit_fn(lambda x: x + big, (jnp.zeros((600, 600)),),
                  where="const")
    assert _rules(fs) == ["folded-constant"]
    small = np.zeros((8, 8), np.float32)
    assert audit_fn(lambda x: x + small, (jnp.zeros((8, 8)),),
                    where="const") == []


def test_undonated_step_via_cache_records():
    class FakeCache:
        def __init__(self, recs):
            self._recs = recs

        def audit_records(self):
            return list(self._recs)

    aval = jax.ShapeDtypeStruct((2, 2), jnp.float32)
    rec = {"key": ("step", (("policy", "f32"),)), "kind": "step-cache",
           "build": lambda: (lambda p, x: p + x),
           "abstract": (aval, aval), "donate_argnums": (), "mesh": False}
    fs = audit_cache(FakeCache([rec]), expect_donation=True)
    assert _rules(fs) == ["undonated-step"]
    # donation present, or donation not expected (CPU): clean
    assert audit_cache(FakeCache([dict(rec, donate_argnums=(0,))]),
                       expect_donation=True) == []
    assert audit_cache(FakeCache([rec]), expect_donation=False) == []


def test_undonated_kv_cache_via_cache_records():
    class FakeCache:
        def __init__(self, recs):
            self._recs = recs

        def audit_records(self):
            return list(self._recs)

    aval = jax.ShapeDtypeStruct((2, 2), jnp.float32)
    rec = {"key": ("decode", "fp", ((2,), "int32"), "single"),
           "kind": "infer-cache",
           "build": lambda: (lambda p, s: p + s),
           "abstract": (aval, aval), "donate_argnums": (), "mesh": False}
    fs = audit_cache(FakeCache([rec]), expect_donation=True)
    assert _rules(fs) == ["undonated-kv-cache"]
    # prefill entries are held to the same donation contract
    fs = audit_cache(FakeCache([dict(rec, key=("prefill",) + rec["key"][1:])]),
                     expect_donation=True)
    assert _rules(fs) == ["undonated-kv-cache"]
    # donated, not a decode entry, or donation not expected (CPU): clean
    assert audit_cache(FakeCache([dict(rec, donate_argnums=(1,))]),
                       expect_donation=True) == []
    assert audit_cache(FakeCache([dict(rec, key=("output",)
                                       + rec["key"][1:])]),
                       expect_donation=True) == []
    assert audit_cache(FakeCache([rec]), expect_donation=False) == []


def test_replicated_large_leaf_rule():
    """ISSUE 17: on a mesh whose shardings carry a `model` axis, any
    param leaf >= threshold bytes left fully replicated is an error —
    it re-caps per-chip memory at the single-chip bound."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    class FakeCache:
        def __init__(self, recs):
            self._recs = recs

        def audit_records(self):
            return list(self._recs)

    devs = np.asarray(jax.devices())
    if devs.size < 8:
        pytest.skip("needs the 8 forced host devices")
    mesh = Mesh(devs[:8].reshape(2, 4), ("batch", "model"))
    rep = NamedSharding(mesh, P())
    col = NamedSharding(mesh, P(None, "model"))

    def aval(shape, s):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=s)

    def rec(params_aval):
        return {"key": ("output", "fp", ((8, 4), "float32"),
                        ("mesh", ("batch", "model"), (2, 4))),
                "kind": "infer-cache",
                "build": lambda: (lambda p, x: x),
                "abstract": ({"W": params_aval},
                             aval((8, 4), NamedSharding(mesh,
                                                        P("batch")))),
                "donate_argnums": (), "mesh": True,
                "shardings": ({"W": rep}, rep)}

    # large replicated param on a model-axis mesh: flagged as error
    fs = audit_cache(FakeCache([rec(aval((16, 16), rep))]),
                     replicated_leaf_threshold=256)
    assert "replicated-large-leaf" in _rules(fs)
    assert any(f.severity == "error" for f in fs
               if f.rule == "replicated-large-leaf")
    # model-sharded leaf of the same size: clean
    fs = audit_cache(FakeCache([rec(aval((16, 16), col))]),
                     replicated_leaf_threshold=256)
    assert "replicated-large-leaf" not in _rules(fs)
    # below the threshold: clean (biases stay replicated by design)
    fs = audit_cache(FakeCache([rec(aval((16, 16), rep))]),
                     replicated_leaf_threshold=1 << 20)
    assert "replicated-large-leaf" not in _rules(fs)
    # no model axis anywhere in the shardings: rule stays silent
    one_d = Mesh(devs[:8], ("batch",))
    r = rec(aval((16, 16), NamedSharding(one_d, P())))
    r["shardings"] = ({"W": NamedSharding(one_d, P())},
                     NamedSharding(one_d, P("batch")))
    fs = audit_cache(FakeCache([r]), replicated_leaf_threshold=256)
    assert "replicated-large-leaf" not in _rules(fs)


def test_decode_structure_audit_is_clean():
    """The compiled decode step must stay [S,S]-free at a cache length
    where a full-scores materialization is unambiguous (the ISSUE 14
    correctness anchor: decode attends [B,1] queries against the cache,
    so scores carry ONE sequence axis)."""
    from deeplearning4j_tpu.analysis.program_audit import (
        audit_decode_structure)

    assert audit_decode_structure() == []


def test_real_step_cache_keeps_audit_records():
    from deeplearning4j_tpu.models.zoo import lenet5
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(lenet5(), seed=0).init()
    x = np.zeros((2, 1, 28, 28), np.float32)
    net.output(x)
    recs = net.infer_cache.audit_records()
    assert recs, "compiling a serve program must leave an audit record"
    assert audit_cache(net.infer_cache) == []


def test_jaxpr_walk_descends_into_scan():
    def scanned(x):
        def body(c, _):
            big = jnp.einsum("sd,td->st", c, c)  # [S,S] inside the scan
            return c + big[:, :1] * 0, None
        out, _ = jax.lax.scan(body, x, None, length=2)
        return out

    q = jax.ShapeDtypeStruct((600, 600), jnp.float32)
    closed = jax.make_jaxpr(scanned)(q)
    assert any(getattr(e.primitive, "name", "") == "scan"
               for e in closed.jaxpr.eqns)
    shapes = collect_shapes(closed.jaxpr)
    assert (600, 600) in shapes  # found through the scan body
    assert len(list(iter_eqns(closed.jaxpr))) > len(closed.jaxpr.eqns)


# -- repo linter: synthetic sources ------------------------------------------

def test_platform_sniff_rule():
    src = "import jax\nd = jax.devices()\n"
    assert _rules(lint_source(src, "parallel/x.py")) == ["platform-sniff"]
    assert lint_source(src, "nd/platform.py") == []          # the home
    waived = "import jax\nd = jax.devices()  # lint: allow(platform-sniff)\n"
    assert lint_source(waived, "parallel/x.py") == []


def test_wall_clock_rule_scoped_to_clocked_modules():
    src = "import time\nt = time.time()\n"
    assert _rules(lint_source(src, "serving/x.py")) == ["wall-clock"]
    assert _rules(lint_source(src, "reliability/x.py")) == ["wall-clock"]
    assert lint_source(src, "clustering/x.py") == []
    dt = "import datetime\nn = datetime.datetime.now()\n"
    assert _rules(lint_source(dt, "serving/x.py")) == ["wall-clock"]
    mono = "import time\nt = time.monotonic()\n"
    assert lint_source(mono, "serving/x.py") == []


def test_unbounded_network_call_rule_both_directions():
    # direction 1: a serving/ network call with no explicit bound hangs
    # the whole control plane on one dead peer — error
    src = ("import urllib.request\n"
           "r = urllib.request.urlopen(url)\n")
    fs = lint_source(src, "serving/x.py")
    assert _rules(fs) == ["unbounded-network-call"]
    assert fs[0].severity == "error"
    sock = "import socket\ns = socket.create_connection((host, port))\n"
    assert _rules(lint_source(sock, "serving/x.py")) == [
        "unbounded-network-call"]
    # direction 2: explicit timeouts (kwarg or positional), out-of-scope
    # modules, and waived calls are all clean
    bounded = ("import urllib.request\n"
               "r = urllib.request.urlopen(url, timeout=2.0)\n")
    assert lint_source(bounded, "serving/x.py") == []
    sock_kw = ("import socket\n"
               "s = socket.create_connection((host, port), timeout=1.0)\n")
    assert lint_source(sock_kw, "serving/x.py") == []
    sock_pos = ("import socket\n"
                "s = socket.create_connection((host, port), 1.0)\n")
    assert lint_source(sock_pos, "serving/x.py") == []
    assert lint_source(src, "cli/x.py") == []  # bench/CLI clients: out of scope
    waived = ("import urllib.request\n"
              "r = urllib.request.urlopen(url)"
              "  # lint: allow(unbounded-network-call)\n")
    assert lint_source(waived, "serving/x.py") == []


def test_f64_literal_and_default_dtype_rules():
    src = "import numpy as np\na = np.zeros((3,), np.float64)\n"
    fs = lint_source(src, "nn/x.py")
    assert _rules(fs) == ["f64-literal"]
    assert lint_source(src, "clustering/x.py") == []  # host analytics
    bare = "import numpy as np\na = np.zeros((3,))\n"
    fs = lint_source(bare, "optimize/x.py")
    assert _rules(fs) == ["np-default-dtype"]
    assert fs[0].severity == "warn"
    typed = "import numpy as np\na = np.zeros((3,), dtype=np.float32)\n"
    assert lint_source(typed, "optimize/x.py") == []
    kw = 'import numpy as np\na = np.asarray(x, dtype="float64")\n'
    assert _rules(lint_source(kw, "nd/x.py")) == ["f64-literal"]


def test_hardcoded_tunable_rule_both_directions():
    # direction 1: literals at known tunable sites are flagged (warn)
    const = "DEFAULT_TARGET_ROWS = 256\n"
    fs = lint_source(const, "serving/x.py")
    assert _rules(fs) == ["hardcoded-tunable"]
    assert fs[0].severity == "warn"
    table = "_BLOCK_TABLE = {(256, 64): (128, 128, 128, 128)}\n"
    assert _rules(lint_source(table, "nd/x.py")) == ["hardcoded-tunable"]
    call = "b = MicroBatcher(net, max_delay_ms=3.0)\n"
    assert _rules(lint_source(call, "serving/x.py")) == ["hardcoded-tunable"]
    sig = "def f(net, n_slots: int = 4):\n    pass\n"
    assert _rules(lint_source(sig, "serving/x.py")) == ["hardcoded-tunable"]
    # direction 2: the registry home, None-resolved defaults, variable
    # pass-through, and waived deliberate pins are all clean
    assert lint_source(const, "optimize/tunables.py") == []
    clean = ("def f(net, n_slots=None):\n"
             "    b = MicroBatcher(net, max_delay_ms=delay)\n")
    assert lint_source(clean, "serving/x.py") == []
    waived = ("b = ContinuousBatcher(net, n_slots=1)"
              "  # lint: allow(hardcoded-tunable)\n")
    assert lint_source(waived, "cli/x.py") == []


def test_hardcoded_tunable_repo_passes_clean_after_migration():
    # the migration moved every registry-owned constant into
    # optimize/tunables.py; any remaining pin is an explicit waiver
    from deeplearning4j_tpu.analysis.repo_lint import package_root
    fs, _ = lint_package(package_root())
    assert [f for f in fs if f.rule == "hardcoded-tunable"] == []


def test_fault_point_rule_directions():
    doc = {"a.b": "doc"}
    ok = 'from x import faults\nfaults.fire("a.b")\n'
    assert lint_source(ok, "serving/x.py", documented_points=doc) == []
    bad = 'from x import faults\nfaults.fire("zz.q")\n'
    fs = lint_source(bad, "serving/x.py", documented_points=doc)
    assert _rules(fs) == ["fault-point"] and fs[0].severity == "error"
    dyn = "from x import faults\nfaults.fire(name)\n"
    fs = lint_source(dyn, "serving/x.py", documented_points=doc)
    assert _rules(fs) == ["fault-point"] and fs[0].severity == "warn"


def test_fault_point_seeded_in_temp_module(tmp_path):
    mod = tmp_path / "chaos.py"
    mod.write_text(textwrap.dedent("""
        from deeplearning4j_tpu.reliability import faults
        def hot_path():
            faults.fire("totally.undocumented")
    """))
    fs = lint_file(str(mod))
    assert _rules(fs) == ["fault-point"]
    assert "totally.undocumented" in fs[0].message


def test_fault_point_unfired_direction_on_package_walk(tmp_path):
    (tmp_path / "only.py").write_text(
        'from deeplearning4j_tpu.reliability import faults\n'
        'faults.fire("compile")\n')
    fs, n = lint_package(root=str(tmp_path))
    from deeplearning4j_tpu.reliability.faults import DOCUMENTED_POINTS
    unfired = {f.message.split("'")[1] for f in fs
               if f.rule == "fault-point"}
    assert n == 1
    assert unfired == set(DOCUMENTED_POINTS) - {"compile"}


def test_registry_matches_real_fire_sites_both_ways():
    """Machine-readable conformance: the fire("...") sites in the
    package and DOCUMENTED_POINTS are the same set (satellite 3)."""
    import ast

    from deeplearning4j_tpu.analysis.repo_lint import (_fire_sites,
                                                       package_root)
    from deeplearning4j_tpu.reliability.faults import DOCUMENTED_POINTS

    fired = set()
    root = package_root()
    for dirpath, _, files in os.walk(root):
        for fn in files:
            if not fn.endswith(".py") or "faults.py" in fn:
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as fh:
                tree = ast.parse(fh.read())
            fired |= {p for p, _ in _fire_sites(tree, path)
                      if p is not None}
    assert fired == set(DOCUMENTED_POINTS)


def test_prom_family_rule():
    def metrics_src(body):
        return ('FAMILIES = {\n'
                '    "dl4j_x_total": ("counter", ("policy",)),\n'
                '    "dl4j_y": ("gauge", ()),\n'
                '}\n'
                'def emit(p, pol):\n' + textwrap.indent(body, "    "))

    clean = metrics_src('p.counter("dl4j_x_total", "h", 1, '
                        '{"policy": pol})\np.gauge("dl4j_y", "h", 2)\n')
    assert lint_source(clean, "serving/metrics.py") == []
    # direction 1: emitted but undeclared
    fs = lint_source(metrics_src(
        'p.counter("dl4j_x_total", "h", 1, {"policy": pol})\n'
        'p.gauge("dl4j_y", "h", 2)\n'
        'p.gauge("dl4j_rogue", "h", 3)\n'), "serving/metrics.py")
    assert _rules(fs) == ["prom-family"] and "dl4j_rogue" in fs[0].message
    # direction 2: declared but never emitted
    fs = lint_source(metrics_src(
        'p.counter("dl4j_x_total", "h", 1, {"policy": pol})\n'),
        "serving/metrics.py")
    assert _rules(fs) == ["prom-family"] and "never emitted" in \
        fs[0].message
    # type mismatch
    fs = lint_source(metrics_src(
        'p.gauge("dl4j_x_total", "h", 1, {"policy": pol})\n'
        'p.gauge("dl4j_y", "h", 2)\n'), "serving/metrics.py")
    assert any("declared counter" in f.message for f in fs)
    # label drift
    fs = lint_source(metrics_src(
        'p.counter("dl4j_x_total", "h", 1, {"zone": pol})\n'
        'p.gauge("dl4j_y", "h", 2)\n'), "serving/metrics.py")
    assert any("labels" in f.message and "zone" in f.message for f in fs)
    # rule only applies to the metrics module
    assert lint_source(clean, "serving/other.py") == []


def test_real_metrics_module_passes_and_registry_is_closed():
    from deeplearning4j_tpu.serving import metrics
    path = os.path.join(REPO_ROOT, "deeplearning4j_tpu", "serving",
                        "metrics.py")
    assert lint_file(path, os.path.join(REPO_ROOT,
                                        "deeplearning4j_tpu")) == []
    for name, (mtype, labels) in metrics.FAMILIES.items():
        assert mtype in ("counter", "gauge", "histogram")
        assert mtype != "counter" or name.endswith("_total")
        assert isinstance(labels, tuple)


def test_lock_order_cycle_rule():
    cyclic = textwrap.dedent("""
        class C:
            def one(self):
                with self._a_lock:
                    with self._b_lock:
                        pass
            def two(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
    """)
    fs = lint_source(cyclic, "serving/x.py")
    assert _rules(fs) == ["lock-order-cycle"]
    assert "C._a_lock" in fs[0].message
    acyclic = cyclic.replace("def two", "def _two_disabled").split(
        "def _two_disabled")[0]
    assert lint_source(acyclic, "serving/x.py") == []


def test_unguarded_shared_write_rule():
    src = textwrap.dedent("""
        import threading
        class D:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
            def bump(self):
                self._n += 1
            def ok(self):
                with self._lock:
                    self._n = 2
            def set_n_locked(self):
                self._n = 3
    """)
    fs = lint_source(src, "serving/x.py")
    assert _rules(fs) == ["unguarded-shared-write"]
    assert "bump" in fs[0].message and fs[0].severity == "warn"


def test_repo_is_lint_clean():
    """The invariant floor, in-process: zero findings of ANY severity
    over the whole package (the CLI gate re-checks this plus the zoo
    programs in a subprocess, `test_analysis_cli.py`)."""
    findings, n_files = lint_package()
    assert n_files > 100
    assert findings == []
