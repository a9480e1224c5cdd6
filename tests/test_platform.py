"""Device selection that says what it found, one process per chip, and a
compile cache placed from outside (`nd/platform.py` and its callers)."""

import json
import os
import re
import subprocess
import sys

import jax
import pytest

from deeplearning4j_tpu.cli.driver import ChipSlots, ReplicaProcess, main
from deeplearning4j_tpu.nd import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIN_KEYS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
            "TPU_PROCESS_BOUNDS", "TPU_CHIPS_PER_HOST_BOUNDS",
            "TPU_HOST_BOUNDS")
# a stand-in replica: prints the environment it was started with where a
# real one prints its startup JSON
PRINT_ENV = [sys.executable, "-c",
             "import json, os; print(json.dumps({k: os.environ.get(k) "
             f"for k in {PIN_KEYS!r}}}), flush=True)"]


# ------------------------------------------------------------ describe

def test_describe_names_the_device():
    d = platform.describe()
    assert d["platform"] == "cpu" and d["device_kind"] == "cpu"
    assert d["device_count"] == len(jax.devices()) == 8
    assert d["chip"] is None  # nobody pinned this process


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cli_train_json_says_which_device_ran(tmp_path, capsys):
    assert main(["train", "--zoo", "mlp:hidden=8", "--input", "iris:30",
                 "--output", str(tmp_path / "ckpt")]) == 0
    out = _last_json(capsys)
    assert (out["platform"], out["device_kind"], out["device_count"]) == \
        ("cpu", "cpu", 8)


def test_cli_warmup_and_tune_json_say_which_device_ran(tmp_path, capsys):
    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.optimize import tunables

    conf = tmp_path / "conf.json"
    conf.write_text(mlp(4, [8], 3).to_json())
    assert main(["warmup", "--model", str(conf), "--compile-cache",
                 str(tmp_path / "cc"), "--shapes", "4"]) == 0
    assert _last_json(capsys)["platform"] == "cpu"
    try:
        assert main(["tune", "--model", str(conf), "--groups", "serve",
                     "--rounds", "1"]) == 0
    finally:
        # `tune` installs its table for the whole process: left in place,
        # its bucket ladder reached every file this worker ran afterwards
        tunables.clear()
    out = _last_json(capsys)
    assert out["platform"] == "cpu" and out["device_count"] == 8


# ------------------------------------------------------- compile cache

@pytest.fixture
def cache_config():
    """Put JAX's own setting back after a test has placed the cache."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_left_to_jax_where_the_variable_is_set(
        monkeypatch, tmp_path, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.place_compile_cache() == str(tmp_path)
    # no directory set in code: JAX reads the variable itself
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_fixed_path_where_the_variable_is_unset(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = platform.place_compile_cache()
    assert platform.place_compile_cache() == first
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    # git-ignored, and not built from tempfile, a pid or the time: a child
    # started with the same environment finds the same path
    child = subprocess.run(
        [sys.executable, "-c", "from deeplearning4j_tpu.nd import platform; "
                               "print(platform.place_compile_cache())"],
        env={**os.environ, "PYTHONPATH": REPO}, capture_output=True,
        text=True, timeout=120)
    assert child.stdout.strip() == first, child.stderr[-1000:]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# -------------------------------------------------- one process per chip

def test_chip_env_names_one_chip_and_differs_between_siblings():
    envs = [platform.chip_env(i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(v == "1,1,1" for e in envs for k, v in e.items()
               if k != "TPU_VISIBLE_CHIPS")
    assert all(set(e) == set(PIN_KEYS) for e in envs)


def test_chip_env_names_a_run_of_chips_for_a_mesh():
    pair = platform.chip_env(2, 2)
    assert pair["TPU_VISIBLE_CHIPS"] == "2,3"
    assert pair["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    assert pair["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    assert pair["TPU_PROCESS_BOUNDS"] == pair["TPU_HOST_BOUNDS"] == "1,1,1"
    assert platform.chip_env(0, 4)["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert platform.chip_env(0, 4)["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
    with pytest.raises(ValueError, match="not 3"):
        platform.chip_env(0, 3)


@pytest.mark.parametrize("argv, chips", [
    (["serve", "--model", "m"], 1),
    (["serve", "--mesh", "all"], 1),         # every device the slot gives
    (["serve", "--mesh", "--port", "0"], 1),  # the bare flag
    (["serve", "--mesh", "batch=2"], 2),
    (["serve", "--mesh", "batch=2,model=2", "--port", "0"], 4),
    (["serve", "--mesh", "batch=-1,model=2"], 2),
])
def test_a_replica_is_given_the_chips_its_mesh_names(argv, chips):
    from deeplearning4j_tpu.cli.driver import _chips_per_replica

    assert _chips_per_replica(argv) == chips


def test_replica_is_started_with_a_chip_of_its_own():
    a, b = ReplicaProcess(PRINT_ENV, chip=0), ReplicaProcess(PRINT_ENV,
                                                             chip=3)
    try:
        env_a, env_b = a.wait_ready(), b.wait_ready()
    finally:
        assert a.wait(10) == 0 and b.wait(10) == 0
    assert env_a == platform.chip_env(0) and env_b == platform.chip_env(3)
    assert env_a != env_b


def test_unpinned_replica_inherits_the_parent_environment(monkeypatch):
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    r = ReplicaProcess(PRINT_ENV)
    try:
        assert r.wait_ready()["TPU_VISIBLE_CHIPS"] is None
    finally:
        assert r.wait(10) == 0


def test_replicas_with_a_mesh_get_disjoint_runs_of_chips():
    """`serve --replicas 2 --mesh batch=2`: each replica's environment
    names the two chips its mesh spans, and no chip twice."""
    slots = ChipSlots()
    one = slots.spawn(PRINT_ENV)  # a one-chip replica holds chip 0
    a, b = slots.spawn(PRINT_ENV, 2), slots.spawn(PRINT_ENV, 2)
    try:
        envs = [r.wait_ready() for r in (one, a, b)]
    finally:
        assert [r.wait(10) for r in (one, a, b)] == [0, 0, 0]
    # runs start on a multiple of their length, so the pair skips chip 1
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "2,3", "4,5"]
    assert envs[1] == platform.chip_env(2, 2)


def test_chip_slots_reuse_the_chip_of_a_replica_that_died():
    sleeper = [sys.executable, "-c", "import time; time.sleep(60)"]
    slots = ChipSlots()
    replicas = [slots.spawn(sleeper) for _ in range(3)]
    try:
        assert [r.chip for r in replicas] == [0, 1, 2]
        replicas[1].kill()
        replicas[1].wait(10)
        replicas.append(slots.spawn(sleeper))  # the replacement
        assert replicas[-1].chip == 1
        replicas.append(slots.spawn(sleeper))
        assert replicas[-1].chip == 3
    finally:
        for r in replicas:
            r.kill()
            r.wait(10)


def test_local_launcher_gives_each_worker_its_own_chip(tmp_path):
    from deeplearning4j_tpu.scaleout.provision import HostSpec, LocalLauncher

    launcher = LocalLauncher(str(tmp_path / "fleet"))
    out = tmp_path / "env.jsonl"
    entry = (f"{sys.executable} -c \"import json, os; "
             f"open({str(out)!r}, 'a').write(json.dumps("
             f"{{k: os.environ.get(k) for k in {PIN_KEYS!r} + ('WORKER',)}}"
             f") + chr(10))\"")
    # a command that is no worker comes first and takes no chip; the
    # workers are pinned by the process id their env names, in any order
    launcher.start(HostSpec(address="10.0.0.9"), entry, {"WORKER": "none"},
                   "w")
    for i in (2, 0, 1):
        launcher.start(HostSpec(address=f"10.0.0.{i}"), entry,
                       {"WORKER": str(i), "JAX_PROCESS_ID": str(i)}, "w")
    assert launcher.wait(timeout=60) == [0, 0, 0, 0]
    rows = sorted((json.loads(l) for l in out.read_text().splitlines()),
                  key=lambda r: r["WORKER"])
    assert [r["TPU_VISIBLE_CHIPS"] for r in rows] == ["0", "1", "2", None]
    assert all(r["TPU_PROCESS_BOUNDS"] == "1,1,1" for r in rows[:3])


_ROUTER_PARENT = r"""
import io, json, os, signal, sys, threading, time
from deeplearning4j_tpu.cli import driver
from jax._src import xla_bridge

ready = threading.Event()
real = sys.stdout


class Tee(io.TextIOBase):
    def write(self, s):
        real.write(s)
        real.flush()
        if '"replica_pids"' in s:  # the router's startup line: fleet is up
            ready.set()
        return len(s)


def look():
    ready.wait(240)
    print(json.dumps({"backends": sorted(xla_bridge._backends),
                      "initialized": xla_bridge.backends_are_initialized()}),
          file=sys.stderr, flush=True)
    # `serve` prints its startup line before it takes SIGTERM for itself: a
    # signal in between ends the router by default and orphans the replicas
    deadline = time.monotonic() + 60
    while (signal.getsignal(signal.SIGTERM) is signal.SIG_DFL
           and time.monotonic() < deadline):
        time.sleep(0.01)
    os.kill(os.getpid(), signal.SIGTERM)


threading.Thread(target=look, daemon=True).start()
sys.stdout = Tee()
sys.exit(driver.main(["serve", "--model", sys.argv[1], "--replicas", "2",
                      "--shapes", "2"]))
"""


def test_router_parent_initialises_no_backend(tmp_path):
    """`serve --replicas 2`: both replicas up and pinned to different
    chips, and the parent that started them has asked JAX for no device —
    on a chip host it would otherwise hold every chip its children need."""
    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import checkpoint

    net = MultiLayerNetwork(mlp(4, [8], 3), seed=0).init()
    ckpt = str(tmp_path / "ckpt")
    checkpoint.save(ckpt, net.params, conf=net.conf)
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": re.sub(         # the rig's flags, one device
               r"--xla_force_host_platform_device_count=\d+", "",
               os.environ["XLA_FLAGS"])}
    proc = subprocess.run([sys.executable, "-c", _ROUTER_PARENT, ckpt],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    startup, drained = (json.loads(l) for l in proc.stdout.splitlines())
    assert [d["chip"] for d in startup["replica_devices"]] == ["0", "1"]
    assert all(d["platform"] == "cpu" for d in startup["replica_devices"])
    assert drained["replica_exit_codes"] == [0, 0]
    parent = next(json.loads(l) for l in proc.stderr.splitlines()
                  if l.startswith('{"backends"'))
    assert parent == {"backends": [], "initialized": False}
