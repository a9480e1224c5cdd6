"""The multi-host fleet's acceptance smoke as a command (ISSUE 20): two `cli
agent` processes and `cli serve --agent`, a whole host SIGKILLed and the
survivor partitioned under load.  Alone in its file: it is a worker's work
for most of a minute (`test_fleet_cli.py` has the one-host smoke)."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np

from deeplearning4j_tpu.parallel import checkpoint
from fleet_helpers import _clean_faults, _http, _net, _x    # noqa: F401  (a fixture)


def test_cli_multihost_agent_sigkill_and_partition_heal_acceptance(tmp_path):
    """ISSUE 20 acceptance: the fleet lives on two loopback agent
    processes (cold caches, warming over the cachesync wire from the
    control-plane host).  SIGKILL one whole agent mid-load AND inject a
    lease partition (`agent.partition`) on the survivor's poll path.
    Every response is a bitwise-correct 200 or a clean JSON 5xx, the
    failover respawn reaches the survivor with fresh_compiles == 0 and
    cache_fetch_hits > 0 (warmed over the wire, never compiled), the
    reconcile never double-spawns (agent /a/replicas live count ==
    supervisor intent), and SIGTERM drain exits 0."""
    net = _net()
    ckpt = str(tmp_path / "model")
    warm = str(tmp_path / "warm")
    checkpoint.save(ckpt, net.params, conf=net.conf)
    x = _x(2, seed=1)
    expected = np.asarray(net.output(x))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.cli", "warmup",
         "--model", ckpt, "--compile-cache", warm, "--shapes", "1,2"],
        check=True, capture_output=True, cwd=repo, env=env, timeout=300)

    def start_agent(name):
        p = subprocess.Popen(
            [sys.executable, "-m", "deeplearning4j_tpu.cli", "agent",
             "--port", "0", "--compile-cache", str(tmp_path / name),
             "--max-replicas", "2"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=repo, env=env)
        watchdog = threading.Timer(120.0, p.kill)
        watchdog.start()
        try:
            startup = json.loads(p.stdout.readline())
        finally:
            watchdog.cancel()
        return p, startup["url"]

    agent_procs = []
    proc = None
    replica_pids = []
    try:
        a1, u1 = start_agent("cache-a")
        agent_procs.append(a1)
        a2, u2 = start_agent("cache-b")
        agent_procs.append(a2)
        # the armed partition plan lives in the SERVE process: the fault
        # point fires twice per supervisor tick (once per agent), so
        # hits 61..72 partition the survivor for ~6 consecutive beats a
        # few seconds into the run — long enough to trip the lease
        # (3 misses), short enough to heal before the failover deadline
        proc = subprocess.Popen(
            [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve",
             "--model", ckpt, "--compile-cache", warm, "--shapes", "1,2",
             "--replicas", "2", "--min-replicas", "2",
             "--max-replicas", "2", "--agent", u1, "--agent", u2,
             "--agent-failover", "4", "--port", "0",
             "--max-delay-ms", "2", "--drain-timeout", "10"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=repo,
            env={**env, "DL4J_FAULT_PLAN": "agent.partition=raise@61x12"})
        watchdog = threading.Timer(240.0, proc.kill)
        watchdog.start()
        try:
            summary = json.loads(proc.stdout.readline())
        finally:
            watchdog.cancel()
        url = summary["url"]
        replica_pids = list(summary["replica_pids"])
        assert summary["agents"] == [u1, u2]
        # both initial replicas warmed over the wire from the control
        # plane's cache server: cold agent disks, zero fresh compiles
        assert summary["fresh_compiles"] == [0, 0]

        outcomes = {"ok": 0, "err5xx": 0, "bad": []}
        lock = threading.Lock()
        stop = threading.Event()

        def client():
            body = {"features": x.tolist()}
            while not stop.is_set():
                try:
                    code, text = _http(url + "/v1/predict", body,
                                       timeout=30)
                except Exception as e:  # noqa: BLE001 — transport drop
                    with lock:
                        outcomes["bad"].append(f"transport: {e}")
                    continue
                if code == 200:
                    out = np.asarray(json.loads(text)["output"])
                    good = np.allclose(out, expected, atol=1e-5)
                    with lock:
                        if good:
                            outcomes["ok"] += 1
                        else:
                            outcomes["bad"].append("wrong output")
                elif 500 <= code < 600:
                    json.loads(text)  # clean structured error, not junk
                    with lock:
                        outcomes["err5xx"] += 1
                else:
                    with lock:
                        outcomes["bad"].append(f"code {code}")

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.5)                      # load established
        a1.kill()                            # chaos 1: a whole host dies
        healed = None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            try:
                code, text = _http(url + "/v1/stats", timeout=10)
                st = json.loads(text)
            except Exception:  # noqa: BLE001
                time.sleep(0.2)
                continue
            fleet = st.get("fleet", {})
            survivor = next((a for a in fleet.get("agents", [])
                             if a["url"] == u2), {})
            if (st.get("healthy_replicas", 0) >= 2
                    and fleet.get("failovers_total", 0) >= 1
                    and survivor.get("partitions_total", 0) >= 1
                    and survivor.get("state") == "leased"):
                healed = st
                break
            time.sleep(0.2)
        time.sleep(0.5)                      # post-heal traffic
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        assert healed is not None, \
            "fleet never healed from SIGKILL + partition within 120s"
        fleet = healed["fleet"]
        # chaos 2 (the armed plan) really fired AND healed: the survivor
        # was partitioned, re-leased, and reconciled its replicas back
        survivor = next(a for a in fleet["agents"] if a["url"] == u2)
        assert survivor["reconciles_total"] >= 1
        # the failover respawn warmed over the cachesync wire on the
        # cold surviving host: fetched, never compiled
        respawned = [s for s in fleet["slots"] if s["restarts"] >= 1]
        assert respawned, fleet["slots"]
        assert all(s["fresh_compiles"] == 0 for s in respawned), respawned
        assert all(s["cache_fetch_hits"] > 0 for s in respawned), respawned
        # zero double-spawns after reconcile: the survivor's ACTUAL live
        # replica count equals the supervisor's intent
        running = [s for s in fleet["slots"] if s["state"] == "running"]
        assert len(running) == 2
        assert all(s["agent"] == u2 for s in running), running
        code, text = _http(u2 + "/a/replicas", timeout=10)
        assert code == 200
        live = [r for r in json.loads(text)["replicas"] if r["alive"]]
        assert len(live) == len(running) == 2
        # every client saw a bitwise-correct answer or a clean 5xx
        assert outcomes["bad"] == [], outcomes["bad"][:5]
        assert outcomes["ok"] > 0

        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=180)
        assert proc.returncode == 0, (out, err)
        drained = json.loads(out.strip().splitlines()[-1])
        assert drained["drained"] is True
        assert all(rc == 0 for rc in drained["replica_exit_codes"])
        proc = None
        # the surviving agent drains cleanly too
        a2.send_signal(signal.SIGTERM)
        out2, err2 = a2.communicate(timeout=60)
        assert a2.returncode == 0, (out2, err2)
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.communicate()
        for p in agent_procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
        # the SIGKILLed agent's replica child outlives its parent: reap
        # it so nothing leaks past the test
        for pid in replica_pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
