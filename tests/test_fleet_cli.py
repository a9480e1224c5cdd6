"""The fleet's first acceptance smoke as a command (ISSUE 11), apart from
`test_fleet.py`'s in-process chaos suite: `cli serve --replicas` as real
processes, SIGKILL under load, heal from the warm disk cache, SIGTERM drain.
Short timeouts and a watchdog.  The multi-host one (`cli agent`) is in
`test_fleet_multihost_cli.py`, a worker's work for most of a minute."""

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


def test_cli_fleet_sigkill_heals_with_warm_cache_and_clean_answers(tmp_path):
    """ISSUE 11 acceptance: SIGKILL one of 2 supervised replicas under
    load -> zero incorrect responses (every client sees a correct
    answer or a clean 5xx), the supervisor restores the fleet with
    fresh_compiles == 0 on the respawn (shared warm disk cache), router
    counters reconcile with client-observed outcomes, SIGTERM drain
    exits 0."""
    net = _net()
    ckpt = str(tmp_path / "model")
    cache = str(tmp_path / "cache")
    checkpoint.save(ckpt, net.params, conf=net.conf)
    x = _x(2, seed=1)
    expected = np.asarray(net.output(x))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.cli", "warmup",
         "--model", ckpt, "--compile-cache", cache, "--shapes", "1,2"],
        check=True, capture_output=True, cwd=repo, env=env, timeout=300)
    proc = subprocess.Popen(
        [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve",
         "--model", ckpt, "--compile-cache", cache, "--shapes", "1,2",
         "--replicas", "2", "--min-replicas", "2", "--max-replicas", "2",
         "--hedge", "--port", "0", "--max-delay-ms", "2",
         "--drain-timeout", "10"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=repo, env=env)
    try:
        watchdog = threading.Timer(240.0, proc.kill)
        watchdog.start()
        try:
            summary = json.loads(proc.stdout.readline())
        finally:
            watchdog.cancel()
        url = summary["url"]
        assert summary["fresh_compiles"] == [0, 0]
        assert summary["hedge"] is True
        assert len(summary["replica_pids"]) == 2
        victim = summary["replica_pids"][0]

        # open-ish loop: 4 client threads hammer while the kill lands;
        # every answer must be bitwise-correct or a clean JSON 5xx
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
        time.sleep(0.3)                      # load established
        os.kill(victim, signal.SIGKILL)      # chaos
        healed = None
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            try:
                code, text = _http(url + "/v1/stats", timeout=10)
                st = json.loads(text)
            except Exception:  # noqa: BLE001
                time.sleep(0.2)
                continue
            fleet = st.get("fleet", {})
            if (st.get("healthy_replicas", 0) >= 2
                    and fleet.get("restarts_total", 0) >= 1):
                healed = st
                break
            time.sleep(0.2)
        time.sleep(0.3)                      # a little post-heal traffic
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        assert healed is not None, "fleet never healed within 60s"
        assert healed["fleet"]["restarts_total"] >= 1
        # the respawned replica came up from the warm shared disk cache
        respawned = [s for s in healed["fleet"]["slots"]
                     if s["restarts"] >= 1]
        assert respawned and all(s["fresh_compiles"] == 0
                                 for s in respawned), respawned
        # zero incorrect responses, and the clients actually worked
        assert outcomes["bad"] == [], outcomes["bad"][:5]
        assert outcomes["ok"] > 0

        # counters reconcile with client-observed outcomes: every
        # request is either in the ok-latency histogram or unroutable
        code, text = _http(url + "/v1/stats", timeout=10)
        st = json.loads(text)
        ok_count = sum(p["latency_hist_s"]["count"]
                       for p in st["priorities"].values())
        total = sum(p["requests"] for p in st["priorities"].values())
        assert ok_count == outcomes["ok"]
        assert st["unroutable"] == outcomes["err5xx"]
        assert total == ok_count + st["unroutable"]

        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=180)
        assert proc.returncode == 0, (out, err)
        drained = json.loads(out.strip().splitlines()[-1])
        assert drained["drained"] is True
        assert drained["restarts"] >= 1
        assert all(rc == 0 for rc in drained["replica_exit_codes"])
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
