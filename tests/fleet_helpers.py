"""What `test_fleet.py` and the two `test_fleet_*cli.py` share: the tiny warmed
net, its inputs, a JSON request, and the fault plan put back round every case.  No test
file: nothing here is collected."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.reliability import faults

N_IN, N_OUT = 6, 3


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _net(seed=0):
    net = MultiLayerNetwork(mlp(n_in=N_IN, hidden=[8], n_out=N_OUT,
                                lr=0.05), seed=seed).init()
    net.warmup([1, 2, 4])
    return net


def _x(rows, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(rows, N_IN).astype(np.float32)


def _http(url, body=None, timeout=30):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()
