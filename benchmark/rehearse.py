"""Compile a cell's programs for a described `v5e:2x2`, from the sandbox,
and print what the compiler says of their memory: a later PR sizes a new
cell with it and spends no chip time.

    JAX_PLATFORMS=cpu python3 -m benchmark.rehearse --workload <name>

A compile, never a run: it says nothing of results or times.  It counts one
program at a time, not what else the process keeps on the device (the
trainer keeps `net.params` beside its state; the batcher keeps the slot
table while a prefill runs).  Code of the program that asks which backend
it is on sees the CPU here.  Only one process at a time may load libtpu.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _report(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    gib = 1 << 30
    out = {"program": name,
           "arguments_gib": m.argument_size_in_bytes / gib,
           "outputs_gib": m.output_size_in_bytes / gib,
           "aliased_gib": m.alias_size_in_bytes / gib,
           "temporaries_gib": m.temp_size_in_bytes / gib,
           "code_gib": m.generated_code_size_in_bytes / gib}
    out["total_gib"] = (out["arguments_gib"] + out["outputs_gib"]
                        - out["aliased_gib"] + out["temporaries_gib"])
    print(json.dumps(out), flush=True)
    return out


def _shaped(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def train(cell, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import families
    from deeplearning4j_tpu.nn.multilayer import init_params
    from deeplearning4j_tpu.optimize.updater import init_updater
    from deeplearning4j_tpu.parallel.data_parallel import (TrainState,
                                                           make_dp_train_step)

    chips = int(cell.cell["chips"])
    conf = families.of(cell.cfg).program.build_conf(cell.cfg)
    mesh = Mesh(topo.devices[:chips], ("dp",))
    rows, seq = int(cell.mix["rows"]) * chips, int(cell.mix["seq"])

    def state(key):
        p = init_params(conf, key)
        return TrainState(p, init_updater(p), jnp.asarray(0, jnp.int32))

    key = jax.random.PRNGKey(0)
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    args = (_shaped(jax.eval_shape(state, key), rep),
            jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=split),
            jax.ShapeDtypeStruct((rows * seq,), jnp.int32, sharding=split),
            _shaped(key, rep))
    _report("dp train step", make_dp_train_step(conf, mesh, "dp").lower(*args).compile())


def generate(cell, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import families
    from deeplearning4j_tpu.nn import decode
    from deeplearning4j_tpu.nn.multilayer import init_params

    conf = families.of(cell.cfg).program.build_conf(cell.cfg)
    srv = cell.mix["server"]
    one = SingleDeviceSharding(topo.devices[0])
    key = jax.random.PRNGKey(0)
    params = _shaped(jax.eval_shape(lambda k: init_params(conf, k), key), one)

    def table(rows):
        return _shaped(jax.eval_shape(
            lambda: decode.init_state(conf, rows, int(srv["max_seq"]))), one)

    n = int(srv["n_slots"])
    ids = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one)
    step = jax.jit(lambda p, s, t, q: decode.decode_step(conf, p, s, t, q),
                   donate_argnums=(1,))
    _report(f"decode step, {n} slots", step.lower(params, table(n), ids, ids).compile())
    for bucket in srv["prompt_buckets"]:
        fill = jax.jit(lambda p, s, t, q: decode.prefill(conf, p, s, t, q),
                       donate_argnums=(1,))
        _report(f"prefill, 1 row of {bucket}", fill.lower(
            params, table(1),
            jax.ShapeDtypeStruct((1, int(bucket)), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)).compile())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies

    from benchmark import run

    if jax.default_backend() != "cpu":
        raise SystemExit("run the rehearsal with JAX_PLATFORMS=cpu")
    jax.config.update("jax_enable_compilation_cache", False)
    cell = run.load_cell(args.workload, rehearse=False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    {"train": train, "generate": generate}[cell.mix["job"]](cell, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
