"""CLI driver: train / test / predict subcommands.

Parity: reference `cli/subcommands/Train.java:33-58` (flags: --input
--model --output --runtime --properties), `Test.java`, `Predict.java`, and
the missing `CommandLineInterfaceDriver` the reference's `bin/dl4j` points
at — implemented for real here.

`--runtime mesh` trains data-parallel over every visible device via the
device-mesh trainer (the reference's {local,Spark,Hadoop} runtimes collapse
into local vs mesh on TPU: one binary, XLA collectives do the rest).
"""

from __future__ import annotations

import argparse
import csv
import itertools
import json
import math
import os
import sys
import threading
from typing import List, Optional

from deeplearning4j_tpu.nd import platform


def _parse_properties(props: Optional[str]) -> dict:
    """`--properties k=v,k2=v2` → dict (Hadoop-style Configuration)."""
    out = {}
    if props:
        for pair in props.split(","):
            if not pair:
                continue
            k, _, v = pair.partition("=")
            out[k.strip()] = v.strip()
    return out


def _load_model(model_dir):
    """Checkpoint dir -> initialized MultiLayerNetwork with restored params."""
    if not model_dir:
        raise SystemExit("this command requires --model <checkpoint dir>")
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import checkpoint

    conf = checkpoint.load_conf(model_dir)
    net = MultiLayerNetwork(conf).init()
    params, _, _ = checkpoint.load(model_dir, like_params=net.params)
    net.params = params
    return net


def _attach_compile_cache(net, args) -> None:
    """--compile-cache DIR: persistent on-disk program store shared by
    the train-step and serve-path caches (see optimize/persist.py).
    --cache-from URL (repeatable) adds a remote-then-compile fallback:
    a locally-absent entry is fetched from a peer agent or cache server
    over the cachesync wire before being compiled."""
    if getattr(args, "compile_cache", None):
        store = net.set_compile_cache(args.compile_cache)
        sources = getattr(args, "cache_from", None)
        if sources:
            from deeplearning4j_tpu.serving.cachesync import CacheFetcher

            store.set_remote(CacheFetcher(list(sources)))


def _disk_stats(net) -> dict:
    """Disk-cache stats block for the CLI JSON (zeros when no store is
    attached, so the schema is stable either way)."""
    cs, ic = net.step_cache.stats, net.infer_cache.stats
    out = {
        "disk_hits": cs.disk_hits + ic.disk_hits,
        "disk_write_seconds": round(
            cs.disk_write_seconds + ic.disk_write_seconds, 3),
        "deserialize_seconds": round(
            cs.deserialize_seconds + ic.deserialize_seconds, 3),
        "fetch_hits": cs.fetch_hits + ic.fetch_hits,
        "fetch_corrupt": cs.fetch_corrupt + ic.fetch_corrupt,
    }
    store = net.step_cache.persist or net.infer_cache.persist
    if store is not None:
        out["dir"] = store.directory
        out["entries"] = len(store)
        out["bytes"] = store.total_bytes()
    return out


def _zoo_conf(spec: str, data):
    """--zoo 'name[:k=v,...]' -> MultiLayerConfiguration, sized from the
    loaded dataset where needed (vocab for char models, dims for mlp)."""
    from deeplearning4j_tpu.models import zoo

    name, _, props = spec.partition(":")
    kw = dict(kv.split("=", 1) for kv in props.split(",") if kv)
    lr = float(kw.get("lr", 0.05))
    iters = int(kw.get("iterations", kw.get("iters", 1)))
    if name == "lenet5":
        return zoo.lenet5(lr=lr, iterations=iters)
    if name == "mlp":
        hidden = [int(h) for h in kw.get("hidden", "64").split("x")]
        return zoo.mlp(n_in=data.features.shape[-1], hidden=hidden,
                       n_out=data.labels.shape[-1], lr=lr)
    if name == "char_lstm":
        vocab = getattr(data, "vocab_size", data.features.shape[-1])
        return zoo.char_lstm(vocab, hidden=int(kw.get("hidden", 128)),
                             n_layers=int(kw.get("layers", 1)), lr=lr,
                             iterations=iters)
    if name == "char_transformer":
        vocab = getattr(data, "vocab_size", data.features.shape[-1])
        seq = getattr(data, "seq_len", 0) or int(kw.get("seq_len", 256))
        return zoo.char_transformer(
            vocab, d_model=int(kw.get("d_model", 128)),
            n_blocks=int(kw.get("blocks", 2)),
            n_heads=int(kw.get("heads", 4)), max_seq_len=seq,
            lr=float(kw.get("lr", 1e-3)), iterations=iters)
    if name == "vgg_cifar10":
        return zoo.vgg_cifar10(lr=lr, iterations=iters,
                               width=int(kw.get("width", 64)))
    if name == "dbn":
        hidden = [int(h) for h in kw.get("hidden", "32x16").split("x")]
        return zoo.dbn(n_in=data.features.shape[-1], hidden=hidden,
                       n_out=data.labels.shape[-1], lr=lr,
                       iterations=int(kw.get("iterations",
                                             kw.get("iters", 30))),
                       k=int(kw.get("k", 1)),
                       finetune_iterations=int(kw.get("finetune", 60)))
    if name == "deep_autoencoder":
        hidden = [int(h) for h in kw.get("hidden", "64x16").split("x")]
        return zoo.deep_autoencoder(
            n_in=data.features.shape[-1], hidden=hidden, lr=lr,
            iterations=int(kw.get("iterations", kw.get("iters", 20))),
            finetune_iterations=int(kw.get("finetune", 100)))
    raise SystemExit(f"unknown --zoo model '{name}' (choose lenet5, mlp, "
                     "char_lstm, char_transformer, vgg_cifar10, dbn, "
                     "deep_autoencoder)")


def cmd_train(args) -> int:
    from deeplearning4j_tpu.cli.schemes import load_input
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import checkpoint

    data = load_input(args.input, label_column=args.label_column,
                      num_examples=args.num_examples)
    if getattr(args, "zoo", None):
        conf = _zoo_conf(args.zoo, data)
    elif args.model:
        with open(args.model) as f:
            conf = MultiLayerConfiguration.from_json(f.read())
    else:
        raise SystemExit("train needs --model <conf.json> or --zoo <name>")
    from deeplearning4j_tpu.nn.conf import LayerType
    if (LayerType(str(conf.confs[0].layer_type)) == LayerType.EMBEDDING
            and data.features.ndim == 3):
        # embedding layers consume integer ids [B,T]; text-scheme input
        # arrives one-hot [B,T,V] — convert by mechanism, not model name
        from deeplearning4j_tpu.datasets.dataset import DataSet
        ds = DataSet(data.features.argmax(-1).astype("int32"), data.labels,
                     data.label_rows)
        for attr in ("vocab_size", "seq_len", "index_to_char"):
            if hasattr(data, attr):
                setattr(ds, attr, getattr(data, attr))
        data = ds
    if args.normalize:
        data = data.normalize_zero_mean_unit_variance()
    if getattr(args, "scale_01", False):
        data = data.scale_to_unit()

    props = _parse_properties(args.properties)
    epochs = int(props.get("epochs", "1"))
    # reconstruction nets are detected by MECHANISM (output loss), not by
    # the --zoo spelling, so a deep-AE conf loaded via --model JSON gets
    # the same treatment: fit/score against the inputs, and Hinton's
    # pretrain->unroll->finetune recipe when it's a pretrainable AE stack
    from deeplearning4j_tpu.nd.losses import LossFunction
    out_lf = conf.conf(conf.n_layers - 1).loss_function
    reconstruction = (LossFunction(str(out_lf))
                      == LossFunction.RECONSTRUCTION_CROSSENTROPY)
    deep_ae = reconstruction and conf.pretrain and any(
        LayerType(str(c.layer_type)) == LayerType.AUTOENCODER
        for c in conf.confs)
    if args.runtime == "mesh" and (deep_ae or conf.pretrain):
        raise SystemExit(
            "pretraining workflows (dbn/deep_autoencoder) need "
            "--runtime local: the mesh data-parallel step is "
            "gradient-only and would silently skip layer-wise pretraining")
    ckpt_dir = getattr(args, "checkpoint_dir", None)
    ckpt_every = int(props.get("checkpoint_every", "10"))
    zero1 = bool(getattr(args, "zero1", False))
    mesh_spec = getattr(args, "mesh", None)
    if mesh_spec is not None:
        args.runtime = "mesh"  # --mesh implies the mesh runtime
    if zero1 and args.runtime != "mesh":
        raise SystemExit("--zero1 shards updater state over the dp mesh "
                         "axis; it requires --runtime mesh (or --mesh)")
    if ckpt_dir and (deep_ae or conf.pretrain):
        raise SystemExit(
            "--checkpoint-dir does not support pretraining recipes "
            "(dbn/deep_autoencoder): their multi-phase schedule is not "
            "batch-cursor resumable")
    import time as _time
    t_train = _time.perf_counter()
    n_trained = data.num_examples() * epochs
    if args.runtime == "mesh":
        from deeplearning4j_tpu.nd.platform import device_count
        from deeplearning4j_tpu.parallel.data_parallel import (
            DataParallelTrainer)
        from deeplearning4j_tpu.parallel.mesh import make_mesh

        net = MultiLayerNetwork(conf).init()
        _attach_compile_cache(net, args)
        n_dev = device_count()
        plan = None
        if mesh_spec is not None:
            from deeplearning4j_tpu.parallel.plan import (
                ShardPlan, parse_mesh_spec, plan_mesh)

            plan = ShardPlan(mesh=plan_mesh(parse_mesh_spec(mesh_spec)))
            mesh = plan.mesh
            dp_rows = plan.rows
        else:
            mesh = make_mesh({"dp": n_dev})
            dp_rows = n_dev
        batch = int(props.get("batch", "128"))
        n = data.num_examples()
        if n < dp_rows:
            raise SystemExit(
                f"mesh runtime needs >= {dp_rows} examples (one per row "
                f"shard), got {n}")
        remainder = sum(b.num_examples() % dp_rows
                        for b in data.batch_by(batch))
        if remainder:
            # remainder batches run through the pad-and-mask step (see
            # DataParallelTrainer._step_padded) in every mode — zero1 and
            # plan steps included: every example still trains, at the
            # cost of one extra compiled variant
            print(f"note: {remainder} examples/epoch take the padded-batch "
                  f"path to stay divisible by the {dp_rows}-row dp axis",
                  file=sys.stderr)
        if plan is not None:
            trainer = DataParallelTrainer(
                net, mode=props.get("mode", "sync"), zero1=zero1,
                plan=plan)
        else:
            trainer = DataParallelTrainer(
                net, mesh, mode=props.get("mode", "sync"), zero1=zero1)
        if ckpt_dir:
            # crash-safe + elastic: full TrainState (params, updater
            # moments, step, RNG key, batch cursor) checkpoints through
            # parallel/checkpoint.py; the saved arrays are gathered, so
            # a rerun resumes on ANY device count
            from deeplearning4j_tpu.reliability import TrainingInterrupted

            try:
                trainer.fit(data.batch_by(batch), epochs=epochs,
                            checkpoint_dir=ckpt_dir,
                            checkpoint_every_n_batches=ckpt_every)
            except TrainingInterrupted as e:
                print(json.dumps({"interrupted": True,
                                  "checkpoint": ckpt_dir,
                                  "detail": str(e)}), flush=True)
                return 0
        else:
            trainer.fit(data.batch_by(batch), epochs=epochs)
        resumed_from_step = trainer.resumed_from_step
        ckpt_write_seconds = trainer.checkpoint_write_seconds
        # multi-chip compiles are timed in the trainer's own program
        # cache (track_jit); report those instead of the bypassed
        # single-chip step cache
        step_stats = trainer.compile_cache.stats
        if plan is not None and plan.has_model_axis:
            # params stay tensor-sharded after fit: score (and the
            # final save's host gather) through the same plan instead
            # of a single-chip program that can't accept them
            net.set_serve_mesh(mesh=plan.mesh)
    else:
        net = MultiLayerNetwork(conf).init()
        _attach_compile_cache(net, args)
        step_stats = net.step_cache.stats
        if deep_ae and epochs > 0:
            # Hinton's recipe: pretrain + decoder unroll happen ONCE —
            # re-running them per epoch would overwrite the previous
            # epoch's finetuned decoder with transposed encoder weights;
            # only the reconstruction finetune repeats (epochs=0 still
            # means "no training", matching the other models)
            from deeplearning4j_tpu.models.zoo import fit_deep_autoencoder

            fit_deep_autoencoder(net, data.features)
            for _ in range(epochs - 1):
                net.finetune(data.features, data.features)
        elif not deep_ae and ckpt_dir:
            # crash-safe path: ONE flat batch stream spanning every epoch,
            # so the checkpoint's single batch cursor addresses the whole
            # run and a restart replays the stream deterministically up to
            # the saved cursor (then resumes bit-for-bit)
            from deeplearning4j_tpu.datasets.iterator import (
                ListDataSetIterator, MultipleEpochsIterator,
                PrefetchIterator, ReconstructionDataSetIterator)
            from deeplearning4j_tpu.reliability import TrainingInterrupted

            if epochs > 0:
                batch = int(props.get("batch", "0"))
                rows = batch if batch > 0 else data.num_examples()
                stream = ListDataSetIterator(data, rows)
                if reconstruction:
                    stream = ReconstructionDataSetIterator(stream)
                if epochs > 1:
                    stream = MultipleEpochsIterator(epochs, stream)
                try:
                    net.fit(PrefetchIterator(stream),
                            checkpoint_dir=ckpt_dir,
                            checkpoint_every_n_batches=ckpt_every)
                except TrainingInterrupted as e:
                    # checkpointed on the way out: report and exit clean
                    # (a rerun with the same flags resumes at the cursor)
                    print(json.dumps({"interrupted": True,
                                      "checkpoint": ckpt_dir,
                                      "detail": str(e)}), flush=True)
                    return 0
        elif not deep_ae:
            # plain reconstruction confs (no AE pretrain stack) still
            # train against the inputs
            batch = int(props.get("batch", "0"))
            for _ in range(epochs):
                if batch > 0:
                    # mini-batch loop: each (conf, bucket-shape) pair
                    # compiles ONE solver program in net.step_cache and
                    # every further batch is a cache hit; the remainder
                    # batch pads into the full-batch bucket.  Prefetch
                    # device_puts each batch one step ahead on a
                    # background thread so the compiled step never waits
                    # on host->device transfer.
                    from deeplearning4j_tpu.datasets.iterator import (
                        PrefetchIterator)

                    for b in PrefetchIterator(data.batch_by(batch)):
                        net.fit(b.features,
                                b.features if reconstruction else b.labels)
                else:
                    net.fit(data.features,
                            data.features if reconstruction else data.labels)

    if args.runtime != "mesh":
        # the single-device trainer keeps the same books on the net
        resumed_from_step = net.resumed_from_batch
        ckpt_write_seconds = net.checkpoint_write_seconds
    train_seconds = _time.perf_counter() - t_train
    # a reconstruction head's output width is n_in: score against the
    # inputs, not the (differently-shaped) labels
    score = net.score(data.features,
                      data.features if reconstruction else data.labels)
    checkpoint.save(args.output, net.params, conf=conf,
                    metadata={"score": score, "input": args.input})
    cs = step_stats  # trainer.compile_cache on mesh, net.step_cache locally
    ic = net.infer_cache.stats  # the final score() above serves from it
    print(json.dumps({"saved": args.output, "score": score,
                      "resumed_from_step": resumed_from_step,
                      "checkpoint_write_seconds": round(
                          ckpt_write_seconds, 3),
                      "train_seconds": round(train_seconds, 3),
                      "examples_per_sec": round(
                          n_trained / max(train_seconds, 1e-9), 2),
                      "compile_seconds": round(cs.total_compile_seconds, 3),
                      "cache_hits": cs.hits,
                      "cache_misses": cs.misses,
                      "infer_compile_seconds": round(
                          ic.total_compile_seconds, 3),
                      "disk_cache": _disk_stats(net),
                      **platform.describe()}))
    return 0


def cmd_test(args) -> int:
    from deeplearning4j_tpu.cli.schemes import load_input
    from deeplearning4j_tpu.evaluation import evaluate

    net = _load_model(args.model)
    _attach_compile_cache(net, args)
    data = load_input(args.input, label_column=args.label_column,
                      num_examples=args.num_examples)
    if args.normalize:
        data = data.normalize_zero_mean_unit_variance()
    if getattr(args, "scale_01", False):
        data = data.scale_to_unit()
    # bucketed eval: fixed-size batches through the serve-path compile
    # cache with one-batch-ahead host->device prefetch, instead of one
    # giant device call over the whole dataset
    ev = evaluate(net, data, batch_size=args.batch)
    print(ev.stats())
    ic = net.infer_cache.stats
    print(json.dumps({"accuracy": ev.accuracy(), "f1": ev.f1(),
                      "infer_compile_seconds": round(
                          ic.total_compile_seconds, 3),
                      "infer_cache_hits": ic.hits,
                      "infer_cache_misses": ic.misses,
                      "disk_cache": _disk_stats(net)}))
    return 0


def cmd_predict(args) -> int:
    import numpy as np

    from deeplearning4j_tpu.cli.schemes import load_input
    from deeplearning4j_tpu.datasets.iterator import (ListDataSetIterator,
                                                      PrefetchIterator)

    net = _load_model(args.model)
    _attach_compile_cache(net, args)
    data = load_input(args.input, label_column=args.label_column,
                      num_examples=args.num_examples)
    if args.normalize:
        data = data.normalize_zero_mean_unit_variance()
    if getattr(args, "scale_01", False):
        data = data.scale_to_unit()
    if 0 < args.batch < data.num_examples():
        # fixed-size buckets through the serve-path compile cache; the
        # ragged tail pads into the full-batch bucket, and prefetch
        # overlaps each batch's host->device copy with the previous
        # batch's forward pass
        probs = np.concatenate(
            [np.asarray(net.output(b.features))
             for b in PrefetchIterator(ListDataSetIterator(data, args.batch))])
    else:
        probs = np.asarray(net.output(data.features))
    preds = probs.argmax(axis=-1)
    if args.output:
        with open(args.output, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["prediction"] +
                       [f"p{i}" for i in range(probs.shape[1])])
            for p, row in zip(preds, probs):
                w.writerow([int(p)] + [f"{v:.6f}" for v in row])
        ic = net.infer_cache.stats
        print(json.dumps({"written": args.output, "n": len(preds),
                          "infer_compile_seconds": round(
                              ic.total_compile_seconds, 3),
                          "infer_cache_hits": ic.hits,
                          "infer_cache_misses": ic.misses,
                          "disk_cache": _disk_stats(net)}))
    else:
        print(" ".join(str(int(p)) for p in preds))
    return 0


def _tuning_status() -> dict:
    """The autotuning observability block (warmup/serve/tune JSON and
    /v1/stats all report the same shape)."""
    from deeplearning4j_tpu.optimize import tunables

    return tunables.status()


def cmd_tune(args) -> int:
    """Search the tunables registry's config space for this model
    (optimize/tune.py): measure real compiled candidate programs through
    the existing caches, prune analytically-bad candidates, persist the
    winning table in the compile cache keyed by (conf fingerprint,
    device kind) — later warmup/serve/replica processes inherit it with
    fresh_tunes == 0."""
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize import tune as tune_mod

    if args.model and os.path.isdir(args.model):
        net = _load_model(args.model)
    elif args.model:
        with open(args.model) as f:
            conf = MultiLayerConfiguration.from_json(f.read())
        net = MultiLayerNetwork(conf).init()
    else:
        raise SystemExit("tune needs --model <conf.json | checkpoint dir>")
    store = None
    if args.compile_cache:
        store = net.set_compile_cache(args.compile_cache)
    groups = tuple(g.strip() for g in args.groups.split(",") if g.strip())
    report = tune_mod.tune_and_store(
        net, store, force=args.force, groups=groups, rounds=args.rounds,
        seed=args.seed, max_seq=args.gen_max_seq)
    report["disk_cache"] = _disk_stats(net)
    report.update(platform.describe())
    print(json.dumps(report))
    return 0


def cmd_warmup(args) -> int:
    """Precompile declared shape buckets into a persistent compile cache
    so a later serving/training process starts from disk hits instead of
    multi-second compiles."""
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if not args.compile_cache:
        raise SystemExit("warmup requires --compile-cache <dir>")
    if args.model and os.path.isdir(args.model):
        net = _load_model(args.model)
    elif args.model:
        with open(args.model) as f:
            conf = MultiLayerConfiguration.from_json(f.read())
        net = MultiLayerNetwork(conf).init()
    else:
        raise SystemExit("warmup needs --model <conf.json | checkpoint dir>")
    net.set_compile_cache(args.compile_cache)
    mesh_devices = None
    if getattr(args, "mesh", None) is not None:
        # BEFORE warmup, so the warmed programs carry the mesh cache key
        # (same ordering rule as the precision policy below)
        mesh_devices = int(net.set_serve_mesh(spec=args.mesh).devices.size)
    precision = getattr(args, "precision", "f32")
    if precision != "f32":
        # BEFORE warmup, so the warmed programs carry the policy cache
        # key (and the int8 quantized-weights artifact lands in the
        # compile cache for the serving processes to reload)
        net.set_serve_precision(precision, measure=False)
    shapes = _parse_shapes(args.shapes)
    if not shapes:
        raise SystemExit("warmup needs --shapes (e.g. 256,1024 or 32x784)")
    entries = tuple(e.strip() for e in args.entries.split(",") if e.strip())
    summary = net.warmup(shapes, entries=entries, train=args.train)
    if getattr(args, "generate", False):
        # generation programs land in the same persistent store, so a
        # later `serve --generate` with matching gen_* flags starts
        # with fresh_compiles == 0
        summary["generation"] = _warm_generate(net, args,
                                               draft=_gen_draft_net(args))
        summary["infer_cache"] = net.infer_cache.stats.as_dict()
    summary["precision"] = net.serve_precision
    summary["mesh_devices"] = mesh_devices
    summary["disk_cache"] = _disk_stats(net)
    summary["tuning"] = _tuning_status()
    summary.update(platform.describe())
    print(json.dumps(summary))
    return 0


def _parse_shapes(spec: str):
    """'256,1024' or '32x784' -> [int batch | full shape tuple, ...]."""
    shapes = []
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        dims = tuple(int(d) for d in part.split("x"))
        shapes.append(dims[0] if len(dims) == 1 else dims)
    return shapes


def _parse_buckets(spec: str):
    """'4,8' -> (4, 8): prompt-token buckets the prefill program pads
    into (one compiled prefill per bucket)."""
    out = tuple(int(p) for p in (spec or "").split(",") if p.strip())
    if not out:
        raise SystemExit("expected a comma-separated bucket list like 4,8")
    return out


def _gen_draft_net(args):
    """--gen-draft CHECKPOINT -> loaded draft net (or None), sharing the
    target's persistent compile cache so draft programs warm to disk
    too."""
    path = getattr(args, "gen_draft", None)
    if not path:
        return None
    if getattr(args, "gen_spec_k", 0) < 2:
        raise SystemExit("--gen-draft requires --gen-spec-k >= 2")
    draft = _load_model(path)
    _attach_compile_cache(draft, args)
    if getattr(args, "mesh", None) is not None:
        # the draft's programs join the same plan-keyed cache family
        # (speculative verify is keyed by the target's plan)
        draft.set_serve_mesh(spec=args.mesh)
    return draft


def _warm_generate(net, args, draft=None) -> dict:
    """Compile the decode + prefill programs for the gen_* flags (shared
    by serve --generate, warmup --generate, and the generate command) —
    always BEFORE traffic, so generation starts from cache hits."""
    summary = net.warmup_generate(
        slots=args.gen_slots, max_seq=args.gen_max_seq,
        prompt_buckets=_parse_buckets(args.gen_prompt_buckets),
        page_size=getattr(args, "gen_page_size", None),
        n_pages=getattr(args, "gen_pages", 0),
        prefix_cache=getattr(args, "gen_prefix_cache", False),
        draft_net=draft,
        spec_k=getattr(args, "gen_spec_k", 0))
    summary.pop("infer_cache", None)  # _build_server reports cache stats
    return summary


def cmd_generate(args) -> int:
    """One-shot autoregressive generation through the compiled KV-cache
    decode path: prefill consumes the prompt, then the continuous
    batcher's decode loop produces each token (n_slots=1 here; `serve
    --generate` runs the multi-slot table behind POST /v1/generate)."""
    import time

    from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

    net = _load_model(args.model)
    _attach_compile_cache(net, args)
    if getattr(args, "mesh", None) is not None:
        # before warmup_generate, so the decode/prefill programs carry
        # the plan's cache key
        net.set_serve_mesh(spec=args.mesh)
    prompt = [int(t) for t in args.prompt.split(",") if t.strip()]
    if not prompt:
        raise SystemExit("generate needs --prompt <id,id,...>")
    if len(prompt) >= args.gen_max_seq:
        raise SystemExit(f"prompt of {len(prompt)} tokens needs "
                         f"--gen-max-seq > {len(prompt)}")
    bucket = max(4, 1 << (len(prompt) - 1).bit_length())
    draft = _gen_draft_net(args)
    # one-shot generation deliberately pins a single decode slot
    net.warmup_generate(slots=1, max_seq=args.gen_max_seq,  # lint: allow(hardcoded-tunable)
                        prompt_buckets=(min(bucket, args.gen_max_seq),),
                        page_size=getattr(args, "gen_page_size", 0),
                        prefix_cache=getattr(args, "gen_prefix_cache",
                                             False),
                        draft_net=draft,
                        spec_k=getattr(args, "gen_spec_k", 0))
    warmed_misses = net.infer_cache.stats.misses
    batcher = ContinuousBatcher(net, n_slots=1,  # lint: allow(hardcoded-tunable)
                                max_seq=args.gen_max_seq,
                                prompt_buckets=(min(bucket,
                                                    args.gen_max_seq),),
                                page_size=getattr(args, "gen_page_size", 0),
                                prefix_cache=getattr(args,
                                                     "gen_prefix_cache",
                                                     False),
                                draft_net=draft,
                                spec_k=getattr(args, "gen_spec_k", 0))
    try:
        t0 = time.perf_counter()
        stream = batcher.submit(prompt,
                                max_new_tokens=args.max_new_tokens,
                                temperature=args.temperature,
                                rng_seed=args.seed)
        tokens = list(stream.tokens(timeout=args.timeout))
        dt = time.perf_counter() - t0
    finally:
        batcher.stop()
    print(json.dumps({
        "tokens": tokens,
        "n_tokens": len(tokens),
        "tokens_per_sec": round(len(tokens) / max(dt, 1e-9), 2),
        "ttft_ms": (None if stream.ttft_s is None
                    else round(stream.ttft_s * 1000.0, 3)),
        "fresh_compiles": net.infer_cache.stats.misses - warmed_misses,
        "disk_cache": _disk_stats(net),
        **platform.describe()}))
    return 0


def _build_server(args):
    """serve subcommand minus the blocking loop (testable): load the
    checkpoint, attach the compile cache, warm the declared buckets, and
    start the gateway.  Returns (net, server, startup-summary dict)."""
    net = _load_model(args.model)
    _attach_compile_cache(net, args)
    mesh_devices = None
    if getattr(args, "mesh", None) is not None:
        # before warmup, so the warmed programs carry the mesh cache key
        mesh_devices = int(net.set_serve_mesh(spec=args.mesh).devices.size)
    precision = getattr(args, "precision", "f32")
    precision_report = None
    if precision != "f32":
        # same ordering rule as the mesh: set the policy BEFORE warmup,
        # so the warmed programs carry the policy cache key (a warmup
        # run with the same --precision prefilled the disk store, so
        # these are disk restores, not compiles)
        precision_report = net.set_serve_precision(precision)
    shapes = _parse_shapes(args.shapes)
    warmed = None
    if shapes:
        # warm BEFORE listening: with a populated --compile-cache these
        # are disk restores, and steady-state serving (requests padding
        # into the warmed buckets) does zero fresh compiles
        warmed = net.warmup(shapes, entries=("output",))["shapes"]
    generate = bool(getattr(args, "generate", False))
    gen_warmed = None
    gen_draft = None
    if generate:
        # same rule as the predict buckets: the decode + prefill
        # programs compile (or disk-restore) before the socket opens
        gen_draft = _gen_draft_net(args)
        gen_warmed = _warm_generate(net, args, draft=gen_draft)
    server = net.serve(host=args.host, port=args.port,
                       max_delay_ms=args.max_delay_ms,
                       max_pending=args.max_pending,
                       max_batch_rows=args.max_batch_rows,
                       batching=not args.no_batching,
                       request_timeout_s=getattr(args, "request_timeout",
                                                 30.0),
                       drain_timeout_s=getattr(args, "drain_timeout", 10.0),
                       default_deadline_ms=getattr(args,
                                                   "default_deadline_ms",
                                                   None),
                       generate=generate,
                       gen_slots=getattr(args, "gen_slots", None),
                       gen_max_seq=getattr(args, "gen_max_seq", 64),
                       gen_prompt_buckets=_parse_buckets(
                           getattr(args, "gen_prompt_buckets", "8"))
                       if generate else (8,),
                       gen_max_pending=getattr(args, "gen_max_pending", 64),
                       gen_page_size=getattr(args, "gen_page_size", None),
                       gen_pages=getattr(args, "gen_pages", 0),
                       gen_prefix_cache=getattr(args, "gen_prefix_cache",
                                                False),
                       gen_prefix_match=getattr(args, "gen_prefix_match",
                                                "exact"),
                       gen_draft=gen_draft,
                       gen_spec_k=getattr(args, "gen_spec_k", 0))
    summary = {"url": server.url, "warmed": warmed,
               "fresh_compiles": net.infer_cache.stats.misses,
               "batching": not args.no_batching,
               "mesh_devices": mesh_devices,
               "precision": net.serve_precision,
               "precision_report": precision_report,
               "generation": gen_warmed,
               "disk_cache": _disk_stats(net),
               "tuning": _tuning_status(),
               **platform.describe()}
    return net, server, summary


def cmd_serve(args) -> int:
    import signal

    if getattr(args, "replicas", 0) >= 1 or getattr(args, "agent", None):
        return cmd_serve_router(args)
    _, server, summary = _build_server(args)
    print(json.dumps(summary), flush=True)
    # SIGTERM/SIGINT → graceful drain: the handler only flips an event
    # (signal-safe); the main thread wakes and runs the bounded drain —
    # every request accepted before the signal gets a real response
    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev[sig] = signal.signal(
                sig, lambda signum, frame: server.request_stop())
        except ValueError:
            pass  # not the main thread (embedded use): explicit stop only
    try:
        server.wait_for_stop()
    except KeyboardInterrupt:
        pass
    finally:
        server.drain(getattr(args, "drain_timeout", 10.0))
        for sig, handler in prev.items():
            signal.signal(sig, handler)
        st = server.stats()
        print(json.dumps({"drained": True,
                          "requests": st.get("requests", 0),
                          "deadline_misses": st.get("deadline_misses", 0),
                          "errors": st.get("errors", 0)}), flush=True)
    return 0


def _replica_cmd(args) -> List[str]:
    """The `serve` command line one replica subprocess runs: the
    caller's flags minus --replicas, always on an ephemeral port."""
    cmd = [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve",
           "--model", args.model, "--host", args.host, "--port", "0",
           "--shapes", args.shapes,
           "--max-pending", str(args.max_pending),
           "--drain-timeout", str(getattr(args, "drain_timeout", 10.0)),
           "--request-timeout", str(getattr(args, "request_timeout", 30.0))]
    if args.max_delay_ms is not None:
        # None = tunable-governed; each replica resolves its own (and a
        # shared tuned table keeps the fleet uniform)
        cmd += ["--max-delay-ms", str(args.max_delay_ms)]
    if args.compile_cache:
        cmd += ["--compile-cache", args.compile_cache]
    if args.max_batch_rows is not None:
        cmd += ["--max-batch-rows", str(args.max_batch_rows)]
    if args.no_batching:
        cmd += ["--no-batching"]
    if getattr(args, "default_deadline_ms", None) is not None:
        cmd += ["--default-deadline-ms", str(args.default_deadline_ms)]
    if getattr(args, "mesh", None) is not None:
        cmd += ["--mesh", args.mesh]
    if getattr(args, "precision", "f32") != "f32":
        cmd += ["--precision", args.precision]
    return cmd


def _remote_serve_argv(args, cache_sources: List[str]) -> List[str]:
    """The `serve` argv a ReplicaAgent spawns for one remote replica:
    the local replica command line minus the interpreter prefix and
    minus --compile-cache (each agent pins its own host's cache dir),
    plus --cache-from URLs so a cold host warms over the cachesync wire
    instead of compiling."""
    cmd = _replica_cmd(args)[3:]  # drop `python -m deeplearning4j_tpu.cli`
    argv: List[str] = []
    skip = False
    for a in cmd:
        if skip:
            skip = False
            continue
        if a == "--compile-cache":
            skip = True
            continue
        argv.append(a)
    for src in cache_sources:
        argv += ["--cache-from", src]
    return argv


class ReplicaProcess:
    """One `serve` replica subprocess: spawn, read the startup JSON off
    its stdout (blocks until the replica warmed and is listening),
    SIGTERM + collect the drained JSON at shutdown.  `chip` pins the
    child to `n_chips` chips of the host from that one on, through its
    environment (`platform.chip_env`); None leaves it every device the
    parent's environment shows."""

    def __init__(self, cmd: List[str], chip: Optional[int] = None,
                 n_chips: int = 1):
        import subprocess

        env = None if chip is None else {
            **os.environ, **platform.chip_env(chip, n_chips)}
        self.chip = chip
        self.n_chips = n_chips
        self.proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                                     env=env)
        self.summary: Optional[dict] = None

    def wait_ready(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            rc = self.proc.wait()
            raise SystemExit(f"replica died during startup (exit {rc})")
        self.summary = json.loads(line)
        return self.summary

    @property
    def url(self) -> Optional[str]:
        return None if self.summary is None else self.summary.get("url")

    @property
    def pid(self) -> int:
        return self.proc.pid

    def poll(self) -> Optional[int]:
        """Exit code if the process died, None while alive — the
        supervisor's reap probe."""
        return self.proc.poll()

    def terminate(self) -> None:
        import signal

        if self.proc.poll() is None:
            try:
                self.proc.send_signal(signal.SIGTERM)
            except OSError:
                pass

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.kill()
            except OSError:
                pass

    def wait(self, timeout: Optional[float] = None) -> int:
        try:
            rc = self.proc.wait(timeout)
        finally:
            if self.proc.stdout is not None:
                self.proc.stdout.close()
        return rc


class ChipSlots:
    """The chips of this host, shared out among the replicas this process
    starts: each spawn takes the lowest run of `n_chips` chips that no
    live replica holds, so a reaped replica's chips go to its
    replacement.  The process that owns the slots stays off JAX — a
    parent that initialised a backend would hold every chip its children
    need."""

    def __init__(self):
        self._held: List[ReplicaProcess] = []
        self._lock = threading.Lock()

    def spawn(self, cmd: List[str], n_chips: int = 1) -> ReplicaProcess:
        with self._lock:
            self._held = [r for r in self._held if r.poll() is None]
            taken = {c for r in self._held
                     for c in range(r.chip, r.chip + r.n_chips)}
            first = next(i for i in itertools.count(0, n_chips)
                         if taken.isdisjoint(range(i, i + n_chips)))
            replica = ReplicaProcess(cmd, chip=first, n_chips=n_chips)
            self._held.append(replica)
            return replica


def _chips_per_replica(serve_argv: List[str]) -> int:
    """How many chips the `--mesh` of a replica's `serve` command line
    spans: the product of the axis sizes it names.  A bare `--mesh` and a
    `-1` axis mean "every device the replica sees", which is whatever its
    slot gives it, so they add nothing."""
    if "--mesh" not in serve_argv:
        return 1
    from deeplearning4j_tpu.parallel.plan import parse_mesh_spec

    value = serve_argv[serve_argv.index("--mesh") + 1:][:1]
    bare = not value or value[0].startswith("--")
    shape = parse_mesh_spec("all" if bare else value[0])
    return math.prod(n for n in shape.values() if n > 0)


def cmd_serve_router(args) -> int:
    """serve --replicas N: spawn N replica subprocesses sharing the
    --compile-cache dir, front them with `serving.Router`, supervise
    them (`FleetSupervisor` reaps + respawns deaths, `Autoscaler` flexes
    the fleet between --min/--max-replicas), and mirror the
    single-server SIGTERM contract fleet-wide — drain the ROUTER first
    (every accepted request still finds its replica), then SIGTERM the
    replicas and insist they all drain to exit 0."""
    import signal

    from deeplearning4j_tpu.serving.autoscaler import Autoscaler
    from deeplearning4j_tpu.serving.router import Router
    from deeplearning4j_tpu.serving.supervisor import FleetSupervisor

    agent_urls = list(getattr(args, "agent", None) or [])
    if agent_urls and args.replicas < 1:
        args.replicas = 1
    min_replicas = getattr(args, "min_replicas", None) or args.replicas
    max_replicas = getattr(args, "max_replicas", None) or args.replicas
    cmd = _replica_cmd(args)
    slots = ChipSlots()
    n_chips = _chips_per_replica(cmd)
    cache_server = None
    remote_argv = None
    clients = []
    if agent_urls:
        # multi-host: replicas live on per-host ReplicaAgents; the
        # supervisor drives them over the network with leases
        from deeplearning4j_tpu.serving.agent import AgentClient
        from deeplearning4j_tpu.serving.cachesync import CacheServer

        clients = [AgentClient(u) for u in agent_urls]
        sources = []
        if args.compile_cache:
            # the control-plane host serves its own warmed cache dir
            # too, so a respawn on a cold host warms over the wire even
            # when every peer agent is cold (or dead)
            cache_server = CacheServer(args.compile_cache).start()
            sources.append(cache_server.url)
        sources += [c.url for c in clients]
        remote_argv = _remote_serve_argv(args, sources)
        replicas = [clients[i % len(clients)].spawn(remote_argv)
                    for i in range(args.replicas)]
    else:
        replicas = [slots.spawn(cmd, n_chips) for _ in range(args.replicas)]
    router = supervisor = autoscaler = None
    try:
        summaries = [r.wait_ready() for r in replicas]
        router = Router([s["url"] for s in summaries],
                        host=args.host, port=args.port,
                        request_timeout_s=getattr(args, "request_timeout",
                                                  30.0) + 5.0,
                        hedge=getattr(args, "hedge", False),
                        retry_budget_ratio=getattr(args, "retry_budget",
                                                   0.1)).start()
        # the supervisor adopts the already-ready initial handles; a
        # respawn re-runs the same replica command line against the same
        # shared disk cache, so coming back is seconds, not compiles
        supervisor = FleetSupervisor(
            spawn_fn=lambda: slots.spawn(cmd, n_chips), router=router,
            initial=replicas, min_replicas=min_replicas,
            max_replicas=max_replicas,
            agents=clients, remote_argv=remote_argv,
            agent_failover_s=getattr(args, "agent_failover", 10.0),
            drain_timeout_s=getattr(args, "drain_timeout", 10.0)).start()
        if max_replicas > min_replicas:
            autoscaler = Autoscaler(
                router, supervisor,
                slo_p99_ms=getattr(args, "slo_p99_ms", 500.0)).start()
        router.attach_fleet(supervisor, autoscaler)
        print(json.dumps({
            "url": router.url,
            "replicas": [s["url"] for s in summaries],
            "replica_pids": [r.pid for r in replicas],
            "min_replicas": min_replicas,
            "max_replicas": max_replicas,
            "hedge": router.hedge,
            "agents": [c.url for c in clients],
            "fresh_compiles": [s.get("fresh_compiles") for s in summaries],
            "mesh_devices": summaries[0].get("mesh_devices"),
            # what each replica's own JAX found; this parent asks for none
            "replica_devices": [
                {k: s.get(k) for k in ("platform", "device_kind",
                                       "device_count", "chip")}
                for s in summaries],
        }), flush=True)
        prev = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev[sig] = signal.signal(
                    sig, lambda signum, frame: router.request_stop())
            except ValueError:
                pass  # not the main thread: explicit stop only
        try:
            router.wait_for_stop()
        except KeyboardInterrupt:
            pass
        finally:
            for sig, handler in prev.items():
                signal.signal(sig, handler)
    finally:
        drain_timeout = getattr(args, "drain_timeout", 10.0)
        # shutdown order: control plane first (no respawn or scale
        # action races the teardown), then the router drain (accepted
        # requests finish against live replicas), then SIGTERM whatever
        # processes the supervisor currently owns
        if autoscaler is not None:
            autoscaler.stop()
        if supervisor is not None:
            supervisor.stop()
        if router is not None:
            router.drain(drain_timeout)
        if cache_server is not None:
            cache_server.stop()
        handles = supervisor.handles() if supervisor is not None else replicas
        for r in handles:
            r.terminate()
        rcs = []
        for r in handles:
            try:
                rcs.append(r.wait(timeout=drain_timeout + 15.0))
            except Exception:  # noqa: BLE001 — a wedged replica: kill
                r.kill()
                rcs.append(r.wait())
        stats = router.stats() if router is not None else {}
        fleet = stats.get("fleet", {})
        print(json.dumps({"drained": True,
                          "replica_exit_codes": rcs,
                          "retries": stats.get("retries", 0),
                          "unroutable": stats.get("unroutable", 0),
                          "hedges": stats.get("hedges", 0),
                          "restarts": fleet.get("restarts_total", 0)}),
              flush=True)
    return 0 if rcs and all(rc == 0 for rc in rcs) else 1


def cmd_agent(args) -> int:
    """agent: the per-host replica-agent control plane.  Runs a small
    HTTP server (POST /a/spawn, POST /a/stop, GET /a/health,
    GET /a/replicas, GET /a/cache/{key}) that owns this host's replica
    subprocesses on behalf of a remote `serve --agent` supervisor.
    Model-free: the agent initialises no JAX backend — replicas are
    ordinary `serve` subprocesses, each on chips of its own
    (`ChipSlots`), and the agent pins each one to this host's
    --compile-cache dir so they share warm compiles locally and serve
    them to cold peers over /a/cache."""
    import signal

    from deeplearning4j_tpu.serving.agent import ReplicaAgent

    slots = ChipSlots()

    def spawn_fn(argv):
        return slots.spawn(
            [sys.executable, "-m", "deeplearning4j_tpu.cli"] + list(argv),
            _chips_per_replica(list(argv)))

    agent = ReplicaAgent(spawn_fn, host=args.host, port=args.port,
                         cache_dir=args.compile_cache,
                         max_replicas=args.max_replicas).start()
    print(json.dumps({"url": agent.url,
                      "compile_cache": args.compile_cache,
                      "max_replicas": args.max_replicas}), flush=True)
    stop = threading.Event()
    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev[sig] = signal.signal(sig,
                                      lambda signum, frame: stop.set())
        except ValueError:
            pass  # not the main thread: explicit stop only
    try:
        while not stop.wait(0.5):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        for sig, handler in prev.items():
            signal.signal(sig, handler)
        h = agent.health()
        rcs = agent.stop(terminate_children=True,
                         drain_timeout_s=getattr(args, "drain_timeout",
                                                 10.0) + 15.0)
        print(json.dumps({"drained": True,
                          "replica_exit_codes": rcs,
                          "spawns_total": h.get("spawns_total", 0),
                          "cache_requests_total":
                              h.get("cache_requests_total", 0),
                          "cache_hits_total": h.get("cache_hits_total", 0)}),
              flush=True)
    return 0


def cmd_analyze(args) -> int:
    """Static analysis over the package and the zoo's compiled programs
    (analysis/): AST convention lint + jaxpr program audit, one report,
    exit 1 when any finding reaches the --fail-on severity."""
    from deeplearning4j_tpu.analysis import (at_or_above, audit_zoo_models,
                                             lint_package, render_text,
                                             to_report)

    findings, n_files = lint_package()
    n_programs = 0
    if not args.skip_programs:
        prog_findings, n_programs = audit_zoo_models(small=True)
        findings = findings + prog_findings
    checked = {"files": n_files, "programs": n_programs}
    if args.format == "json":
        print(json.dumps(to_report(findings, checked)))
    else:
        print(render_text(findings, checked))
    return 1 if at_or_above(findings, args.fail_on) else 0


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--input", required=True,
                   help="mnist|iris|lfw|curves|cifar10|csv:<path>[:label_col]|"
                        "text:<path>[:seq_len]|*.csv")
    p.add_argument("--model", default=None,
                   help="conf JSON (train) or checkpoint dir (test/predict)")
    p.add_argument("--label-column", type=int, default=-1)
    p.add_argument("--num-examples", type=int, default=None)
    p.add_argument("--scale-01", dest="scale_01", action="store_true",
                   help="min-max scale features into [0, 1] (RBM/DBN "
                        "visible units)")
    p.add_argument("--normalize", action="store_true",
                   help="zero-mean/unit-variance features")
    p.add_argument("--compile-cache", dest="compile_cache", default=None,
                   metavar="DIR",
                   help="persistent on-disk compile cache: programs "
                        "compiled by this run are reused by every later "
                        "run pointed at the same directory (see the "
                        "warmup subcommand to prefill it)")


def _add_generate_flags(p: argparse.ArgumentParser) -> None:
    """Continuous-batching generation flags shared by `serve --generate`
    and `warmup --generate` (matching flags → matching cache keys, so a
    warmed serve process starts generating with zero fresh compiles)."""
    p.add_argument("--generate", action="store_true",
                   help="compile the autoregressive decode + prefill "
                        "programs; on serve, also run the continuous-"
                        "batching decode loop behind POST /v1/generate")
    p.add_argument("--gen-slots", dest="gen_slots", type=int, default=None,
                   help="decode slot-table width: concurrent generation "
                        "streams per device call (one compiled decode "
                        "step over the whole table); default: the "
                        "decode.slots tunable (4, or the tuned table)")
    p.add_argument("--gen-max-seq", dest="gen_max_seq", type=int,
                   default=64,
                   help="KV-cache length per slot; prompt + generated "
                        "tokens must fit in it")
    p.add_argument("--gen-prompt-buckets", dest="gen_prompt_buckets",
                   default="8",
                   help="comma-separated prompt-token buckets; each "
                        "admission pads its prompt into the smallest "
                        "fitting bucket (one compiled prefill per bucket)")
    p.add_argument("--gen-max-pending", dest="gen_max_pending", type=int,
                   default=64,
                   help="queued generation streams bound; beyond it "
                        "submissions get 503")
    p.add_argument("--gen-page-size", dest="gen_page_size", type=int,
                   default=None,
                   help="tokens per KV-cache page; > 0 switches decode "
                        "to the paged pool (memory scales with live "
                        "tokens, not slots x max-seq); default: the "
                        "decode.page_size tunable (0 = contiguous)")
    p.add_argument("--gen-pages", dest="gen_pages", type=int, default=0,
                   help="physical KV pages in the pool (0 = enough for "
                        "every slot at full max-seq; smaller values "
                        "overcommit admission)")
    p.add_argument("--gen-prefix-cache", dest="gen_prefix_cache",
                   action="store_true",
                   help="cache prefill state by prompt digest; a "
                        "repeated prompt skips prefill (TTFT ~ one "
                        "decode step), token-identical to a cold start")
    p.add_argument("--gen-prefix-match", dest="gen_prefix_match",
                   choices=("exact", "longest"), default="exact",
                   help="prefix-cache matching: exact prompt only, or "
                        "longest cached prefix (suffix fed through the "
                        "decode table)")
    p.add_argument("--gen-draft", dest="gen_draft", default=None,
                   help="checkpoint dir of a small recurrent draft "
                        "model for speculative decoding (requires "
                        "--gen-spec-k)")
    p.add_argument("--gen-spec-k", dest="gen_spec_k", type=int, default=0,
                   help="speculative chunk: draft proposes spec_k - 1 "
                        "tokens, ONE verify step accepts the agreeing "
                        "prefix (greedy output token-identical to "
                        "non-speculative decode)")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="dl4j-tpu", description="TPU-native deep learning CLI")
    sub = ap.add_subparsers(dest="command", required=True)

    t = sub.add_parser("train", help="train a model from a conf JSON")
    _add_common(t)
    t.add_argument("--output", required=True, help="checkpoint output dir")
    t.add_argument("--zoo", default=None,
                   help="train a zoo model instead of a conf JSON: "
                        "lenet5|mlp|char_lstm[:k=v,...] (e.g. "
                        "char_lstm:layers=4,hidden=128)")
    t.add_argument("--runtime", choices=["local", "mesh"], default="local")
    t.add_argument("--mesh", nargs="?", const="all", default=None,
                   metavar="SPEC",
                   help="device mesh spec like batch=2,model=4 (implies "
                        "--runtime mesh); a model axis tensor-shards "
                        "params/grads per the ShardPlan so one model can "
                        "exceed one chip's HBM, and checkpoints write "
                        "per-shard (save_sharded); bare --mesh or "
                        "--mesh all is the 1-D batch=all-devices layout")
    t.add_argument("--zero1", action="store_true",
                   help="ZeRO-1: shard updater (optimizer) state over the "
                        "dp mesh axis instead of replicating it; non-dp-"
                        "divisible batches pad-and-mask like every other "
                        "mode; composes with a --mesh model axis; "
                        "checkpoints gather to full shape, so resume "
                        "works on any device count")
    t.add_argument("--properties", default=None,
                   help="k=v[,k=v...] train properties: epochs, batch, "
                        "mode, checkpoint_every (batches between "
                        "checkpoints with --checkpoint-dir; default 10)")
    t.add_argument("--checkpoint-dir", dest="checkpoint_dir", default=None,
                   metavar="DIR",
                   help="crash-safe training: checkpoint params + RNG key "
                        "+ batch cursor (on mesh, also the full sharded "
                        "updater state) here every checkpoint_every "
                        "batches and on SIGTERM; rerunning with the same "
                        "flags auto-resumes at the saved cursor — a mesh "
                        "checkpoint resumes on any device count")
    t.set_defaults(fn=cmd_train)

    te = sub.add_parser("test", help="evaluate a checkpoint")
    _add_common(te)
    te.add_argument("--batch", type=int, default=1024,
                    help="evaluation batch rows (0 = one giant device "
                         "call); batches share one compiled program per "
                         "shape bucket and prefetch one batch ahead")
    te.set_defaults(fn=cmd_test)

    pr = sub.add_parser("predict", help="write predictions for a dataset")
    _add_common(pr)
    pr.add_argument("--output", default=None, help="predictions CSV path")
    pr.add_argument("--batch", type=int, default=1024,
                    help="prediction batch rows (0 = one giant device "
                         "call); batches share one compiled program per "
                         "shape bucket and prefetch one batch ahead")
    pr.set_defaults(fn=cmd_predict)

    w = sub.add_parser("warmup",
                       help="precompile shape buckets into a persistent "
                            "compile cache ahead of traffic")
    w.add_argument("--model", required=True,
                   help="conf JSON or checkpoint dir to warm up")
    w.add_argument("--compile-cache", dest="compile_cache", required=True,
                   metavar="DIR", help="cache directory to populate")
    w.add_argument("--shapes", default="1024",
                   help="comma-separated batch sizes or full input shapes "
                        "('x'-separated dims): 256,1024 or 32x1x28x28")
    w.add_argument("--entries", default="output",
                   help="serve entry points to compile: "
                        "output,feed_forward,loss")
    w.add_argument("--train", action="store_true",
                   help="also compile the train step for each shape")
    w.add_argument("--mesh", nargs="?", const="all", default=None,
                   metavar="SPEC",
                   help="warm under a serve mesh ('' spec / bare flag = "
                        "1-D batch mesh; batch=2,model=4 adds tensor "
                        "parallelism) so the warmed programs carry the "
                        "mesh cache key a `serve --mesh` process with the "
                        "same spec will look up")
    w.add_argument("--precision", choices=["f32", "bf16", "int8"],
                   default="f32",
                   help="serve-precision policy to warm under (set BEFORE "
                        "compiling, so the warmed programs — and for int8 "
                        "the quantized-weights artifact — carry the policy "
                        "cache key a `serve --precision` process will look "
                        "up)")
    _add_generate_flags(w)
    w.set_defaults(fn=cmd_warmup)

    tu = sub.add_parser(
        "tune",
        help="search the tunables registry's config space (attention "
             "blocks, batch targets, decode geometry) by measuring real "
             "compiled programs; persist the winning table per (conf "
             "fingerprint, device kind) in the compile cache")
    tu.add_argument("--model", required=True,
                    help="conf JSON or checkpoint dir to tune for")
    tu.add_argument("--compile-cache", dest="compile_cache", default=None,
                    metavar="DIR",
                    help="persistent compile cache to store the tuned "
                         "table in (and to inherit an existing one from "
                         "— inherited tables report fresh_tunes == 0)")
    tu.add_argument("--groups", default="attention,serve,decode",
                    help="comma-separated tunable groups to search")
    tu.add_argument("--rounds", type=int, default=3,
                    help="timed rounds per candidate (min-of-rounds)")
    tu.add_argument("--seed", type=int, default=0,
                    help="rng seed for measurement inputs (the search "
                         "is deterministic under a fixed seed)")
    tu.add_argument("--gen-max-seq", dest="gen_max_seq", type=int,
                    default=64,
                    help="KV-cache length for the decode-group sweep")
    tu.add_argument("--force", action="store_true",
                    help="re-search even when the store already holds a "
                         "valid table for this (fingerprint, device kind)")
    tu.set_defaults(fn=cmd_tune)

    g = sub.add_parser(
        "generate",
        help="autoregressive generation from a checkpoint through the "
             "compiled KV-cache decode path (one prefill + one decode "
             "step per token)")
    g.add_argument("--model", required=True,
                   help="checkpoint dir (or conf JSON) of a generative "
                        "model (char_lstm / char_transformer)")
    g.add_argument("--compile-cache", dest="compile_cache", default=None,
                   metavar="DIR",
                   help="persistent compile cache (see warmup --generate)")
    g.add_argument("--prompt", required=True,
                   help="comma-separated prompt token ids, e.g. 1,7,3")
    g.add_argument("--max-new-tokens", dest="max_new_tokens", type=int,
                   default=16,
                   help="tokens to generate (clamped so prompt + output "
                        "fit --max-seq)")
    g.add_argument("--temperature", type=float, default=0.0,
                   help="0 decodes greedily; >0 samples with this "
                        "temperature")
    g.add_argument("--seed", type=int, default=0,
                   help="PRNG seed for temperature sampling")
    g.add_argument("--max-seq", dest="gen_max_seq", type=int, default=64,
                   help="KV-cache length: prompt + generated tokens "
                        "must fit in it")
    g.add_argument("--timeout", type=float, default=120.0,
                   help="bound on the whole generation (seconds)")
    g.add_argument("--page-size", dest="gen_page_size", type=int,
                   default=0,
                   help="tokens per KV page; > 0 decodes through the "
                        "paged pool (token-identical output)")
    g.add_argument("--prefix-cache", dest="gen_prefix_cache",
                   action="store_true",
                   help="cache the prompt's prefill state by digest")
    g.add_argument("--draft", dest="gen_draft", default=None,
                   help="draft-model checkpoint dir for speculative "
                        "decoding (requires --spec-k)")
    g.add_argument("--spec-k", dest="gen_spec_k", type=int, default=0,
                   help="speculative chunk size (>= 2; draft proposes "
                        "spec_k - 1 tokens per verify step)")
    g.add_argument("--mesh", nargs="?", const="all", default=None,
                   metavar="SPEC",
                   help="decode on a device mesh (bare flag = 1-D batch "
                        "mesh; batch=1,model=4 shards params and KV "
                        "state over the model axis — greedy output "
                        "token-identical to single-chip decode)")
    g.set_defaults(fn=cmd_generate)

    s = sub.add_parser("serve",
                       help="micro-batching HTTP gateway: POST "
                            "/v1/predict + GET /v1/stats")
    s.add_argument("--model", required=True,
                   help="checkpoint dir (or conf JSON) to serve")
    s.add_argument("--compile-cache", dest="compile_cache", default=None,
                   metavar="DIR",
                   help="persistent compile cache; warm it first with the "
                        "warmup subcommand so serving starts with zero "
                        "fresh compiles")
    s.add_argument("--shapes", default="64",
                   help="row buckets to precompile before listening "
                        "(comma-separated, like warmup --shapes); '' "
                        "skips warmup")
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=0,
                   help="0 picks an ephemeral port (printed in the "
                        "startup JSON)")
    s.add_argument("--max-delay-ms", dest="max_delay_ms", type=float,
                   default=None,
                   help="how long a request may wait for batch co-riders "
                        "(default: the batcher.max_delay_ms tunable — "
                        "3.0, or the tuned table)")
    s.add_argument("--max-pending", dest="max_pending", type=int,
                   default=1024,
                   help="queued-request bound; beyond it requests get 503")
    s.add_argument("--max-batch-rows", dest="max_batch_rows", type=int,
                   default=None,
                   help="cap on coalesced rows per device call (default: "
                        "largest warmed bucket)")
    s.add_argument("--no-batching", dest="no_batching", action="store_true",
                   help="bypass the micro-batcher (per-request device "
                        "calls; the control arm)")
    s.add_argument("--drain-timeout", dest="drain_timeout", type=float,
                   default=10.0, metavar="SECONDS",
                   help="bound on the SIGTERM graceful drain (stop "
                        "accepting -> flush queued batches -> exit 0)")
    s.add_argument("--request-timeout", dest="request_timeout", type=float,
                   default=30.0, metavar="SECONDS",
                   help="server-side cap on how long one request may "
                        "wait for its coalesced result (504 past it)")
    s.add_argument("--default-deadline-ms", dest="default_deadline_ms",
                   type=float, default=None, metavar="MS",
                   help="deadline applied to requests that carry no "
                        "deadline_ms of their own; expired requests are "
                        "evicted before padding and answered 504")
    s.add_argument("--replicas", type=int, default=0, metavar="N",
                   help="front N replica subprocesses (each its own "
                        "gateway, all sharing --compile-cache) with the "
                        "routing front end; 0 (default) serves in-process "
                        "with no router")
    s.add_argument("--min-replicas", dest="min_replicas", type=int,
                   default=None, metavar="N",
                   help="floor for the supervised fleet (default: "
                        "--replicas); scale-down and quarantine never "
                        "shrink below it")
    s.add_argument("--max-replicas", dest="max_replicas", type=int,
                   default=None, metavar="N",
                   help="ceiling for the supervised fleet (default: "
                        "--replicas); setting it above --min-replicas "
                        "enables the autoscaler")
    s.add_argument("--hedge", action="store_true",
                   help="hedged requests: a proxy attempt that outlives "
                        "the p95 of recent latencies is duplicated at a "
                        "second replica, first answer wins; hedges and "
                        "retries share the --retry-budget")
    s.add_argument("--retry-budget", dest="retry_budget", type=float,
                   default=0.1, metavar="RATIO",
                   help="extra attempts (retries + hedges) allowed as a "
                        "fraction of the trailing request window "
                        "(default 0.1); exhausted requests degrade to "
                        "single-attempt instead of storming")
    s.add_argument("--slo-p99-ms", dest="slo_p99_ms", type=float,
                   default=500.0,
                   help="autoscaler latency objective: fleet p99 above "
                        "this is a scale-up signal")
    s.add_argument("--mesh", nargs="?", const="all", default=None,
                   metavar="SPEC",
                   help="shard serving across a device mesh: bare --mesh "
                        "(or --mesh all) is the 1-D Mesh(('batch',)) over "
                        "every visible device — rows split, params "
                        "replicated, bitwise-identical outputs; a spec "
                        "like batch=2,model=4 adds tensor parallelism "
                        "(params, activations, and decode KV state "
                        "sharded over the model axis per the ShardPlan); "
                        "one program per sharding in the compile cache")
    s.add_argument("--precision", choices=["f32", "bf16", "int8"],
                   default="f32",
                   help="serve-precision policy (optimize/quantize.py): "
                        "bf16 casts weights on load, int8 quantizes them "
                        "per-channel with calibrated scales; applied "
                        "BEFORE warmup so warmed programs carry the "
                        "policy cache key; f32 (default) stays bitwise-"
                        "identical to not passing the flag")
    s.add_argument("--agent", action="append", default=None, metavar="URL",
                   help="multi-host: spawn replicas through a ReplicaAgent "
                        "at URL instead of forking locally (repeatable — "
                        "one per host; replicas round-robin across "
                        "agents); supervision becomes lease-based with "
                        "partition tolerance and failover")
    s.add_argument("--agent-failover", dest="agent_failover", type=float,
                   default=10.0, metavar="SECONDS",
                   help="how long an agent may stay partitioned before "
                        "its replicas fail over to surviving agents "
                        "(default 10.0); short partitions just hold "
                        "replicas out of rotation")
    s.add_argument("--cache-from", dest="cache_from", action="append",
                   default=None, metavar="URL",
                   help="warm the compile cache over the wire: on a local "
                        "disk miss, fetch the entry from these cachesync "
                        "URLs (repeatable, tried in order) before "
                        "compiling; fetched entries are checksum-"
                        "validated and served from memory")
    _add_generate_flags(s)
    s.set_defaults(fn=cmd_serve)

    ag = sub.add_parser(
        "agent",
        help="per-host replica agent: HTTP control plane (POST /a/spawn, "
             "POST /a/stop, GET /a/health, GET /a/replicas, GET "
             "/a/cache/{key}) that owns this host's replica subprocesses "
             "for a remote serve --agent supervisor")
    ag.add_argument("--host", default="127.0.0.1")
    ag.add_argument("--port", type=int, default=0,
                    help="0 picks an ephemeral port (printed in the "
                         "startup JSON)")
    ag.add_argument("--compile-cache", dest="compile_cache", default=None,
                    metavar="DIR",
                    help="this host's persistent compile cache: every "
                         "spawned replica is pinned to it, and its "
                         "checksummed entries are served to cold peers "
                         "over GET /a/cache/{key}")
    ag.add_argument("--max-replicas", dest="max_replicas", type=int,
                    default=4, metavar="N",
                    help="capacity cap: spawns beyond it get 409 "
                         "(default 4)")
    ag.add_argument("--drain-timeout", dest="drain_timeout", type=float,
                    default=10.0, metavar="SECONDS",
                    help="bound on each child's SIGTERM graceful drain "
                         "at agent shutdown")
    ag.set_defaults(fn=cmd_agent)

    an = sub.add_parser(
        "analyze",
        help="static analysis: lint the package's ASTs against repo "
             "conventions and audit the jaxprs of the zoo models' "
             "compiled programs (analysis/)")
    an.add_argument("--format", choices=["text", "json"], default="text",
                    help="report rendering (json emits the versioned "
                         "report schema tests assert on)")
    an.add_argument("--fail-on", dest="fail_on",
                    choices=["warn", "error"], default="error",
                    help="exit 1 when any finding reaches this severity "
                         "(default error)")
    an.add_argument("--skip-programs", dest="skip_programs",
                    action="store_true",
                    help="lint only: skip compiling + auditing the zoo "
                         "models' programs (fast pre-commit mode)")
    an.set_defaults(fn=cmd_analyze)
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    platform.place_compile_cache()
    return args.fn(args)
