"""ServePlan / ShardPlan — the one description of *how* a model is laid
out across devices.

Before this module every subsystem threaded its own ad-hoc layout
tuples: the infer cache built `(entry, fp, sig, sharding_tag) +
policy_suffix` keys by hand, decode programs hardcoded a "single" tag,
checkpoints recorded a free-form mesh dict, and the tensor-parallel
pspec helpers in `parallel/data_parallel.py` were orphaned from all of
them.  `ShardPlan` collapses those into one first-class value:

  mesh        a `jax.sharding.Mesh` (or None = single chip) with named
              axes — serving uses `('batch',)` (1-D, params replicated)
              or `('batch', 'model')` (2-D, params tensor-sharded)
  policy      the serve-precision policy ("f32" | "bf16" | "int8")
  per-leaf    `param_pspecs` / `state_pspecs` derive a PartitionSpec for
  specs       every params / decode-state leaf from its NAME and shape —
              the GSPMD recipe of SNIPPETS [3]: column/row-split matmuls
              annotated at the boundary, `jax.jit` inserts the
              all-reduces

Back-compat is a hard contract, not an aspiration: for 1-D and
single-chip plans `sharding_tag()` / `policy_suffix()` /
`decode_tag()` reproduce the pre-plan cache-key elements BYTE-FOR-BYTE
(`"single"`, `("mesh", axis_names, shape)`, `()` for f32,
`(("policy", name),)` otherwise, and decode entries stay `"single"`
even under a 1-D batch mesh).  Identical key tuples mean identical
`repr(key)` means identical persistent-store paths — existing disk
artifacts stay pure hits, no eviction, no recompile
(tests/test_serve_plan.py pins this).

Axis semantics:

  batch   rows of the padded serve batch (and of the decode slot
          table).  Divisibility: buckets round to multiples of the
          batch-axis size.
  model   the tensor-parallel axis.  QKV / up-projections column-split
          (`P(None, 'model')`), attention output and FFN down
          projections row-split (`P('model', None)`, jit inserts the
          all-reduce), embedding splits its d_model columns, the vocab
          projection splits whichever dim divides, and the decode K/V
          tables (dense AND paged) split by head: the feature dim of a
          [.., n] table, the heads' axis of a layer's [B, H, max_S, hd]
          one (`SPLIT_HEADS`) — the layout that lets params + KV cache
          exceed one chip's HBM.

Any spec is a *layout hint*, never a semantics change: GSPMD reshards
as needed, so an indivisible leaf simply replicates.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: axis names of the serving mesh (mesh.SERVE_AXIS is the 1-D name)
BATCH_AXIS = "batch"
MODEL_AXIS = "model"

#: the single-chip sharding tag (== InferCache.SINGLE, byte-for-byte)
SINGLE = "single"

#: 2-D param leaves whose FIRST dim splits over `model` (row-split: the
#: matmul's contraction dim is sharded, jit inserts the all-reduce) —
#: the attention output projection and the FFN down projection, per the
#: Megatron column-then-row recipe.  Everything else 2-D column-splits
#: its last dim when divisible.
ROW_SPLIT_NAMES = frozenset({"Wo", "W2"})

#: decode-state leaf names whose trailing (feature/hidden) dim splits
#: over `model`: attention K/V pools ([pages, page, n]) split by head;
#: recurrent carries split their hidden.  A layer whose class says
#: `SPLIT_HEADS` (multi-head attention's dense [B, H, max_S, hd] table)
#: splits its `k` and `v` on the heads' axis instead
STATE_SPLIT_NAMES = frozenset({"k", "v", "h", "c"})
#: Every other leaf is whole on every chip: a KDA layer's `S` and `conv`,
#: an MLA layer's `kr` and `ki` (its indexer's keys), and its latent, which
#: is also called `c` but has a position axis ([B, max_S or a window, rank];
#: an LSTM's is [B, H]): one latent serves
#: all heads, so it cannot be split by head, and the plan has no expert or
#: data axis for decode state to lie along yet.  A grouped-heads layer's `k`
#: and `v` ([B, G, cells, h], four axes, no `SPLIT_HEADS`) stay whole too:
#: its decode kernel runs whole on every chip (`pallas_kernels._on_each_chip`)


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """Parse a CLI `--mesh` value: "batch=2,model=4" -> {"batch": 2,
    "model": 4}.  "" / "all" (the bare-flag compatibility value) parse
    to {} — the 1-D all-device serve mesh.  Sizes may be -1 ("all
    remaining devices", resolved by `plan_mesh`)."""
    spec = (spec or "").strip()
    if spec in ("", "all"):
        return {}
    shape: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected axis=N[,axis=N...] "
                f"(e.g. 'batch=2,model=4'), got segment {part!r}")
        axis, _, size = part.partition("=")
        axis = axis.strip()
        try:
            n = int(size)
        except ValueError:
            raise ValueError(f"bad mesh spec {spec!r}: size {size!r} of "
                             f"axis {axis!r} is not an integer") from None
        if n == 0 or n < -1:
            raise ValueError(f"bad mesh spec {spec!r}: axis {axis!r} "
                             f"size must be positive or -1, got {n}")
        if axis in shape:
            raise ValueError(f"bad mesh spec {spec!r}: axis {axis!r} "
                             f"given twice")
        shape[axis] = n
    return shape


def plan_mesh(shape: Optional[Dict[str, int]] = None, devices=None) -> Mesh:
    """Build the serving mesh for a parsed `--mesh` spec: {} (or None)
    is the 1-D all-device `('batch',)` mesh — byte-identical tag to the
    pre-plan `serve_mesh()`; {"batch": N, "model": M} is the 2-D
    tensor-parallel mesh with `batch` outermost.  One axis may be -1
    (all remaining devices)."""
    from deeplearning4j_tpu.nd import platform
    from deeplearning4j_tpu.parallel.mesh import serve_mesh

    if devices is None:
        devices = platform.devices()
    if not shape:
        return serve_mesh(devices)
    shape = dict(shape)
    shape.setdefault(BATCH_AXIS, 1)
    # batch outermost (gradient/row collectives tolerate lower
    # bandwidth), model innermost (activation all-reduces want the
    # fastest links) — the standard mesh layout recipe
    axes = [BATCH_AXIS] + [a for a in shape if a != BATCH_AXIS]
    n = len(devices)
    fills = [a for a in axes if shape[a] == -1]
    if len(fills) > 1:
        raise ValueError("at most one mesh axis may be -1")
    fixed = 1
    for a in axes:
        if shape[a] != -1:
            fixed *= shape[a]
    if fills:
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by {fixed}")
        shape[fills[0]] = n // fixed
    total = 1
    for a in axes:
        total *= shape[a]
    if total > n:
        raise ValueError(f"mesh {shape} needs {total} devices, have {n}")
    dev = np.asarray(devices[:total]).reshape([shape[a] for a in axes])
    return Mesh(dev, axis_names=tuple(axes))


def _split_heads_layers(conf) -> frozenset:
    """The indices of the layers of `conf` (None: none) whose class says
    `SPLIT_HEADS`: a decode state's entry i is layer i's."""
    if conf is None:
        return frozenset()
    from deeplearning4j_tpu.nn.layers import get_layer

    return frozenset(i for i in range(conf.n_layers) if getattr(
        get_layer(conf.conf(i).layer_type), "SPLIT_HEADS", False))


def _leaf_name(path) -> str:
    """The semantic name of a pytree leaf: the last dict key on its
    path that is not a precision-policy wrapper key (int8 params nest
    each weight as {"q": ..., "scale": ...})."""
    names = [str(getattr(p, "key")) for p in path if hasattr(p, "key")]
    for n in reversed(names):
        if n not in ("q", "scale"):
            return n
    return names[-1] if names else ""


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """How one model's programs are keyed, placed, and partitioned.

    The cache-key surface (`sharding_tag` / `policy_suffix` /
    `decode_tag`) is byte-identical to the pre-plan ad-hoc tuples for
    every 1-D / single-chip plan; the partitioning surface
    (`param_pspecs` / `state_pspecs` / `zero1_pspecs`) only activates
    when the mesh carries a `model` axis."""

    mesh: Optional[Mesh] = None
    policy: str = "f32"
    batch_axis: str = BATCH_AXIS
    model_axis: str = MODEL_AXIS

    # -- identity / cache keys ----------------------------------------------
    @property
    def axis_names(self) -> Tuple[str, ...]:
        return () if self.mesh is None else tuple(self.mesh.axis_names)

    @property
    def has_model_axis(self) -> bool:
        return self.model_axis in self.axis_names

    @property
    def model_size(self) -> int:
        if not self.has_model_axis:
            return 1
        return int(self.mesh.shape[self.model_axis])

    @property
    def rows(self) -> int:
        """Row-divisibility the plan demands of serve buckets: the
        batch-axis size (1-D meshes: every device — the pre-plan
        behavior, unchanged)."""
        if self.mesh is None:
            return 1
        if self.batch_axis in self.axis_names:
            return int(self.mesh.shape[self.batch_axis])
        return int(self.mesh.devices.size)

    def sharding_tag(self):
        """The sharding element of every batch-entry cache key —
        byte-identical to the pre-plan `InferCache.sharding_tag()`."""
        if self.mesh is None:
            return SINGLE
        return ("mesh", tuple(self.mesh.axis_names),
                tuple(int(d) for d in self.mesh.devices.shape))

    def policy_suffix(self) -> Tuple:
        """The policy element(s) of every cache key — byte-identical to
        the pre-plan `InferCache._policy_suffix()`: f32 contributes
        NOTHING."""
        if self.policy == "f32":
            return ()
        return (("policy", self.policy),)

    def decode_tag(self):
        """The sharding element of decode/prefill/verify keys.  Decode
        stays single-chip under a 1-D batch mesh (rows replicate
        trivially and pre-plan artifacts hardcoded "single"); only a
        `model` axis re-keys decode — those programs genuinely differ
        (sharded KV tables, jit-inserted collectives)."""
        return self.sharding_tag() if self.has_model_axis else SINGLE

    def key_suffix(self) -> Tuple:
        return (self.sharding_tag(),) + self.policy_suffix()

    def decode_key_suffix(self) -> Tuple:
        return (self.decode_tag(),) + self.policy_suffix()

    def fingerprint(self) -> str:
        """Stable string identity of the plan (digest material for the
        prefix cache and checkpoint metadata)."""
        return repr((self.sharding_tag(), self.policy))

    def describe(self) -> dict:
        """JSON-able plan anatomy (checkpoint meta, /v1/stats)."""
        return {"axes": list(self.axis_names),
                "shape": {a: int(self.mesh.shape[a])
                          for a in self.axis_names},
                "policy": self.policy} if self.mesh is not None else {
                    "axes": [], "shape": {}, "policy": self.policy}

    # -- placements ----------------------------------------------------------
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.batch_axis))

    def _param_spec(self, name: str, shape: Tuple[int, ...]) -> P:
        m = self.model_size
        nd = len(shape)
        if m <= 1 or nd < 2:
            return P()
        if name in ROW_SPLIT_NAMES and shape[0] % m == 0:
            return P(*((self.model_axis,) + (None,) * (nd - 1)))
        if shape[-1] % m == 0:
            # column split: QKV by head, FFN up projection, embedding
            # d_model columns, conv output feature maps (4-D)
            return P(*((None,) * (nd - 1) + (self.model_axis,)))
        if shape[0] % m == 0:
            # vocab projection whose n_out doesn't divide: row-split the
            # contraction dim instead (jit inserts the all-reduce)
            return P(*((self.model_axis,) + (None,) * (nd - 1)))
        return P()

    def param_pspecs(self, params):
        """Per-leaf PartitionSpecs for a params tree, derived from leaf
        names + shapes (works across zoo models and the int8 policy's
        nested {"q","scale"} sub-dicts).  No model axis: everything
        replicates — the pre-plan placement, unchanged."""
        if not self.has_model_axis:
            return jax.tree_util.tree_map(lambda _: P(), params)
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        specs = [self._param_spec(_leaf_name(path),
                                  tuple(getattr(leaf, "shape", ()) or ()))
                 for path, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, specs)

    def param_shardings(self, params):
        """`param_pspecs` as NamedShardings (None without a mesh)."""
        if self.mesh is None:
            return None
        mesh = self.mesh
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self.param_pspecs(params),
            is_leaf=lambda x: isinstance(x, P))

    def _state_spec(self, name: str, shape: Tuple[int, ...],
                    heads: bool = False) -> P:
        m = self.model_size
        nd = len(shape)
        if m <= 1 or nd < 2 or name not in STATE_SPLIT_NAMES:
            return P()
        if heads and nd == 4:
            return (P(None, self.model_axis, None, None) if shape[1] % m == 0
                    else P())
        if shape[-1] % m or (name == "c" and nd == 3) or nd > 3:
            return P()
        return P(*((None,) * (nd - 1) + (self.model_axis,)))

    def state_pspecs(self, state, conf=None):
        """Per-leaf PartitionSpecs for a decode-state tree: K/V tables
        (dense and paged) and recurrent carries split their trailing
        feature dim over `model` when divisible — the sharded KV slot
        table that lets a generation cache exceed one chip's HBM.  Given
        the stack's `conf`, the four-axis `k` and `v` of a layer whose
        class says `SPLIT_HEADS` split their heads' axis (1)."""
        if not self.has_model_axis:
            return jax.tree_util.tree_map(lambda _: P(), state)
        heads = _split_heads_layers(conf)
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        specs = [self._state_spec(_leaf_name(path),
                                  tuple(getattr(leaf, "shape", ()) or ()),
                                  getattr(path[0], "idx", None) in heads)
                 for path, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, specs)

    def state_shardings(self, state, conf=None):
        if self.mesh is None:
            return None
        mesh = self.mesh
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self.state_pspecs(state, conf),
            is_leaf=lambda x: isinstance(x, P))

    # -- training ------------------------------------------------------------
    def zero1_pspecs(self, tree):
        """ZeRO-1 specs COMPOSED with the model axis: each leaf keeps
        its tensor-parallel param spec and additionally shards its first
        still-replicated, batch-divisible dim over the batch/dp axis —
        optimizer moments end up 1/(batch*model) per chip."""
        if self.mesh is None or self.batch_axis not in self.axis_names:
            return self.param_pspecs(tree)
        size = int(self.mesh.shape[self.batch_axis])
        base = self.param_pspecs(tree)

        def compose(leaf, spec):
            shape = tuple(getattr(leaf, "shape", ()) or ())
            parts = list(spec) + [None] * (len(shape) - len(spec))
            for d, dim in enumerate(shape):
                if parts[d] is None and dim % size == 0 and dim >= size:
                    parts[d] = self.batch_axis
                    return P(*parts)
            return spec

        # tree drives the traversal (its leaves are arrays); each P in
        # `base` aligns as the matching leaf via flatten_up_to
        return jax.tree_util.tree_map(compose, tree, base)
