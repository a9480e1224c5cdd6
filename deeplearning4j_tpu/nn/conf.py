"""Configuration system — parity with reference `nn/conf/*`.

Reference: `NeuralNetConfiguration.java:52-115` (~40 per-layer hyperparameter
fields, fluent Builder at :880-1145, Jackson JSON serde at :809-878) and
`MultiLayerConfiguration.java:34-46` (layer list, `pretrain`, `backward`,
per-layer `ConfOverride` hooks at :235+, `InputPreProcessor` map).

TPU-native design: frozen dataclasses.  Frozen ⇒ hashable ⇒ usable as static
arguments to `jax.jit`; "builder" chaining is `dataclasses.replace`, and the
reference's `ConfOverride` per-layer hooks become `override(i, **kwargs)`.
JSON round-trip is capability parity with `toJson/fromJson`.
"""

from __future__ import annotations

import dataclasses
import enum
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

from deeplearning4j_tpu.nd.losses import LossFunction
from deeplearning4j_tpu.nd.ops import Activation
from deeplearning4j_tpu.nn.weights import WeightInit


class OptimizationAlgorithm(str, enum.Enum):
    """Parity: `nn/api/OptimizationAlgorithm` + `Solver.java:54-70` dispatch."""

    GRADIENT_DESCENT = "gradient_descent"          # line-searched GD
    ITERATION_GRADIENT_DESCENT = "iteration_gradient_descent"  # plain SGD steps
    CONJUGATE_GRADIENT = "conjugate_gradient"
    LBFGS = "lbfgs"
    HESSIAN_FREE = "hessian_free"

    def __str__(self) -> str:
        return self.value


class LayerType(str, enum.Enum):
    DENSE = "dense"
    OUTPUT = "output"
    AUTOENCODER = "autoencoder"
    RBM = "rbm"
    RECURSIVE_AUTOENCODER = "recursive_autoencoder"
    LSTM = "lstm"
    GRAVES_LSTM = "graves_lstm"
    CONVOLUTION = "convolution"
    SUBSAMPLING = "subsampling"
    BATCH_NORM = "batch_norm"
    EMBEDDING = "embedding"
    ATTENTION = "attention"
    TRANSFORMER_FFN = "transformer_ffn"
    KDA = "kda"                    # gated delta-rule linear attention
    MLA = "mla"                    # multi-head latent attention
    GQA = "gqa"                    # grouped K/V heads, full or windowed
    SWIGLU = "swiglu"              # gated (SiLU) FFN under an RMSNorm
    MOE = "moe"                    # routed experts + one shared expert
    SHORT_CONV = "shortconv"       # gated short convolution (LFM2's mixer)
    PARALLEL = "parallel"          # attention and experts off one LayerNorm
    GDN = "gdn"                    # gated delta rule, a decay a value head

    def __str__(self) -> str:
        return self.value


class RBMUnit(str, enum.Enum):
    """RBM visible/hidden unit types — parity: `RBM.java:83-89` (4 x 4)."""

    BINARY = "binary"
    GAUSSIAN = "gaussian"
    RECTIFIED = "rectified"
    SOFTMAX = "softmax"

    def __str__(self) -> str:
        return self.value


class PoolingType(str, enum.Enum):
    MAX = "max"
    AVG = "avg"
    SUM = "sum"
    NONE = "none"

    def __str__(self) -> str:
        return self.value


@dataclass(frozen=True)
class Distribution:
    """Weight-init distribution spec (parity: `nn/conf/distribution`)."""

    kind: str = "normal"  # normal | uniform | binomial
    mean: float = 0.0
    std: float = 1.0
    lo: float = -1.0
    hi: float = 1.0
    p: float = 0.5

    def sampler(self):
        from deeplearning4j_tpu.nd import random as ndr

        if self.kind == "normal":
            return lambda key, shape: ndr.normal(key, self.mean, self.std, shape)
        if self.kind == "uniform":
            return lambda key, shape: ndr.uniform(key, self.lo, self.hi, shape)
        if self.kind == "binomial":
            return lambda key, shape: ndr.binomial(key, self.p, shape)
        raise ValueError(f"unknown distribution kind {self.kind}")


# -- typed settings of the layer types that carry their own ------------------
# One frozen object a layer type, under `NeuralNetConfiguration.layer_spec`:
# a layer type's sizes live with it, not as flat fields every other layer
# type ignores.  A conf without one serialises exactly as it did before
# the field existed (`to_dict` leaves a None out), so its fingerprint and
# every cache key made from it are unchanged.

@dataclass(frozen=True)
class KDASpec:
    """LayerType.KDA (Kimi Delta Attention, arXiv:2510.26692): `n_heads`
    heads whose keys and values are both `head_dim` wide, a depthwise causal
    convolution of `conv_kernel` taps on q, k and v, and a per-channel decay
    `exp(gate_lower_bound * sigmoid(.))`."""

    n_heads: int
    head_dim: int
    conv_kernel: int = 4
    gate_lower_bound: float = -5.0
    eps: float = 1e-6


@dataclass(frozen=True)
class GDNSpec:
    """LayerType.GDN (Gated DeltaNet, arXiv:2412.06464, as Qwen3-Next runs
    it): `n_v_heads` value heads of `v_dim`, each with a state of `k_dim` x
    `v_dim`, read and written by `n_k_heads` query/key heads of `k_dim`
    (key head j serves value heads j r .. j r + r - 1, r = n_v_heads /
    n_k_heads); a depthwise causal convolution of `conv_kernel` taps on q, k
    and v; a decay `exp(g)` of one number a value head; the output normed a
    value head and gated by SiLU of a projection of its own."""

    n_k_heads: int
    n_v_heads: int
    k_dim: int
    v_dim: int
    conv_kernel: int = 4
    eps: float = 1e-6


@dataclass(frozen=True)
class MLASpec:
    """LayerType.MLA (arXiv:2405.04434): keys and values from one cached
    latent of `kv_lora_rank` and one rotary key of `qk_rope_head_dim` shared
    by all heads.  The options, each off at its default (and left out of
    the conf's JSON there, `OMIT_AT_DEFAULT`: a conf from before them
    serialises as it did):
    `q_lora_rank` R > 0: the query comes through a latent of its own, `cq =
    RMSNorm(Wqa u)` in R^R, `q = Wqb cq`; 0 is `q = Wq u`.
    `lora_rescale`: `cq` times `sqrt(n_in / q_lora_rank)` and the cached
    latent times `sqrt(n_in / kv_lora_rank)`.
    `window` W > 0: a token sees itself and the W - 1 before it, and the
    decode state is a ring of W latents written at `pos % W`; 0 is full
    causal attention over a table of `max_seq` latents.
    `gate`: every head's output times `sigmoid(Wg u)_h` before `Wo`.
    `index_topk` K > 0 (with `index_n_heads` heads of `index_head_dim`, and
    a low-rank query): a learned indexer scores every cached position for
    the token, `sum_j w_j relu(qi_j . ki_s)`, and attention is over the K
    positions of largest score alone (all of them while there are at most
    K); its keys `ki` are a third table of the decode state."""

    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    q_lora_rank: int = 0
    lora_rescale: bool = False
    window: int = 0
    gate: bool = False
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    OMIT_AT_DEFAULT = ("q_lora_rank", "lora_rescale", "window", "gate",
                       "index_n_heads", "index_head_dim", "index_topk")

    @property
    def scope_kind(self) -> Optional[str]:
        """What `layer_scope` calls the layer where a model has the type in
        two geometries (`mla_window` beside `mla_full`, told by the
        low-rank query both have); None, the layer type's own name, for
        the plain layer."""
        if self.window:
            return "mla_window"
        return "mla_full" if self.q_lora_rank else None


@dataclass(frozen=True)
class GQASpec:
    """LayerType.GQA: `n_heads` query heads of `head_dim` over `n_kv_heads`
    key/value heads (query head j reads K/V head j // (n_heads //
    n_kv_heads)), rotary positions, no bias.  `window` 0 is full causal
    attention, whose decode state is a K/V table of `max_seq` positions;
    `window` W > 0 lets a token see itself and the W - 1 before it, and the
    state is a ring of W cells written at `pos % W`.  `yarn`, where given,
    is `(factor, original_max_position_embeddings, beta_fast, beta_slow,
    attention_factor)`: the rotary frequencies are blended toward
    `1 / factor` of themselves and cos and sin scaled by the last (arXiv:
    2309.00071); None is plain rotary at `rope_theta`.  `qk_norm` puts an
    RMSNorm over each head's `head_dim` of q and of k before the rotation.
    `rotary` pairs the dimensions that turn together: `half` (rotate-half,
    m with m + head_dim/2), `pairs` (interleaved, 2m with 2m + 1), or
    `none` (no positions at all).  `rotary_dim` R > 0 turns the first R
    dimensions of a head alone (rotate-half within them; 0: all of them).
    `out_gate`: the query projection gives each head a query and a gate of
    `head_dim` side by side, and the attention's output is multiplied by
    the gate's sigmoid, element by element, before Wo.  The last three are
    left out of the conf's JSON at their defaults (`OMIT_AT_DEFAULT`)."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int = 0
    rope_theta: float = 10000.0
    yarn: Optional[Tuple[float, ...]] = None
    qk_norm: bool = False
    eps: float = 1e-6
    rotary: str = "half"
    rotary_dim: int = 0
    out_gate: bool = False

    OMIT_AT_DEFAULT = ("rotary", "rotary_dim", "out_gate")

    @property
    def scope_kind(self) -> str:
        """What `layer_scope` calls the layer: a trace read by scope then
        tells window layers from full ones."""
        return "gqa_window" if self.window else "gqa_full"


@dataclass(frozen=True)
class ShortConvSpec:
    """LayerType.SHORT_CONV: a gated short convolution in place of
    attention, `[B; C; x] = W_in u`, `o_t = (C_t * sum_j w_j (B * x)_{t-K+1+j})
    W_out` with `kernel` K taps a channel, no bias and no activation."""

    kernel: int = 3
    eps: float = 1e-6


@dataclass(frozen=True)
class SwiGLUSpec:
    """LayerType.SWIGLU: `down(silu(gate x) * up x)` of width `hidden`."""

    hidden: int
    eps: float = 1e-6


@dataclass(frozen=True)
class MoESpec:
    """LayerType.MOE: a router over `n_routed` experts (`score` `sigmoid`,
    or `softmax` over all of them) in `n_group` groups (the best
    `topk_group` groups stay, then the `top_k` best experts among them), of
    which this layer holds `n_held` from `first_held` on and computes those
    picks alone; one shared expert of `shared_hidden` is whole here
    (`shared_hidden` 0: there is none, and no such leaves), its output times
    `shared_scale` (several shared experts averaged are one of their widths
    summed, scaled by one over their number), and `router_bias` says
    whether a learned bias enters the choice.  `ranks`: the chips of the
    expert-parallel group whose rows reach the held experts where this layer
    is one chip of a deployment (1: this chip's rows alone); the form of the
    held experts' product counts their picks (`experts.experts_form`).
    `shared_gate`: the shared expert's output is scaled, a token at a time,
    by `sigmoid(u . w_sg)` of a learned vector (after `shared_scale`).  The
    fields of `OMIT_AT_DEFAULT` are left out of the conf's JSON where they
    hold their defaults: a conf from before them serialises as it did."""

    n_routed: int
    n_held: int
    hidden: int
    shared_hidden: int
    first_held: int = 0
    top_k: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling: float = 1.0
    eps: float = 1e-6
    score: str = "sigmoid"
    router_bias: bool = True
    shared_scale: float = 1.0
    ranks: int = 1
    shared_gate: bool = False

    OMIT_AT_DEFAULT = ("score", "router_bias", "shared_scale", "ranks",
                       "shared_gate")


@dataclass(frozen=True)
class HeadSpec:
    """LayerType.OUTPUT as a language model's head: a norm (`norm` `rms`, or
    `layer`: a LayerNorm with a weight and no bias), then a matrix with no
    bias whose logits come out in float32 whatever type the weights are
    kept in.  `tied`: the matrix is the EMBEDDING layer's table itself,
    read in place (the head holds the norm alone).  Both are left out of
    the conf's JSON at their defaults."""

    eps: float = 1e-6
    norm: str = "rms"
    tied: bool = False

    OMIT_AT_DEFAULT = ("norm", "tied")


@dataclass(frozen=True)
class ParallelSpec:
    """LayerType.PARALLEL: attention and an expert FFN side by side off one
    LayerNorm (a weight, no bias, `eps`), `x + Attn(LN(x)) + MoE(LN(x))`:
    `attn` is a `GQASpec`, `ffn` a `MoESpec` (their own `eps` unused)."""

    attn: GQASpec
    ffn: MoESpec
    eps: float = 1e-5

    @property
    def scope_kind(self) -> str:
        """`parallel_window` or `parallel_full`, by the mixer's window."""
        return "parallel_window" if self.attn.window else "parallel_full"


LAYER_SPECS = {c.__name__: c for c in (KDASpec, MLASpec, GQASpec, SwiGLUSpec,
                                       MoESpec, HeadSpec, ShortConvSpec,
                                       ParallelSpec, GDNSpec)}


def _spec_dict(spec) -> Dict[str, Any]:
    """A typed spec as JSON: its kind, then its fields (a spec inside it as
    one of these), without those of its `OMIT_AT_DEFAULT` that hold their
    defaults."""
    out = {"kind": type(spec).__name__, **dataclasses.asdict(spec)}
    for f in dataclasses.fields(spec):
        value = getattr(spec, f.name)
        if type(value) in LAYER_SPECS.values():
            out[f.name] = _spec_dict(value)
    for name in getattr(spec, "OMIT_AT_DEFAULT", ()):
        if out[name] == type(spec).__dataclass_fields__[name].default:
            del out[name]
    return out


def _spec_of(d: Dict[str, Any]):
    """`_spec_dict`'s inverse (JSON has no tuples: a list is one)."""
    d = dict(d)
    kind = LAYER_SPECS[d.pop("kind")]
    for k, v in d.items():
        if isinstance(v, dict) and "kind" in v:
            d[k] = _spec_of(v)
        elif isinstance(v, list):
            d[k] = tuple(v)
    return kind(**d)


@dataclass(frozen=True)
class NeuralNetConfiguration:
    """Per-layer hyperparameters (reference `NeuralNetConfiguration.java:52-115`)."""

    layer_type: LayerType = LayerType.DENSE
    n_in: int = 0
    n_out: int = 0

    activation: Activation = Activation.SIGMOID
    weight_init: WeightInit = WeightInit.VI
    dist: Optional[Distribution] = None
    loss_function: LossFunction = LossFunction.MCXENT

    # optimization
    optimization_algo: OptimizationAlgorithm = OptimizationAlgorithm.CONJUGATE_GRADIENT
    lr: float = 1e-1
    num_iterations: int = 100
    momentum: float = 0.5
    momentum_after: Tuple[Tuple[int, float], ...] = ()  # (iteration, momentum) schedule
    l1: float = 0.0
    l2: float = 0.0
    use_regularization: bool = False
    use_adagrad: bool = True
    adagrad_reset_iterations: int = 0  # 0 = never reset (ref: resetAdaGradIterations)
    constrain_gradient_to_unit_norm: bool = False
    gradient_clip_norm: float = 0.0  # 0 = off (new capability)
    minimize: bool = True
    step_function: str = "default"  # default | gradient | negative_default
                                    # | negative_gradient (stepfunctions/*)
    # pluggable termination conditions (ref optimize/terminations/*):
    # any of "eps" (EpsTermination), "norm2" (Norm2Termination),
    # "zero_direction" (ZeroDirection); empty tuple = run all iterations
    termination_conditions: Tuple[str, ...] = ("eps", "norm2")
    termination_eps: float = 1e-6
    termination_norm2: float = 1e-8
    # updater selection: "" = legacy chain (use_adagrad flag + momentum),
    # or one of sgd | adagrad | nesterov | adam | rmsprop (parity-plus:
    # the reference stops at AdaGrad/momentum, GradientAdjustment.java:159)
    updater: str = ""
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    rmsprop_decay: float = 0.95
    num_line_search_iterations: int = 20
    lbfgs_memory: int = 4          # two-loop history (LBFGS.java m=4)
    hf_cg_iterations: int = 32     # inner CG trip count (Martens HF)
    hf_initial_lambda: float = 1.0  # initial LM damping (HF)

    # stochastic regularization
    dropout: float = 0.0
    drop_connect: bool = False

    # pretrain-layer knobs
    corruption_level: float = 0.3   # denoising AE
    sparsity: float = 0.0
    k: int = 1                      # CD-k Gibbs steps (RBM.java:121-201)
    visible_unit: RBMUnit = RBMUnit.BINARY
    hidden_unit: RBMUnit = RBMUnit.BINARY

    # attention knobs (new scope — no attention in the 2015 reference)
    n_heads: int = 4
    causal: bool = False
    attention_block_size: int = 0  # 0 = full attention; >0 = blockwise/flash
    attention_impl: str = "auto"   # auto | full | blockwise | flash (pallas)
    # skip the mask arithmetic on fully-unmasked causal flash tiles (MFU
    # campaign leg d; value-identical, gated for A/B benching)
    attention_block_skip: bool = False
    ffn_hidden: int = 0            # transformer FFN width (0 = 4*n_in)
    max_seq_len: int = 0           # >0: learned positional embedding table
    lstm_impl: str = "auto"        # auto | scan | fused (pallas cell)

    # MFU campaign hot-path flags (each bitwise-f32-identical to the path
    # it replaces; parity-tested in tests/test_mfu_paths.py)
    sparse_labels: bool = False    # int class-id labels: gather mcxent, no
                                   # [rows, vocab] one-hot gemm
    fused_updater: bool = False    # accepted and inert (PR 31): the updater
                                   # has one layout; the benchmark's GPT-2
                                   # configurations still pass the key
    attention_fused_bwd: bool = False  # flash bwd via fused Pallas kernels
                                   # over saved logsumexp residuals (no
                                   # fwd recompute); only consulted when
                                   # the flash impl dispatches — training-
                                   # only, never an infer-cache key
                                   # (allclose, not bitwise, vs recompute)

    # batch-norm running-stat decay (ema = m*ema + (1-m)*batch)
    batch_norm_momentum: float = 0.9

    # conv knobs (NCHW)
    kernel_size: Tuple[int, int] = (5, 5)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    n_channels: int = 1
    pooling: PoolingType = PoolingType.MAX

    # misc
    batch_size: int = 0             # 0 = whatever the iterator yields
    seed: int = 123
    dtype: str = "float32"          # params (master-weight) dtype
    compute_dtype: str = ""         # matmul/conv operand dtype ("" = dtype);
                                    # "bfloat16" = mixed precision: bf16 MXU
                                    # inputs, f32 accumulation, f32 params
    remat: bool = False             # jax.checkpoint this layer's forward:
                                    # recompute activations in backward,
                                    # trading FLOPs for HBM (big batches)
    # the layer type's own typed settings (one of LAYER_SPECS), for the
    # layer types that have them; None everywhere else
    layer_spec: Optional[Any] = None

    def replace(self, **kwargs) -> "NeuralNetConfiguration":
        return dataclasses.replace(self, **kwargs)

    # --- JSON serde (parity: toJson/fromJson, NeuralNetConfiguration.java:809-878)
    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        for k, v in d.items():
            if isinstance(v, enum.Enum):
                d[k] = v.value
        if d.get("dist") is not None and isinstance(self.dist, Distribution):
            d["dist"] = dataclasses.asdict(self.dist)
        if self.layer_spec is None:
            del d["layer_spec"]     # as before the field existed
        else:
            d["layer_spec"] = _spec_dict(self.layer_spec)
        return d

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "NeuralNetConfiguration":
        d = dict(d)
        conv = {
            "layer_type": LayerType,
            "activation": Activation,
            "weight_init": WeightInit,
            "loss_function": LossFunction,
            "optimization_algo": OptimizationAlgorithm,
            "visible_unit": RBMUnit,
            "hidden_unit": RBMUnit,
            "pooling": PoolingType,
        }
        for k, e in conv.items():
            if k in d and d[k] is not None:
                d[k] = e(d[k])
        if d.get("dist") is not None:
            d["dist"] = Distribution(**d["dist"])
        if d.get("layer_spec") is not None:
            d["layer_spec"] = _spec_of(d["layer_spec"])
        for k in ("momentum_after",):
            if k in d and d[k] is not None:
                d[k] = tuple(tuple(x) for x in d[k])
        for k in ("kernel_size", "stride", "padding",
                  "termination_conditions"):
            if k in d and d[k] is not None:
                d[k] = tuple(d[k])
        known = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in known}
        return cls(**d)

    @classmethod
    def from_json(cls, s: str) -> "NeuralNetConfiguration":
        return cls.from_dict(json.loads(s))


@dataclass(frozen=True)
class MultiLayerConfiguration:
    """Stacked-network config (reference `MultiLayerConfiguration.java:34-46`).

    `confs` is one `NeuralNetConfiguration` per layer (the last is normally an
    OUTPUT layer).  `pretrain`/`backprop` gate the phases of
    `MultiLayerNetwork.fit` exactly as the reference's `pretrain`/`backward`
    flags do (`MultiLayerNetwork.java:928-992`).  `input_preprocessors` maps
    layer index -> preprocessor name (see nn/layers/preprocessor.py).
    """

    confs: Tuple[NeuralNetConfiguration, ...] = ()
    pretrain: bool = False
    backprop: bool = True
    use_drop_connect: bool = False
    damping_factor: float = 10.0
    input_preprocessors: Tuple[Tuple[int, str], ...] = ()

    @property
    def n_layers(self) -> int:
        return len(self.confs)

    def conf(self, i: int) -> NeuralNetConfiguration:
        return self.confs[i]

    def preprocessor(self, i: int) -> Optional[str]:
        for idx, name in self.input_preprocessors:
            if idx == i:
                return name
        return None

    def override(self, i: int, **kwargs) -> "MultiLayerConfiguration":
        """Per-layer override hook (parity: `ConfOverride`, builder :235+)."""
        confs = list(self.confs)
        confs[i] = confs[i].replace(**kwargs)
        return dataclasses.replace(self, confs=tuple(confs))

    def replace(self, **kwargs) -> "MultiLayerConfiguration":
        return dataclasses.replace(self, **kwargs)

    def with_compute_dtype(self, compute_dtype: str) -> "MultiLayerConfiguration":
        """Every layer's matmul/conv compute dtype flipped at once (the
        `layers.base.mixed_matmul` lever) — params/master dtype stays
        put.  The serve-precision policy derives its bf16 confs through
        this."""
        return self.replace(confs=tuple(
            c.replace(compute_dtype=compute_dtype) for c in self.confs))

    def to_json(self) -> str:
        return json.dumps(
            {
                "confs": [c.to_dict() for c in self.confs],
                "pretrain": self.pretrain,
                "backprop": self.backprop,
                "use_drop_connect": self.use_drop_connect,
                "damping_factor": self.damping_factor,
                "input_preprocessors": [list(x) for x in self.input_preprocessors],
            },
            indent=2,
            sort_keys=True,
        )

    @classmethod
    def from_json(cls, s: str) -> "MultiLayerConfiguration":
        d = json.loads(s)
        return cls(
            confs=tuple(NeuralNetConfiguration.from_dict(c) for c in d["confs"]),
            pretrain=d.get("pretrain", False),
            backprop=d.get("backprop", True),
            use_drop_connect=d.get("use_drop_connect", False),
            damping_factor=d.get("damping_factor", 10.0),
            input_preprocessors=tuple(
                (int(i), str(n)) for i, n in d.get("input_preprocessors", [])
            ),
        )


class ListBuilder:
    """Fluent multi-layer builder — parity with the reference's
    `new NeuralNetConfiguration.Builder()....list(n).override(...).build()`
    idiom (`MultiLayerConfiguration.Builder`, `MultiLayerTest.java:55-110`).
    """

    def __init__(self, base: NeuralNetConfiguration, n_layers: int):
        self._confs = [base] * n_layers
        self._pretrain = False
        self._backprop = True
        self._preprocessors: Dict[int, str] = {}

    def hidden_layer_sizes(self, sizes, n_in: int, n_out: int) -> "ListBuilder":
        """Set n_in/n_out per layer from input dim, hidden sizes, output dim."""
        dims = [n_in] + list(sizes) + [n_out]
        for i in range(len(self._confs)):
            self._confs[i] = self._confs[i].replace(
                n_in=dims[i], n_out=dims[i + 1]
            )
        return self

    def override(self, i: int, **kwargs) -> "ListBuilder":
        self._confs[i] = self._confs[i].replace(**kwargs)
        return self

    def pretrain(self, flag: bool) -> "ListBuilder":
        self._pretrain = flag
        return self

    def backprop(self, flag: bool) -> "ListBuilder":
        self._backprop = flag
        return self

    def input_preprocessor(self, i: int, name: str) -> "ListBuilder":
        self._preprocessors[i] = name
        return self

    def build(self) -> MultiLayerConfiguration:
        return MultiLayerConfiguration(
            confs=tuple(self._confs),
            pretrain=self._pretrain,
            backprop=self._backprop,
            input_preprocessors=tuple(sorted(self._preprocessors.items())),
        )


def list_builder(base: NeuralNetConfiguration, n_layers: int) -> ListBuilder:
    return ListBuilder(base, n_layers)
