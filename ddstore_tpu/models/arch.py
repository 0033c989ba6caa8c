"""Described architectures: a published model's ``config.json`` as one tuple
a family, and :func:`lm_from_description`, which builds a
:class:`~.transformer.TransformerLM` from one. A family's tuple is its
fields (what a description sets) over :class:`Arch`, the traits every arch
answers. A new family is a tuple, a parser and a row of :data:`FAMILIES`.
"""

from __future__ import annotations

from typing import Any, Mapping, NamedTuple, Optional, Tuple


class Arch:
    """What the layers, the losses and the set-up read of a described
    architecture, each trait with the answer most families give. A family
    overrides one as a field, which its descriptions may set, or as a
    constant of its class, which a description may repeat and not change
    (:meth:`fixed`). Besides, ``mixer(layer)`` is the layer's mixer (a key
    of ``mixers.MIXERS``) or None, and ``mlp(layer)`` its MLP (``"dense"``,
    or ``"experts"``: such a layer returns a load vector) or None."""

    __slots__ = ()

    router_input: str = "ln2"        # "ln1": attention's input, before it
    router_scoring: str = "sigmoid"  # with a correction bias; or "softmax"
    block_length: int = 0            # > 0: trained by block diffusion
    qk_norm: bool = False            # q and k RMS-normed a head
    rotary: bool = True              # q and k rotated on the whole head
    n_shared_experts: int = 0
    # the shared expert's width; None: n_shared x the routed width
    moe_shared_expert_intermediate_size: Optional[int] = None
    expert_activation: str = "swiglu"   # SharedRoutedMoe's ``activation``
    routed_scaling_factor: float = 1.0
    route_eps: float = 0.0           # the routing weights' normaliser epsilon
    bias_update_speed: float = 0.0   # noaux_tc's step toward balance
    tie_word_embeddings: bool = False   # the head is the embedding's matrix
    num_nextn_predict_layers: int = 0   # multi-token-prediction modules
    mtp_loss_weight: float = 0.0

    def attention(self, layer: int) -> Tuple[bool, Optional[int]]:
        """``(rotary, window)`` of layer ``layer``'s attention: whether it
        rotates q and k, and its sliding window (None: causal)."""
        return self.rotary, None

    @classmethod
    def fixed(cls) -> dict:
        """The traits that are no field of the family's tuple, by name, with
        the one value every model of the type has."""
        return {k: getattr(cls, k) for k in TRAITS if k not in cls._fields}


# Every trait an arch answers, by name.
TRAITS = tuple(Arch.__annotations__)


def _family(fields):
    """A family's tuple: ``fields``, the NamedTuple of its fields, its
    ``mixer`` and ``mlp`` and the traits it fixes, over :class:`Arch`."""
    return type(fields.__name__, (fields, Arch), {
        "__slots__": (), "__doc__": fields.__doc__,
        "__module__": fields.__module__})


@_family
class MlaMoeArch(NamedTuple):
    """A latent-attention (MLA), shared + routed expert, multi-token-
    prediction decoder, by the keys of its published ``config.json`` (the
    ``glm4_moe_lite`` / DeepSeek-V3 family). ``TransformerLM(arch=...)``
    builds :class:`~.transformer.DecoderBlock` layers from it; ``vocab``,
    ``dim``, ``heads`` and ``layers`` stay the model's own fields.
    ``n_routed_experts`` is the router's width (the published count);
    ``expert_share = (which, of)`` is this chip's share of them
    (:class:`~.moe.SharedRoutedMoe`)."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 1
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    expert_share: Tuple[int, int] = (0, 1)
    bias_update_speed: float = 0.0
    tie_word_embeddings: bool = False
    route_eps: float = 0.0           # route_noaux_tc's normaliser epsilon
    expert_activation: str = "swiglu"
    moe_shared_expert_intermediate_size: Optional[int] = None

    def mixer(self, layer: int) -> Optional[str]:
        return "mla"

    def mlp(self, layer: int) -> Optional[str]:
        return "dense" if layer < self.first_k_dense_replace else "experts"


@_family
class Lfm2MoeArch(NamedTuple):
    """A gated-short-convolution / grouped-query-attention decoder with
    bias-routed experts and no shared expert, by the keys of its published
    ``config.json`` (``model_type`` ``lfm2_moe``): ``layer_types`` says a
    layer which mixer it has (``conv`` or ``full_attention``), the first
    ``first_k_dense_replace`` layers (the published ``num_dense_layers``)
    have a dense SwiGLU MLP and the others ``num_experts_per_tok`` of
    ``n_routed_experts`` routed experts (the router's width: the published
    ``num_experts``), of which this chip holds ``expert_share``'s.
    ``rms_norm_eps`` is the published ``norm_eps``. Heads are ``head_dim``
    wide (None: ``dim / heads``), q and k RMS-normed a head (``qk_norm``)
    and rotated (``rotary``)."""

    layer_types: Tuple[str, ...]
    num_key_value_heads: int
    conv_L_cache: int                # taps of the short convolution
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    n_shared_experts: int = 0
    route_eps: float = 1e-6          # Lfm2MoeSparseMoeBlock's normaliser
    tie_word_embeddings: bool = True
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.0
    expert_share: Tuple[int, int] = (0, 1)
    bias_update_speed: float = 0.0
    head_dim: Optional[int] = None
    qk_norm: bool = True
    rotary: bool = True
    expert_activation: str = "swiglu"
    moe_shared_expert_intermediate_size: Optional[int] = None

    def mixer(self, layer: int) -> Optional[str]:
        return self.layer_types[layer]

    def mlp(self, layer: int) -> Optional[str]:
        return "dense" if layer < self.first_k_dense_replace else "experts"


@_family
class NemotronHArch(NamedTuple):
    """A Mamba-2 / attention / expert hybrid every layer of which is one
    branch alone, by the keys of its published ``config.json``
    (``model_type`` ``nemotron_h``): ``pattern`` (the published
    ``hybrid_override_pattern``) says a layer what it is: ``M`` a Mamba-2
    mixer (``mamba_num_heads`` heads of ``mamba_head_dim``, state
    ``ssm_state_size``, ``n_groups`` groups of B and C, ``conv_kernel``
    biased taps, scanned in chunks of ``chunk_size``), ``*`` grouped-query
    attention at ``head_dim`` with no rotary step and no q/k norm, ``E``
    ``num_experts_per_tok`` of ``n_routed_experts`` ungated relu² experts
    (the router's width; this chip holds ``expert_share``'s) beside a
    shared expert ``moe_shared_expert_intermediate_size`` wide. No layer
    has a second branch. ``rms_norm_eps`` is the published
    ``layer_norm_epsilon``; ``time_step_*`` bound the step the Mamba
    layers' ``dt_bias`` is drawn for."""

    pattern: str
    num_key_value_heads: int
    head_dim: int
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    n_groups: int
    conv_kernel: int
    chunk_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    rms_norm_eps: float = 1e-5
    route_eps: float = 1e-20         # NemotronHTopkRouter's normaliser
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.0
    expert_share: Tuple[int, int] = (0, 1)
    bias_update_speed: float = 0.0
    qk_norm: bool = False
    rotary: bool = False
    rope_theta: float = 10000.0      # published, read by nothing
    expert_activation: str = "relu2"

    def mixer(self, layer: int) -> Optional[str]:
        return {"M": "mamba2", "*": "full_attention"}.get(self.pattern[layer])

    def mlp(self, layer: int) -> Optional[str]:
        return "experts" if self.pattern[layer] == "E" else None


@_family
class SdarMoeArch(NamedTuple):
    """A grouped-query-attention decoder of softmax-routed experts trained
    by block diffusion, by the keys of its published ``config.json``
    (``model_type`` ``sdar_moe``; the layer is Qwen3-MoE's): every layer
    ``heads`` query heads on ``num_key_value_heads`` K/V heads of
    ``head_dim``, q and k RMS-normed a head and rotated, then
    ``num_experts_per_tok`` of ``n_routed_experts`` SwiGLU experts (the
    router's width: the published ``num_experts``; this chip holds
    ``expert_share``'s) chosen by a softmax over all of them and
    renormalised, no shared expert, no dense layer, an untied head.

    The objective is the arch's (``transformer.lm_loss``,
    ``transformer.diffusion_noise``): a window is cut into blocks of
    ``block_length``, each block draws a masking probability t uniform on
    ``[noise_low, 1]`` and every position of it is replaced by
    ``mask_token`` with that probability (``None``: the vocabulary's last
    id); the trunk runs on ``[noised ; clean]``, both halves at the
    window's positions, under the block-diffusion mask
    (:class:`~ddstore_tpu.ops.attention.BlockDiffusion`), and the loss is
    the cross-entropy of the masked positions against the window's own
    tokens, weighted 1 / t, over all the window's positions. The draw of
    step n is keyed by ``(noise_seed, n)``. ``block_length = 0`` is the
    same trunk as a causal next-token model."""

    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    block_length: int
    noise_low: float
    mask_token: Optional[int] = None
    noise_seed: int = 0
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    expert_share: Tuple[int, int] = (0, 1)

    router_scoring = "softmax"
    qk_norm = True

    def mixer(self, layer: int) -> Optional[str]:
        return "full_attention"

    def mlp(self, layer: int) -> Optional[str]:
        return "experts"


@_family
class SmallThinkerArch(NamedTuple):
    """A decoder of grouped-query attention, window and full mixed, over
    softmax-routed ReGLU experts whose router reads the layer's input before
    attention, by the keys of its published ``config.json`` (PowerInfer
    SmallThinker; the catalog's row gives no ``model_type``): every layer
    ``heads`` query heads on ``num_key_value_heads`` K/V heads of
    ``head_dim``, no q/k norm; layer i rotates q and k on the whole head
    width where ``rope_layout[i]`` is 1 (no position step at all where it is
    0: NoPE) and sees only the ``sliding_window`` keys up to its own where
    ``sliding_window_layout[i]`` is 1 (causal where it is 0). Then
    ``num_experts_per_tok`` of ``n_routed_experts`` experts ``down(relu(gate
    h) * up h)`` (the router's width: the published
    ``moe_num_primary_experts``; this chip holds ``expert_share``'s) chosen
    by a softmax over the router's logits and renormalised, the router
    reading ``ln1(x)``, the normed input of attention, and the experts
    ``ln2(x + attn)``. No shared expert, no dense layer, an untied head;
    next-token cross-entropy."""

    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    rope_layout: Tuple[int, ...]
    sliding_window_layout: Tuple[int, ...]
    sliding_window: int
    rope_theta: float = 1500000.0
    rms_norm_eps: float = 1e-6
    expert_share: Tuple[int, int] = (0, 1)

    router_scoring = "softmax"
    router_input = "ln1"
    expert_activation = "reglu"

    def mixer(self, layer: int) -> Optional[str]:
        return "sliding_attention" if self.sliding_window_layout[layer] \
            else "full_attention"

    def mlp(self, layer: int) -> Optional[str]:
        return "experts"

    def attention(self, layer: int) -> Tuple[bool, Optional[int]]:
        return (bool(self.rope_layout[layer]),
                self.sliding_window if self.sliding_window_layout[layer]
                else None)


def _refuse_unless(desc: Mapping[str, Any], built) -> None:
    """Raises for a key of ``desc`` whose value is not the one built here
    (``built``: pairs of key and that value; an absent key passes)."""
    for key, want in built:
        if desc.get(key, want) != want:
            raise ValueError(f"{key}={desc[key]!r} is not built here "
                             f"(only {key}={want!r})")


def _described(cls, desc: Mapping[str, Any], built, experts_key: str,
               **fields):
    """``cls`` from ``desc``: what ``built`` says is built and what the
    family fixes (:meth:`Arch.fixed`) refused otherwise, then its fields
    under their own names, then ``fields``. The routed experts are
    ``desc[experts_key]`` a chip of ``expert_parallel = {"chips": n,
    "chip": i}``, so the router is ``chips`` times as wide."""
    _refuse_unless(desc, (*built, *cls.fixed().items()))
    ep = desc.get("expert_parallel", {"chips": 1, "chip": 0})
    held, chips = int(desc[experts_key]), int(ep["chips"])
    return cls(**{**{k: desc[k] for k in cls._fields if k in desc}, **fields,
                  "n_routed_experts": held * chips,
                  "expert_share": (int(ep["chip"]), chips)})


def _mla_arch(desc: Mapping[str, Any]) -> MlaMoeArch:
    """Refused: a ``hidden_act`` other than silu, a ``topk_method`` other
    than noaux_tc, expert groups, ``norm_topk_prob`` false, attention
    biases, a ``rope_scaling``, a partial rotary factor, and grouped K/V
    heads."""
    return _described(MlaMoeArch, desc, (
        ("hidden_act", "silu"), ("topk_method", "noaux_tc"), ("n_group", 1),
        ("topk_group", 1), ("norm_topk_prob", True),
        ("attention_bias", False), ("rope_scaling", None),
        ("partial_rotary_factor", 1),
        ("num_key_value_heads", desc["num_attention_heads"])),
        "n_routed_experts")


def _lfm2_arch(desc: Mapping[str, Any]) -> Lfm2MoeArch:
    """Its layers by ``layer_types``, one a layer, each ``conv`` or
    ``full_attention``. Refused: convolution biases, ``norm_topk_prob``
    false, no expert bias, a ``rope_scaling``, an untied embedding."""
    _refuse_unless(desc, (
        ("conv_bias", False), ("norm_topk_prob", True),
        ("use_expert_bias", True), ("rope_scaling", None),
        ("tie_embedding", True)))
    kinds = tuple(desc["layer_types"])
    if len(kinds) != int(desc["num_hidden_layers"]):
        raise ValueError(f"layer_types has {len(kinds)} entries for "
                         f"num_hidden_layers={desc['num_hidden_layers']}")
    for kind in kinds:
        if kind not in ("conv", "full_attention"):
            raise ValueError(f"layer_types entry {kind!r} is not built "
                             f"here (only 'conv' and 'full_attention')")
    rope = desc.get("rope_parameters", desc)
    return _described(
        Lfm2MoeArch, desc, (), "num_experts", layer_types=kinds,
        first_k_dense_replace=int(desc["num_dense_layers"]),
        rope_theta=float(rope["rope_theta"]),
        rms_norm_eps=float(desc["norm_eps"]), tie_word_embeddings=True)


def _nemotron_arch(desc: Mapping[str, Any]) -> NemotronHArch:
    """Its layers by ``hybrid_override_pattern``, one of ``M``, ``E`` and
    ``*`` a layer. Refused: biases in any projection, a convolution
    without one, expert groups, ``norm_topk_prob`` false, other
    activations than relu2 and silu."""
    _refuse_unless(desc, (
        ("mamba_proj_bias", False), ("use_bias", False), ("mlp_bias", False),
        ("attention_bias", False), ("use_conv_bias", True), ("n_group", 1),
        ("topk_group", 1), ("norm_topk_prob", True),
        ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu")))
    pattern = str(desc["hybrid_override_pattern"])
    if len(pattern) != int(desc["num_hidden_layers"]):
        raise ValueError(f"hybrid_override_pattern has {len(pattern)} "
                         f"layers for num_hidden_layers="
                         f"{desc['num_hidden_layers']}")
    for kind in pattern:
        if kind not in "ME*":
            raise ValueError(
                f"hybrid_override_pattern layer {kind!r} is not built here "
                f"(only 'M', 'E' and '*'; '-' is a dense MLP layer)")
    return _described(NemotronHArch, desc, (), "n_routed_experts",
                      pattern=pattern,
                      rms_norm_eps=float(desc["layer_norm_epsilon"]))


def _sdar_arch(desc: Mapping[str, Any]) -> SdarMoeArch:
    """The published keys and the objective's ``block_length``,
    ``noise_low``, ``mask_token``, ``noise_seed``, each under the field's
    own name. Refused: ``use_sliding_window`` true, a ``rope_scaling``,
    ``attention_bias`` true, ``norm_topk_prob`` false, a non-empty
    ``mlp_only_layers``, a ``decoder_sparse_step`` other than 1, a
    ``hidden_act`` other than silu, and another value of a constant of the
    class (``router_scoring``, ``tie_word_embeddings`` ...)."""
    return _described(SdarMoeArch, desc, (
        ("use_sliding_window", False), ("rope_scaling", None),
        ("attention_bias", False), ("norm_topk_prob", True),
        ("mlp_only_layers", []), ("decoder_sparse_step", 1),
        ("hidden_act", "silu")), "num_experts")


def _smallthinker_arch(desc: Mapping[str, Any]) -> SmallThinkerArch:
    """Its layers by ``rope_layout`` and ``sliding_window_layout``, a 0 or
    a 1 a layer each; three fields by published keys of other names.
    Refused: a ``rope_scaling``, ``attention_bias`` true, ``norm_topk_prob``
    or ``moe_primary_router_apply_softmax`` false, an early router off,
    secondary experts on, and another value of a constant of the class."""
    layers = int(desc["num_hidden_layers"])
    layouts = {}
    for key in ("rope_layout", "sliding_window_layout"):
        layouts[key] = tuple(int(x) for x in desc[key])
        if len(layouts[key]) != layers or not set(layouts[key]) <= {0, 1}:
            raise ValueError(f"{key} {list(desc[key])}: one 0 or 1 for each "
                             f"of num_hidden_layers={layers}")
    return _described(SmallThinkerArch, desc, (
        ("rope_scaling", None), ("attention_bias", False),
        ("norm_topk_prob", True), ("moe_primary_router_apply_softmax", True),
        ("moe_enable_early_router", True),
        ("moe_enable_secondary_experts", False)),
        "moe_num_primary_experts", **layouts,
        moe_intermediate_size=desc["moe_ffn_hidden_size"],
        num_experts_per_tok=desc["moe_num_active_primary_experts"],
        sliding_window=desc["sliding_window_size"])


# A description's family is the first row whose ``model_type`` it gives or
# whose keys it has all of: (model_type, keys, parser).
FAMILIES = (
    ("smallthinker", ("moe_num_primary_experts", "sliding_window_layout"),
     _smallthinker_arch),
    ("sdar_moe", None, _sdar_arch),
    ("nemotron_h", None, _nemotron_arch),
    ("lfm2_moe", ("layer_types", "conv_L_cache"), _lfm2_arch),
    (None, ("q_lora_rank",), _mla_arch),
)


def lm_from_description(desc: Mapping[str, Any], **kw):
    """A :class:`~.transformer.TransformerLM` from one description of the
    architecture: the keys of a published model's ``config.json``, of the
    first family of :data:`FAMILIES` it matches (its parser says what it
    reads and what it refuses; what a description asks for and is not
    built raises, naming the key and the value that is), or else the dense
    block's own keys (``vocab``, ``dim``, ``heads``, ``layers``,
    ``mlp_ratio``; ``experts`` / ``moe_top_k`` for the capacity-bound
    ``MoeMlp``). ``kw`` are further ``TransformerLM`` fields
    (``compute_dtype``, ``mesh``, ``remat``...)."""
    from .transformer import TransformerLM

    for model_type, keys, parse in FAMILIES:
        if (model_type is not None and desc.get("model_type") == model_type
                or keys is not None and all(k in desc for k in keys)):
            break
    else:
        return TransformerLM(
            vocab=int(desc["vocab"]), dim=int(desc["dim"]),
            heads=int(desc["heads"]), layers=int(desc["layers"]),
            mlp_ratio=int(desc.get("mlp_ratio", 4)),
            n_experts=int(desc.get("experts", 0)),
            moe_top_k=int(desc.get("moe_top_k", 1)), **kw)
    if "remat_policy" in desc and "remat_policy" not in kw:
        kw = dict(kw, remat=True, remat_policy=desc["remat_policy"])
    return TransformerLM(
        vocab=int(desc["vocab_size"]), dim=int(desc["hidden_size"]),
        heads=int(desc["num_attention_heads"]),
        layers=int(desc["num_hidden_layers"]), arch=parse(desc), **kw)
