"""Layer protocol.

The reference splits a layer into a config class (org.deeplearning4j.nn.conf.
layers.*) and an implementation class (org.deeplearning4j.nn.layers.*) bound to
a param view in the model's flat buffer. Here a layer is ONE immutable config
dataclass with pure functions:

* ``output_type(input)``   — InputType shape inference (reference: getOutputType)
* ``with_input(input)``    — returns a config with nIn/shape fields resolved
                             (reference: setNIn during setInputType walk)
* ``init(key, dtype)``     — build the param pytree (dict of named arrays,
                             names matching the reference's param keys W/b/RW/
                             gamma/beta... for checkpoint familiarity)
* ``init_state(dtype)``    — non-trainable state (BN running stats, RNN carry)
* ``apply(params, state, x, ctx)`` -> (y, new_state)

``apply`` is trace-friendly: no Python branching on array values; ``train`` is
a static Python bool baked into the jitted train/infer programs.

Backprop does not exist as a method — jax reverse-mode AD differentiates
``apply`` directly, which removes the reference's entire backpropGradient
codepath (and its class of fwd/bwd mismatch bugs).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax

from ...core.config import register_config
from ..activations import Activation
from ..input_type import InputType
from ..weights import Distribution, WeightInit

Params = Dict[str, Any]
State = Dict[str, Any]

_TRACE = threading.local()


@contextlib.contextmanager
def fresh_rows():
    """Trace the multi-token calls made inside as PREFILLS of fresh rows:
    each row of a decode state stands at position 0 with nothing before it,
    so a mixer may attend the call's tokens alone. Outside it, a
    multi-token call over a decode state is a window over what the state
    holds (a speculative verify), which the mixer attends. Read while a
    program is traced (:func:`rows_are_fresh`): enter it inside the
    function that is jitted, so that its compiled program is always traced
    the same way."""
    depth = getattr(_TRACE, "fresh", 0)
    _TRACE.fresh = depth + 1
    try:
        yield
    finally:
        _TRACE.fresh = depth


def rows_are_fresh() -> bool:
    """Whether a :func:`fresh_rows` block is open on this thread."""
    return getattr(_TRACE, "fresh", 0) > 0


@dataclasses.dataclass(frozen=True)
class DistContext:
    """Static data-parallel execution context for layers that compute
    cross-replica statistics (distributed batch norm). Set by
    ``DistributedTrainer`` when building its step; ``None`` everywhere
    else (single-device Solver, inference), so layers fall back to their
    local spelling.

    ``axis`` is the named data-mesh axis when the forward runs inside
    ``shard_map`` (the explicit strategy path — collectives like
    ``lax.psum`` may bind it); ``None`` on the implicit GSPMD path,
    where the batch array is GLOBAL and group statistics are spelled as
    a sharding-friendly reshape instead. ``n_shards`` is the data-axis
    width either way, and ``bn_group_size`` the trainer-level default
    statistics group size (overridable per layer).

    ``ep_axis``/``ep_shards`` name the expert-parallel mesh axis on the
    explicit path (``DistributedTrainer`` with
    ``moe_expert_parallel_rules`` and an explicit strategy): expert-dim
    params arrive sliced over that axis and MoE layers combine local
    expert outputs with collectives bound to it. ``None``/1 everywhere
    else (the implicit path shards experts through GSPMD instead)."""

    axis: Optional[str] = None
    n_shards: int = 1
    bn_group_size: Optional[int] = None
    ep_axis: Optional[str] = None
    ep_shards: int = 1


@dataclasses.dataclass(frozen=True)
class LayerContext:
    """Per-call dynamic context threaded through layer application."""

    train: bool = False
    rng: Optional[jax.Array] = None  # dropout/noise key (None in inference)
    mask: Optional[jax.Array] = None  # sequence mask [batch, time] where applicable
    dist: Optional[DistContext] = None  # data-parallel context (trainer only)


@register_config
@dataclasses.dataclass(frozen=True, kw_only=True)
class Layer:
    """Base layer config. Fields set to None inherit the network's global
    defaults (reference: NeuralNetConfiguration.Builder global conf)."""

    name: Optional[str] = None
    activation: Optional[Activation] = None
    weight_init: Optional[WeightInit] = None
    weight_init_distribution: Optional[Distribution] = None
    bias_init: float = 0.0
    dropout: Optional[float] = None  # retain-input semantics? see note below
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    weight_decay: Optional[float] = None
    updater: Optional[Any] = None  # per-layer updater config override
    frozen: bool = False  # transfer-learning freeze (reference: FrozenLayer)

    # True on layers whose input is integer INDICES (embedding lookups).
    # Inputs feeding such layers keep their integer dtype end-to-end: a
    # float cast — especially the bf16 compute cast — corrupts ids > 256
    # (bf16 has 8 mantissa bits). All other inputs are promoted to the
    # model float dtype as the reference does.
    consumes_indices: ClassVar[bool] = False

    # NOTE on dropout: the reference's layer-level ``dropOut(p)`` keeps each
    # input unit with probability p and scales by 1/p (inverted dropout with
    # p = RETAIN probability, applied to the layer INPUT). We preserve that
    # convention: ``dropout=0.8`` keeps 80% of inputs.

    # ---- shape inference ---------------------------------------------------
    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def with_input(self, input_type: InputType) -> "Layer":
        return self

    # ---- parameters --------------------------------------------------------
    def init(self, key: jax.Array, dtype: Any) -> Params:
        return {}

    def init_state(self, dtype: Any) -> State:
        return {}

    def decode_state(self, batch: int, max_len: int, dtype: Any) -> State:
        """Transient per-sequence carry for incremental autoregressive
        decode: a static-shape KV cache + position counter for attention
        layers, the (h, c) recurrent carry for RNN layers, a position
        offset for positional embeddings. Threaded through ``apply`` via
        the ``rnn_state`` channel (never persisted), so one preallocated
        pytree serves an entire generation — shapes depend only on
        ``(batch, max_len)``, never on how far decoding has advanced.
        Layers without decode-time state return {} (stateless layers are
        applied per step as-is)."""
        return {}

    def decode_planes(self) -> Tuple[str, ...]:
        """What the layer declares of its :meth:`decode_state`: the names
        of the leaves that are PLANES, ``[batch, ..., entries, ...]`` arrays
        written in place entry by entry, where a fused batch step's idle row
        writes nothing when the state carries a ``write_mask`` leaf
        (``generate/paged.py`` ``mask_inactive_writes``). Nothing selects
        over a plane or copies one; every other leaf is per-row state and
        takes the row select of ``freeze_rows``. The carry's masking,
        freezing and paging work from this declaration."""
        return ()

    #: True on layers whose planes are K/V caches indexed by absolute
    #: position (``[b, h, max_len, ...]``), which the paged layout can cut
    #: into blocks; a layer that keeps a bounded state of another shape
    #: leaves it False and the paged engine refuses the model.
    pages_decode_planes: ClassVar[bool] = False

    def decode_window(self) -> Optional[int]:
        """The length of the aligned window after which the layer's decode
        state folds its entries into summaries; ``None`` for a layer that
        keeps every entry (or none)."""
        return None

    def decode_ring(self) -> Optional[int]:
        """The entries of a RING that the layer's decode state keeps a row
        (a sliding window's last keys and values, position ``p`` at slot
        ``p mod ring``), which a row at position ``p`` attends ``min(p + 1,
        ring)`` of; ``None`` for a layer that keeps none. Not a
        :meth:`decode_window`: nothing folds, and a prompt is prefilled
        whole."""
        return None

    def decode_counts(self) -> Dict[str, Tuple[str, ...]]:
        """What the layer COUNTS of each call in its :meth:`decode_state`:
        ``{leaf: names of its columns}`` for every per-row leaf ``[batch,
        columns]`` int32 that the call overwrites with counts of what it did
        for the row's tokens (an expert layer: where the tokens' choices
        went). The engine sums such a leaf over a step's active rows and the
        layers that declare it and fetches the sums with the step's tokens;
        ``{}`` for a layer that counts nothing."""
        return {}

    def decode_live_bytes(self, position: int, itemsize: int) -> Dict[str, int]:
        """Bytes of the decode state that a row standing at ``position``
        has made valid, by kind of entry (host arithmetic, for the engine's
        ``dl4j_tpu_decode_state_bytes`` gauge); ``{}`` where the layer does
        not say. The engine hands over all its rows' positions at once, an
        integer array: keep to arithmetic that works on either (a kind that
        does not depend on the position stays one number)."""
        return {}

    def has_params(self) -> bool:
        return False

    def tied_params(self) -> Dict[str, Tuple[int, str]]:
        """Parameters the layer READS from another layer of its network
        and does not own: ``{the name it reads them under: (index of the
        layer that owns them, their name there)}`` (a head that shares the
        embedding's matrix). The network hands them over beside the
        layer's own (``MultiLayerNetwork.layer_params``); they are
        initialised, trained and saved once, where they are owned."""
        return {}

    # ---- forward -----------------------------------------------------------
    def apply(self, params: Params, state: State, x: jax.Array, ctx: LayerContext) -> Tuple[jax.Array, State]:
        raise NotImplementedError

    # ---- mask propagation (reference: feedForwardMaskArray) ----------------
    def feed_forward_mask(self, mask: Optional[jax.Array], input_type: InputType) -> Optional[jax.Array]:
        return mask

    # ---- regularization contribution for the score (reference: calcRegularizationScore)
    def trainable_param_names(self) -> Tuple[str, ...]:
        return tuple()

    def weight_param_names(self) -> Tuple[str, ...]:
        """Params that l1/l2/weight-decay apply to (biases excluded)."""
        return tuple(n for n in self.trainable_param_names() if n not in ("b", "gb", "bb"))


def sub_params(params: Params, prefix: str) -> Params:
    """The parameters a layer holds flat under ``prefix`` for one of its
    parts, under the part's own names."""
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def resolve(value, default):
    return default if value is None else value


def apply_input_dropout(cfg: Layer, x: jax.Array, ctx: LayerContext) -> jax.Array:
    """Inverted dropout on layer input, reference retain-probability semantics."""
    if cfg.dropout is None or not ctx.train or ctx.rng is None:
        return x
    retain = float(cfg.dropout)
    if retain >= 1.0:
        return x
    keep = jax.random.bernoulli(ctx.rng, retain, x.shape)
    return jax.numpy.where(keep, x / retain, 0.0).astype(x.dtype)


def apply_layer(layer, lparams, lstate, x, ctx, *, name: str,
                remat: bool = False):
    """Layer apply under ``jax.named_scope(name)`` (the layer's name in its
    network: metadata on every operation it lowers to, which a profile
    groups device time by), optionally under jax.checkpoint: the backward
    then recomputes this layer's intermediates (attention probs, FFN
    hidden) instead of holding them in HBM — SURVEY §7's remat trade. Homed
    here next to LayerContext so both network classes import it
    cycle-free."""
    with jax.named_scope(name):
        if not remat:
            return layer.apply(lparams, lstate, x, ctx)

        def fn(p, s, xx, key, mask):
            # dist is static config (axis name / group sizes), safe to
            # close over
            c = LayerContext(train=ctx.train, rng=key, mask=mask,
                             dist=ctx.dist)
            return layer.apply(p, s, xx, c)

        return jax.checkpoint(fn)(lparams, lstate, x, ctx.rng, ctx.mask)
