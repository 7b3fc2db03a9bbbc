"""Seeded token-sampling utilities for autoregressive decode.

All functions operate on the LAST axis of a logits array and are pure /
jit-safe. Log-probabilities work everywhere plain logits do: for a
softmax-output model, ``log(p)`` differs from the true logits by a
per-row constant, which temperature scaling, top-k/top-p truncation and
``jax.random.categorical`` are all invariant to — so the decode path can
sample straight from the output layer's probabilities without
re-deriving pre-activation logits.

Determinism contract: every sampler takes an explicit PRNG key (or a
``(seed, step)`` pair in the batched engine form), so a request that
declares a seed replays the identical token stream regardless of which
other sequences happen to share its decode batch — the property that
makes continuous batching debuggable.

Tie semantics (documented, enforced by tests): ``top_k`` keeps every
token tied with the k-th largest logit (the support may exceed k on
ties); ``top_p`` keeps the smallest prefix of the sorted distribution
whose cumulative mass reaches ``p``, including the token that crosses
the threshold, plus any tokens tied with the last kept probability.

Cost contract: :func:`sample_tokens` decides once per BATCH, on the device
and inside the one compiled program, which work its rows' specs ask for
(:func:`sampler_path`), and runs only that:

* ``argmax`` -- every row greedy: one argmax of the logits. No temperature
  divide, no sort, no softmax or cumulative sum, no key and no noise.
* ``sample`` -- some row samples and no sampling row truncates (``k > 0`` or
  ``p < 1``): the temperature scale and one Gumbel draw a row, no sort.
* ``sort`` -- some sampling row truncates: ONE descending sort of the scaled
  logits serves top-k (its k-th value is the threshold) and top-p (masking
  below that threshold keeps the order, so the sorted probabilities and
  their cumulative mass come from the same array).

A greedy row's ``k`` and ``p`` hold nobody on the sort path, and a mixed
batch takes the costliest path any of its sampling rows asks for. The
choice is one ``lax.switch`` outside the ``vmap`` over rows (a ``where`` or
a ``cond`` on a per-row flag under ``vmap`` evaluates both sides), and each
arm hands back the tokens alone, so the arms share their temporaries.
:func:`speculative_accept` reads its distributions through the same single
sort, for every batch.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

_NEG = -1e30  # finite -inf: masked logits stay exp-safe


def _scaled(logits: jax.Array, temp) -> jax.Array:
    return logits / jnp.maximum(jnp.asarray(temp, logits.dtype), 1e-6)


def greedy(logits: jax.Array) -> jax.Array:
    """Argmax over the last axis — the deterministic decode mode."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature(logits: jax.Array, key: jax.Array, temp: float = 1.0) -> jax.Array:
    """Sample from softmax(logits / temp)."""
    return jax.random.categorical(key, _scaled(logits, temp),
                                  axis=-1).astype(jnp.int32)


def _sort_desc(z: jax.Array) -> jax.Array:
    # values alone, so not stable: a stable sort carries an index beside
    # every value, to no end (equal values are equal wherever they land)
    axis = z.ndim - 1
    return jax.lax.rev(jax.lax.sort(z, dimension=axis, is_stable=False),
                       (axis,))


def _keep_top_k(z: jax.Array, sz: jax.Array, k):
    """``z`` and its descending sort ``sz``, both masked below the k-th
    largest value: the masked ``sz`` is still the descending sort of the
    masked ``z``."""
    kk = jnp.clip(jnp.asarray(k, jnp.int32), 1, z.shape[-1])
    thr = jnp.take_along_axis(
        sz, jnp.broadcast_to(kk - 1, z.shape[:-1])[..., None], axis=-1)
    return jnp.where(z >= thr, z, _NEG), jnp.where(sz >= thr, sz, _NEG)


def _keep_top_p(z: jax.Array, sz: jax.Array, p) -> jax.Array:
    """``z`` masked outside its nucleus, given its descending sort ``sz``.
    One shift and one normaliser make ``z``'s probabilities and ``sz``'s by
    the same elementwise steps, so the latter ARE the former sorted, bit
    for bit."""
    shift = sz[..., :1]
    unnorm = jnp.exp(z - shift)
    total = jnp.sum(unnorm, axis=-1, keepdims=True)
    probs = unnorm / total
    sp = jnp.exp(sz - shift) / total
    cs = jnp.cumsum(sp, axis=-1)
    # keep while the mass BEFORE this token is < p (always keeps the top-1,
    # includes the token that crosses the threshold)
    keep = (cs - sp) < jnp.asarray(p, probs.dtype)
    thr = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(probs >= thr, z, _NEG)


def top_k(logits: jax.Array, key: jax.Array, k: int,
          temp: float = 1.0) -> jax.Array:
    """Sample among the k highest-logit tokens (ties at the k-th kept)."""
    z = _scaled(logits, temp)
    return jax.random.categorical(
        key, _keep_top_k(z, _sort_desc(z), k)[0], axis=-1).astype(jnp.int32)


def top_p(logits: jax.Array, key: jax.Array, p: float,
          temp: float = 1.0) -> jax.Array:
    """Nucleus sampling: smallest prefix of the sorted distribution with
    cumulative probability >= p."""
    z = _scaled(logits, temp)
    return jax.random.categorical(
        key, _keep_top_p(z, _sort_desc(z), p), axis=-1).astype(jnp.int32)


# what a batch's specs ask of the sampler, cheapest first (module docstring)
PATHS = ("argmax", "sample", "sort")


def sampler_path(greedy_mask, k, p):
    """Index into :data:`PATHS` of the work a batch's specs ask for, from
    its sampling rows alone. Takes numpy or jax arrays (bool, int, float,
    one entry a row): the device's branch and the engine's host-side count
    of it are this one expression."""
    sampling = ~greedy_mask
    truncating = sampling & ((k > 0) | (p < 1.0))
    return (sampling.any().astype("int32")
            + truncating.any().astype("int32"))


def _scale_only(logits, temp, k, p):
    return _scaled(logits.astype(jnp.float32), temp)


def _warp(logits, temp, k, p):
    """Shared logits warping: temperature scale, then top-k, then top-p
    over the surviving support (``k == 0`` and ``p >= 1`` disable), both
    read from ONE descending sort of the scaled logits."""
    z = _scale_only(logits, temp, k, p)
    sz = _sort_desc(z)
    zk, szk = _keep_top_k(z, sz, jnp.maximum(k, 1))
    z, sz = jnp.where(k > 0, zk, z), jnp.where(k > 0, szk, sz)
    return jnp.where(p < 1.0, _keep_top_p(z, sz, jnp.clip(p, 1e-6, 1.0)), z)


def _sample_one(warp, logits, seed, step, greedy_flag, temp, k, p):
    """One row of the batched engine sampler, its logits warped by ``warp``
    (:func:`_warp`, or :func:`_scale_only` for a batch in which no sampling
    row truncates). ``k == 0`` disables top-k, ``p >= 1`` disables top-p;
    both compose (top-k first, then top-p over the surviving support).
    Keyed by fold_in(PRNGKey(seed), step) so the stream depends only on
    (seed, position), never on batch composition."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed.astype(jnp.uint32)), step)
    sampled = jax.random.categorical(key, warp(logits, temp, k, p))
    return jnp.where(greedy_flag, jnp.argmax(logits), sampled).astype(jnp.int32)


@jax.jit  # an eager switch would trace, and compile, its arms at every call
def sample_tokens(
    logits: jax.Array,       # [B, V]
    seeds: jax.Array,        # [B] uint32 per-request seed
    steps: jax.Array,        # [B] int32 per-request decode step index
    greedy_mask: jax.Array,  # [B] bool — True rows take argmax
    temp: jax.Array,         # [B] float temperature
    k: jax.Array,            # [B] int32 top-k (0 = off)
    p: jax.Array,            # [B] float top-p (>= 1 = off)
) -> jax.Array:
    """Batched per-row sampler for the continuous-batching decode engine:
    every row carries its own sampling spec, so requests with different
    (greedy/temperature/top-k/top-p, seed) settings share one compiled
    decode step, whose cost follows what the batch's specs ask for (the
    module's cost contract)."""
    # float32 BEFORE the branch: what an arm is handed is materialised as
    # written, and bfloat16 log-probabilities tie at the top (on the chip
    # XLA's excess precision keeps the producer's float32 up to here)
    logits = logits.astype(jnp.float32)
    greedy_mask = greedy_mask.astype(bool)
    k, p = k.astype(jnp.int32), p.astype(jnp.float32)

    def rows(warp):
        return lambda: jax.vmap(partial(_sample_one, warp))(
            logits, seeds.astype(jnp.uint32), steps.astype(jnp.int32),
            greedy_mask, temp.astype(jnp.float32), k, p)

    # one arm a path, in PATHS' order; each hands back [B] tokens only
    return jax.lax.switch(
        sampler_path(greedy_mask, k, p),
        (lambda: greedy(logits), rows(_scale_only), rows(_warp)))


# ---------------------------------------------------------------------------
# speculative decoding: exact accept-or-resample
# ---------------------------------------------------------------------------

# fold_in tags keeping the accept-test and residual-resample streams
# independent of each other AND of the draft's proposal draw at the same
# (seed, step) — the independence the exactness proof requires
_ACCEPT_TAG = 0x5A
_RESID_TAG = 0x5B


def _warped_probs(logits, greedy_flag, temp, k, p):
    """The per-position sampling distribution a request's spec implies:
    softmax of the warped logits, or a one-hot argmax for greedy rows
    (greedy == the temperature->0 limit, so the ratio test degenerates to
    exact token equality and speculative greedy streams stay
    token-identical to plain greedy)."""
    probs = jax.nn.softmax(_warp(logits, temp, k, p), axis=-1)
    hot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                         dtype=probs.dtype)
    return jnp.where(greedy_flag, hot, probs)


def _residual_probs(p_t, p_d):
    """Normalized max(0, p_t - p_d): the exact residual distribution a
    rejection resamples from. Falls back to p_t when the residual has no
    mass (p_d == p_t — a rejection there has probability zero, the
    fallback only guards the division)."""
    r = jnp.maximum(p_t - p_d, 0.0)
    mass = jnp.sum(r, axis=-1, keepdims=True)
    return jnp.where(mass > 1e-12, r / jnp.maximum(mass, 1e-12), p_t)


def _speculative_row(draft_tokens, draft_logits, target_logits, seed, step,
                     spec_k, greedy_flag, temp, tk, tp):
    """One row of :func:`speculative_accept` — see there for shapes."""
    kmax = draft_tokens.shape[0]
    steps = step + jnp.arange(kmax + 1, dtype=jnp.int32)
    base = jax.random.PRNGKey(seed.astype(jnp.uint32))
    keys = jax.vmap(lambda s: jax.random.fold_in(base, s))(steps)

    p_t = _warped_probs(target_logits, greedy_flag, temp, tk, tp)  # [K+1,V]
    p_d = _warped_probs(draft_logits, greedy_flag, temp, tk, tp)   # [K, V]

    # accept test at each proposed position: u < p_t(x)/p_d(x)
    pt_x = jnp.take_along_axis(p_t[:kmax], draft_tokens[:, None],
                               axis=-1)[:, 0]
    pd_x = jnp.take_along_axis(p_d, draft_tokens[:, None], axis=-1)[:, 0]
    u = jax.vmap(lambda kk: jax.random.uniform(
        jax.random.fold_in(kk, _ACCEPT_TAG)))(keys[:kmax])
    ratio = pt_x / jnp.maximum(pd_x, 1e-30)
    in_window = jnp.arange(kmax, dtype=jnp.int32) < spec_k
    accept = (u < ratio) & in_window
    # number of LEADING accepts (a rejection stops the window)
    n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))

    # at every position, what a rejection there would emit (residual
    # dist), and what full acceptance emits (plain sample from the
    # target — position kmax's untagged key is exactly the key plain
    # decode would use at that step, so a spec_k == 0 row reproduces the
    # non-speculative stream token-for-token even when sampling)
    resid = _residual_probs(p_t[:kmax], p_d)
    resample = jax.vmap(lambda kk, pr: jax.random.categorical(
        jax.random.fold_in(kk, _RESID_TAG),
        jnp.log(jnp.maximum(pr, 1e-30))))(keys[:kmax], resid)
    plain = jax.vmap(lambda kk, lg: jax.random.categorical(
        kk, jnp.log(jnp.maximum(lg, 1e-30))))(keys, p_t)
    greedy_fix = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)
    idx = jnp.arange(kmax + 1, dtype=jnp.int32)
    # inside the window a stop is a REJECTION -> residual resample; at
    # position spec_k the window is merely exhausted -> plain sample from
    # the full target dist (the "bonus" token; for spec_k == 0 this IS
    # plain decode, same untagged (seed, step) key, token-identical)
    sampled_fix = jnp.where(idx < spec_k,
                            jnp.pad(resample, (0, 1)), plain)
    correction = jnp.where(greedy_flag, greedy_fix,
                           sampled_fix).astype(jnp.int32)
    out = jnp.where(idx < n_acc, jnp.pad(draft_tokens, (0, 1)),
                    correction[jnp.minimum(n_acc, kmax)])
    return out.astype(jnp.int32), n_acc.astype(jnp.int32), \
        (n_acc + 1).astype(jnp.int32)


def speculative_accept(
    draft_tokens: jax.Array,   # [B, K] proposed tokens
    draft_logits: jax.Array,   # [B, K, V] draft dist at each proposal
    target_logits: jax.Array,  # [B, K+1, V] target dist at each position
    seeds: jax.Array,          # [B] uint32 per-request seed
    steps: jax.Array,          # [B] int32 decode step of the FIRST position
    spec_ks: jax.Array,        # [B] int32 per-row window (0 = plain decode)
    greedy_mask: jax.Array,    # [B] bool
    temp: jax.Array,           # [B] float temperature
    k: jax.Array,              # [B] int32 top-k (0 = off)
    p: jax.Array,              # [B] float top-p (>= 1 = off)
):
    """Exact acceptance sampling for draft-model speculative decoding.

    Per row: walk the ``spec_ks`` proposed tokens left to right, accepting
    token ``x_i`` with probability ``min(1, p_target(x_i)/p_draft(x_i))``;
    the first rejection emits a resample from the normalized residual
    ``max(0, p_target - p_draft)`` and closes the window; full acceptance
    emits a bonus token sampled from the target's ``K+1``-th distribution.
    The emitted-token marginal at every position is exactly the (warped)
    target distribution — speculation changes only the cost per token,
    never the output law (``tests/test_speculative.py`` checks the closed
    form). Greedy rows degenerate to argmax equality, so greedy streams
    are token-identical to plain decode.

    Randomness at output position ``steps + i`` is keyed by
    ``fold_in(PRNGKey(seed), steps + i)`` (+ per-use tags), so a row's
    stream depends only on ``(seed, position)`` — never on batch
    composition. Returns ``(tokens [B, K+1], n_accepted [B],
    n_emitted [B])`` with ``n_emitted == n_accepted + 1``; entries past
    ``n_emitted`` are padding."""
    return jax.vmap(_speculative_row)(
        draft_tokens.astype(jnp.int32), draft_logits, target_logits,
        seeds.astype(jnp.uint32), steps.astype(jnp.int32),
        spec_ks.astype(jnp.int32), greedy_mask,
        temp.astype(jnp.float32), k.astype(jnp.int32),
        p.astype(jnp.float32))


def make_sampler(*, greedy_mode: Optional[bool] = None,
                 temp: float = 1.0, k: int = 0, p: float = 1.0):
    """Single-spec convenience: returns ``fn(logits [B, V], seeds, steps)``
    applying one sampling configuration to every row."""
    use_greedy = bool(greedy_mode) if greedy_mode is not None else (
        k == 0 and p >= 1.0 and temp == 0.0)

    def fn(logits, seeds, steps):
        b = logits.shape[0]
        return sample_tokens(
            logits, seeds, steps,
            jnp.full((b,), use_greedy, bool),
            jnp.full((b,), temp, jnp.float32),
            jnp.full((b,), k, jnp.int32),
            jnp.full((b,), p, jnp.float32))

    return fn
