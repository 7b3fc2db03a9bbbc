"""KV-cached autoregressive generation (ISSUE 9).

The load-bearing contract is prefill/decode EQUIVALENCE: incremental
KV-cached decode must be token-for-token identical (greedy) to a full
re-forward at every position, for the attention and LSTM paths, across
prompt-bucket boundaries — plus seeded-sampling semantics, the flash
decode kernel vs the reference impl, and the continuous-batching
DecodeEngine (admission, deadlines, slot reuse, metrics, spans).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.generate import (
    GenerationSession,
    bucket_length,
    sample_tokens,
)
from deeplearning4j_tpu.generate import sampling as S
from deeplearning4j_tpu.model.zoo import TextGenerationLSTM, TransformerLM
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.ops import (
    decode_attention_reference,
    decode_fetched_entries,
    flash_decode_attention,
)
from deeplearning4j_tpu.parallel import DecodeEngine


def _one_hot(toks, vocab):
    oh = np.zeros((1, vocab, len(toks)), np.float32)
    for i, t in enumerate(toks):
        oh[0, t, i] = 1.0
    return oh


def _full_greedy(model, prompt, n, vocab, max_len, one_hot=False):
    """The re-forward oracle: rebuild the whole sequence every step and
    argmax the last position's distribution."""
    toks = list(prompt)
    out_toks = []
    for _ in range(n):
        if len(toks) >= max_len:
            break
        x = (_one_hot(toks, vocab) if one_hot
             else jnp.asarray([toks], jnp.int32))
        out = model.output(x)
        nxt = int(jnp.argmax(out[0, :, -1]))
        out_toks.append(nxt)
        toks.append(nxt)
    return out_toks


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

_NEG = -1e30


def _ref_top_k(z, k):
    kk = jnp.clip(jnp.asarray(k, jnp.int32), 1, z.shape[-1])
    thr = jnp.sort(z)[::-1][kk - 1]
    return jnp.where(z >= thr, z, _NEG)


def _ref_top_p(z, p):
    probs = jax.nn.softmax(z)
    sp = jnp.sort(probs)[::-1]
    cs = jnp.cumsum(sp)
    thr = jnp.min(jnp.where((cs - sp) < jnp.asarray(p, probs.dtype), sp,
                            jnp.inf))
    return jnp.where(probs >= thr, z, _NEG)


def _ref_sample_one(logits, seed, step, greedy_flag, temp, k, p):
    """The plain per-row sampler ``sample_tokens`` is held to: every row
    pays for everything (two sorts, a softmax, a cumulative sum, a draw)
    and a ``where`` picks what its spec asked for."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed.astype(jnp.uint32)), step)
    z = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
    z = jnp.where(k > 0, _ref_top_k(z, jnp.maximum(k, 1)), z)
    z = jnp.where(p < 1.0, _ref_top_p(z, jnp.clip(p, 1e-6, 1.0)), z)
    sampled = jax.random.categorical(key, z)
    return jnp.where(greedy_flag, jnp.argmax(logits), sampled).astype(jnp.int32)


def _spec_vectors(greedy, temp, k, p):
    return (jnp.asarray(greedy, bool), jnp.asarray(temp, jnp.float32),
            jnp.asarray(k, jnp.int32), jnp.asarray(p, jnp.float32))


_V = 48
# logits with ties: row 0's k-th largest value (k = 4) stands three times
_TIED = np.random.RandomState(11).randn(4, _V).astype(np.float32)
_TIED[0, :6] = [5.0, 4.0, 3.0, 2.0, 2.0, 2.0]
_TIED[0, 6:] = np.minimum(_TIED[0, 6:], 1.0)
# one token carries 0.6 of the mass and three 0.1 each: p = 0.7 is crossed
# by the second, whose probability the two after it tie
_CROSS = np.full((4, _V), -30.0, np.float32)
_CROSS[:, :4] = np.log([0.6, 0.1, 0.1, 0.1])
_CROSS[:, 4:] += np.random.RandomState(12).randn(4, _V - 4)

# id -> (logits or None for random, greedy, temp, k, p), a row an entry
_SPEC_GRID = {
    "all-greedy": (None, [True] * 4, [1.0] * 4, [0] * 4, [1.0] * 4),
    "greedy-and-sampling-mixed": (
        None, [True, False, True, False], [1.0, 0.8, 1.0, 1.2],
        [0, 5, 0, 0], [1.0, 1.0, 1.0, 0.9]),
    "temperature-only": (None, [False] * 4, [0.5, 0.8, 1.0, 1.7],
                         [0] * 4, [1.0] * 4),
    "top-k-only": (None, [False] * 4, [1.0] * 4, [1, 3, 5, 40], [1.0] * 4),
    "top-p-only": (None, [False] * 4, [1.0] * 4, [0] * 4,
                   [0.1, 0.5, 0.9, 0.99]),
    "top-k-and-top-p": (None, [False] * 4, [0.7, 1.0, 1.3, 1.0],
                        [3, 10, 5, 40], [0.9, 0.5, 0.99, 0.3]),
    "ties-at-the-kth-logit": (_TIED, [False] * 4, [1.0] * 4, [4, 4, 2, 6],
                              [1.0, 0.9, 1.0, 1.0]),
    "the-token-that-crosses-p": (_CROSS, [False] * 4, [1.0] * 4,
                                 [0, 0, 3, 0], [0.7, 0.6, 0.7, 0.65]),
    "k-at-least-vocab": (None, [False] * 4, [1.0] * 4,
                         [_V, _V + 1, 10 * _V, _V], [1.0, 1.0, 0.8, 1.0]),
    "p-equal-one": (None, [False] * 4, [0.9] * 4, [0, 0, 5, 0], [1.0] * 4),
    "greedy-row-carrying-k-and-p": (
        None, [True, True, True, True], [0.8] * 4, [5, 0, 3, 0],
        [0.9, 0.5, 1.0, 1.0]),
}


def _eqn_subjaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _primitives(jaxpr, enter_cond=True):
    """Names of a jaxpr's primitives, sub-jaxprs included; with
    ``enter_cond=False`` a ``cond``'s branches are left out."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name == "cond" and not enter_cond:
            continue
        for sub in _eqn_subjaxprs(eqn):
            yield from _primitives(sub, enter_cond)


def _is_costly(name):
    return (name in ("sort", "cumsum", "exp", "div")
            or "random" in name or "threefry" in name)


@pytest.fixture(scope="module")
def sampler_jaxpr():
    """The jaxpr that ``sample_tokens`` jits (its one equation's own)."""
    b = 4
    (call,) = jax.make_jaxpr(sample_tokens)(
        jnp.zeros((b, _V), jnp.float32), jnp.zeros((b,), jnp.uint32),
        jnp.zeros((b,), jnp.int32),
        *_spec_vectors([True] * b, [1.0] * b, [0] * b, [1.0] * b)).eqns
    return call.params["jaxpr"].jaxpr


class TestSampling:
    def test_greedy_is_argmax(self):
        logits = jnp.asarray([[0.1, 3.0, -1.0], [5.0, 0.0, 4.9]])
        assert S.greedy(logits).tolist() == [1, 0]

    def test_temperature_seeded_deterministic(self):
        key = jax.random.PRNGKey(7)
        logits = jnp.asarray(np.random.RandomState(0).randn(4, 16), jnp.float32)
        a = S.temperature(logits, key, 0.8)
        b = S.temperature(logits, key, 0.8)
        assert a.tolist() == b.tolist()
        c = S.temperature(logits, jax.random.PRNGKey(8), 0.8)
        assert a.tolist() != c.tolist() or True  # different key may differ

    def test_top_k_restricts_support(self):
        logits = jnp.asarray(np.random.RandomState(1).randn(64), jnp.float32)
        top3 = set(np.argsort(np.asarray(logits))[-3:].tolist())
        draws = {int(S.top_k(logits, jax.random.PRNGKey(i), 3))
                 for i in range(50)}
        assert draws <= top3

    def test_top_p_restricts_support(self):
        # one dominant token: p=0.5 must always return it
        logits = jnp.asarray([10.0, 0.0, 0.0, 0.0], jnp.float32)
        draws = {int(S.top_p(logits, jax.random.PRNGKey(i), 0.5))
                 for i in range(20)}
        assert draws == {0}

    def test_temperature_equivalence_on_log_probs(self):
        # sampling from log(softmax(z))/T must equal sampling from z/T —
        # the invariance the decode path relies on for softmax outputs
        key = jax.random.PRNGKey(3)
        z = jnp.asarray(np.random.RandomState(2).randn(8, 32), jnp.float32)
        lp = jnp.log(jax.nn.softmax(z, axis=-1))
        assert (S.temperature(z, key, 0.7).tolist()
                == S.temperature(lp, key, 0.7).tolist())

    def test_batched_sampler_per_row_specs(self):
        rng = np.random.RandomState(3)
        logits = jnp.asarray(rng.randn(3, 32), jnp.float32)
        seeds = jnp.asarray([1, 2, 3], jnp.uint32)
        steps = jnp.zeros((3,), jnp.int32)
        toks = sample_tokens(
            logits, seeds, steps,
            jnp.asarray([True, False, False]),
            jnp.asarray([1.0, 0.9, 0.9], jnp.float32),
            jnp.asarray([0, 5, 0], jnp.int32),
            jnp.asarray([1.0, 1.0, 0.9], jnp.float32))
        # row 0 greedy == argmax
        assert int(toks[0]) == int(jnp.argmax(logits[0]))
        # row 1 top-k: inside the top-5 set
        top5 = set(np.argsort(np.asarray(logits[1]))[-5:].tolist())
        assert int(toks[1]) in top5

    def test_batched_sampler_seed_independent_of_batch(self):
        # the (seed, step) keying makes a row's draw independent of which
        # other rows share the batch — continuous batching determinism
        rng = np.random.RandomState(4)
        row = jnp.asarray(rng.randn(1, 32), jnp.float32)
        other = jnp.asarray(rng.randn(1, 32), jnp.float32)
        args = (jnp.asarray([9], jnp.uint32), jnp.asarray([2], jnp.int32),
                jnp.asarray([False]), jnp.asarray([0.8], jnp.float32),
                jnp.asarray([0], jnp.int32), jnp.asarray([1.0], jnp.float32))
        solo = sample_tokens(row, *args)
        both = sample_tokens(
            jnp.concatenate([row, other]),
            jnp.asarray([9, 1], jnp.uint32), jnp.asarray([2, 0], jnp.int32),
            jnp.asarray([False, False]), jnp.asarray([0.8, 1.0], jnp.float32),
            jnp.asarray([0, 0], jnp.int32), jnp.asarray([1.0, 1.0], jnp.float32))
        assert int(solo[0]) == int(both[0])

    @pytest.mark.parametrize("neighbours", [
        "alone", "greedy", "sampling", "truncating"])
    @pytest.mark.parametrize("spec", ["temperature", "top-k-and-top-p"])
    def test_seeded_row_draws_alike_on_every_branch(self, spec, neighbours):
        """The batch's branch follows the neighbours' specs; a seeded row's
        token follows its own (seed, step) and spec alone."""
        rng = np.random.RandomState(5)
        logits = jnp.asarray(rng.randn(4, _V), jnp.float32)
        mine = {"temperature": (False, 0.8, 0, 1.0),
                "top-k-and-top-p": (False, 0.8, 6, 0.9)}[spec]
        theirs = {"greedy": (True, 1.0, 0, 1.0),
                  "sampling": (False, 1.1, 0, 1.0),
                  "truncating": (False, 1.1, 3, 0.8)}
        n = 1 if neighbours == "alone" else 4
        rows = [mine] + [theirs.get(neighbours)] * (n - 1)
        want = _ref_sample_one(logits[0], jnp.uint32(9), jnp.int32(2),
                               *_spec_vectors(*mine))
        got = sample_tokens(
            logits[:n], jnp.asarray([9, 1, 2, 3][:n], jnp.uint32),
            jnp.asarray([2, 0, 7, 1][:n], jnp.int32),
            *_spec_vectors(*zip(*rows)))
        assert int(got[0]) == int(want)

    @pytest.mark.parametrize("case", list(_SPEC_GRID))
    def test_batched_sampler_equals_the_plain_per_row_reference(self, case):
        """Token for token, over seeds and steps: a greedy row's argmax, a
        sampling row's key, warped support and draw are the reference's."""
        logits, greedy, temp, k, p = _SPEC_GRID[case]
        spec = _spec_vectors(greedy, temp, k, p)
        ref = jax.jit(jax.vmap(_ref_sample_one))
        run = jax.jit(sample_tokens)
        rng = np.random.RandomState(6)
        for trial in range(12):
            z = jnp.asarray(rng.randn(4, _V) * 2.0 if logits is None
                            else logits, jnp.float32)
            seeds = jnp.asarray(rng.randint(0, 2**31, 4), jnp.uint32)
            steps = jnp.asarray(rng.randint(0, 500, 4), jnp.int32)
            assert (run(z, seeds, steps, *spec).tolist()
                    == ref(z, seeds, steps, *spec).tolist()), trial

    @pytest.mark.parametrize("case", ["top-k-and-top-p",
                                      "ties-at-the-kth-logit",
                                      "the-token-that-crosses-p"])
    def test_single_sort_support_is_the_two_sort_support(self, case):
        """The warped logits themselves, not only a draw from them: what
        one sort keeps is what top-k's sort and then top-p's kept."""
        logits, _, temp, k, p = _SPEC_GRID[case]
        z = jnp.asarray(np.random.RandomState(7).randn(4, _V) * 2.0
                        if logits is None else logits, jnp.float32)
        for i in range(4):
            t, kk, pp = (jnp.float32(temp[i]), jnp.int32(k[i]),
                         jnp.float32(p[i]))
            want = z[i] / t
            want = jnp.where(kk > 0, _ref_top_k(want, jnp.maximum(kk, 1)),
                             want)
            want = jnp.where(pp < 1.0, _ref_top_p(want, pp), want)
            got = S._warp(z[i], t, kk, pp)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        if case == "ties-at-the-kth-logit":  # k = 4 keeps all three ties
            kept = np.asarray(S._warp(z[0], 1.0, jnp.int32(4),
                                      jnp.float32(1.0))) > _NEG
            assert kept.sum() == 6
        if case == "the-token-that-crosses-p":  # 0.6, the crosser, its ties
            kept = np.asarray(S._warp(z[0], 1.0, jnp.int32(0),
                                      jnp.float32(0.7))) > _NEG
            assert np.nonzero(kept)[0].tolist() == [0, 1, 2, 3]

    def test_nothing_costly_stands_outside_a_branch(self, sampler_jaxpr):
        outside = list(_primitives(sampler_jaxpr, enter_cond=False))
        assert not [n for n in outside if _is_costly(n)], outside
        assert outside.count("cond") == 1

    def test_the_whole_sampler_holds_one_sort(self, sampler_jaxpr):
        names = list(_primitives(sampler_jaxpr))
        assert names.count("sort") == 1 and names.count("cumsum") == 1

    @pytest.mark.parametrize("path", S.PATHS)
    def test_each_arm_runs_what_its_path_asks_for(self, sampler_jaxpr, path):
        (switch,) = [e for e in sampler_jaxpr.eqns
                     if e.primitive.name == "cond"]
        assert len(switch.params["branches"]) == len(S.PATHS)
        arm = switch.params["branches"][S.PATHS.index(path)].jaxpr
        names = set(_primitives(arm))
        draws = any("random" in n for n in names)
        if path == "argmax":
            assert names <= {"argmax", "convert_element_type"}, names
        elif path == "sample":
            assert draws and not names & {"sort", "cumsum", "exp"}, names
        else:
            assert draws and {"sort", "cumsum"} <= names, names

    @pytest.mark.parametrize("case", ["all-greedy",
                                      "greedy-and-sampling-mixed"])
    def test_bfloat16_logits_reach_the_arms_as_float32(self, case):
        """What a branch is handed is materialised as written: bfloat16
        log-probabilities tie at the top, and on the chip the step before
        ISSUE 35 never rounded them (XLA kept the producer's float32 up to
        its fused argmax). The cast stands before the branch, and changes
        no token where the rounding is real."""
        _, greedy, temp, k, p = _SPEC_GRID[case]
        z = jnp.asarray(np.random.RandomState(8).randn(4, _V) * 2.0,
                        jnp.bfloat16)
        args = (z, jnp.arange(4, dtype=jnp.uint32),
                jnp.arange(4, dtype=jnp.int32),
                *_spec_vectors(greedy, temp, k, p))
        (call,) = jax.make_jaxpr(sample_tokens)(*args).eqns
        (switch,) = [e for e in call.params["jaxpr"].jaxpr.eqns
                     if e.primitive.name == "cond"]
        wide = [v.aval.dtype for v in switch.invars if v.aval.shape == (4, _V)]
        assert wide and all(d == jnp.float32 for d in wide), wide
        assert (sample_tokens(*args).tolist()
                == jax.vmap(_ref_sample_one)(*args).tolist())

    @pytest.mark.parametrize("greedy,k,p,path", [
        ([True, True], [0, 0], [1.0, 1.0], "argmax"),
        ([True, True], [5, 0], [0.5, 1.0], "argmax"),
        ([True, False], [5, 0], [0.5, 1.0], "sample"),
        ([False, False], [0, 0], [1.0, 1.5], "sample"),
        ([True, False], [0, 2], [1.0, 1.0], "sort"),
        ([False, True], [0, 0], [0.99, 1.0], "sort"),
    ])
    def test_sampler_path_on_host_and_device_arrays(self, greedy, k, p, path):
        host = (np.asarray(greedy), np.asarray(k, np.int32),
                np.asarray(p, np.float32))
        assert S.PATHS[int(S.sampler_path(*host))] == path
        assert S.PATHS[int(jax.jit(S.sampler_path)(
            *map(jnp.asarray, host)))] == path


# ---------------------------------------------------------------------------
# decode attention kernel
# ---------------------------------------------------------------------------


# h, d, L, block_k, the rows' positions (-1: an inactive row)
_DECODE_CASES = [
    pytest.param(4, 16, 40, 8, [0, 5, 39], id="spread"),
    pytest.param(4, 16, 40, 8, [1, 1, 1], id="all-equal"),
    pytest.param(4, 16, 40, 8, [38, 0, 20], id="all-different"),
    pytest.param(4, 16, 40, 8, [-1, 0, -1], id="lengths-0-and-1"),
    pytest.param(4, 16, 40, 8, [6, 7, 8, 15, 16], id="round-a-block-edge"),
    pytest.param(4, 16, 40, 8, [39, -1, 39, 31], id="the-whole-cache"),
    pytest.param(3, 16, 40, 16, [20] * 5, id="five-equal-rows-padded-cache"),
    pytest.param(2, 8, 600, 256, [599, 255, 256, 300, -1, 511, 512],
                 id="600-that-no-block-divides"),
    pytest.param(12, 64, 256, 128, [0, 127, 128, 255, 100, -1],
                 id="12-heads-of-64"),
    pytest.param(2, 8, 48, 256, [0, 47, -1, 13], id="one-block"),
    # a K and a V block of all six heads pass 4 MiB: two head groups
    pytest.param(6, 64, 4096, 2048, [5, 2047, 2048, -1, 4095],
                 id="two-head-groups"),
]


class TestDecodeAttention:
    @staticmethod
    def _case(h, d, L, pos, seed=0):
        rng = np.random.RandomState(seed)
        b = len(pos)
        q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
        k = rng.randn(b, h, L, d).astype(np.float32)
        v = rng.randn(b, h, L, d).astype(np.float32)
        return q, k, v, jnp.asarray(pos, jnp.int32)

    @pytest.mark.parametrize("h,d,L,block_k,pos", _DECODE_CASES)
    def test_flash_matches_reference(self, h, d, L, block_k, pos):
        q, k, v, sp = self._case(h, d, L, pos)
        ref = decode_attention_reference(q, jnp.asarray(k), jnp.asarray(v), sp)
        fl = flash_decode_attention(q, jnp.asarray(k), jnp.asarray(v), sp,
                                    block_k=block_k)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        # an inactive row attends nothing and outputs exactly 0
        idle = np.asarray(pos) < 0
        assert not np.asarray(fl)[idle].any()

    @pytest.mark.parametrize("h,d,L,block_k,pos", _DECODE_CASES[3:9])
    def test_flash_reads_nothing_past_the_frontier(self, h, d, L, block_k,
                                                   pos):
        """The cache past each row's position holds NaN: the kernel's
        result is finite and equal to the one over a clean cache."""
        q, k, v, sp = self._case(h, d, L, pos, seed=3)
        clean = flash_decode_attention(q, jnp.asarray(k), jnp.asarray(v), sp,
                                       block_k=block_k)
        for row, p in enumerate(pos):
            k[row, :, p + 1:] = np.nan
            v[row, :, p + 1:] = np.nan
        got = np.asarray(flash_decode_attention(
            q, jnp.asarray(k), jnp.asarray(v), sp, block_k=block_k))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, np.asarray(clean))

    def test_fetched_entries_against_a_hand_count(self):
        """Whole blocks up to the one that holds a row's last entry; one
        block for a row with none; a block no longer than the cache."""
        lengths = np.array([0, 1, 255, 256, 257, 512, 513, 1024])
        got = decode_fetched_entries(lengths, 1024, 256)
        assert isinstance(got, np.ndarray)  # host arithmetic stays on the host
        assert got.tolist() == [256, 256, 256, 256, 512, 512, 768, 1024]
        assert decode_fetched_entries(lengths, 1024, 128).tolist() == [
            128, 128, 256, 256, 384, 512, 640, 1024]
        assert decode_fetched_entries(221, 1024) == 256  # the default block
        assert decode_fetched_entries(7, 40, 256) == 40
        assert decode_fetched_entries(0, 40, 8) == 8
        assert decode_fetched_entries(601, 1024, 512) == 1024
        # traced, as the kernel's index map calls it
        traced = jax.jit(lambda n: decode_fetched_entries(n, 1024, 256))(
            jnp.asarray(lengths, jnp.int32))
        assert np.asarray(traced).tolist() == got.tolist()

    def test_reference_masks_future(self):
        # entries past the frontier must not influence the output
        rng = np.random.RandomState(1)
        b, h, L, d = 1, 2, 16, 8
        q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
        k = np.asarray(rng.randn(b, h, L, d), np.float32)
        v = np.asarray(rng.randn(b, h, L, d), np.float32)
        pos = jnp.asarray([4], jnp.int32)
        base = decode_attention_reference(q, jnp.asarray(k), jnp.asarray(v), pos)
        k2, v2 = k.copy(), v.copy()
        k2[:, :, 5:] = 99.0
        v2[:, :, 5:] = -99.0
        pert = decode_attention_reference(q, jnp.asarray(k2), jnp.asarray(v2), pos)
        np.testing.assert_allclose(np.asarray(pert), np.asarray(base),
                                   atol=1e-6)

    def test_chunk_queries_causal(self):
        # tq > 1: query i attends [0, start+i] — matches per-step calls
        rng = np.random.RandomState(2)
        b, h, L, d, tq = 2, 2, 12, 8, 3
        q = jnp.asarray(rng.randn(b, h, tq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
        start = jnp.asarray([0, 4], jnp.int32)
        chunk = decode_attention_reference(q, k, v, start)
        for i in range(tq):
            single = decode_attention_reference(q[:, :, i:i + 1], k, v,
                                                start + i)
            np.testing.assert_allclose(np.asarray(chunk[:, :, i:i + 1]),
                                       np.asarray(single), atol=1e-5)


# ---------------------------------------------------------------------------
# prefill/decode equivalence (the acceptance contract)
# ---------------------------------------------------------------------------


class TestEquivalence:
    MAX_LEN = 16

    @pytest.fixture(scope="class")
    def lm(self):
        return TransformerLM(vocab_size=29, hidden=32, n_layers=2,
                             n_heads=4, max_len=self.MAX_LEN).init()

    def test_attention_path_across_buckets(self, lm):
        """Greedy incremental decode == full re-forward at every position,
        for prompt lengths straddling bucket boundaries (3 -> bucket 4,
        5 -> bucket 8, 8 -> bucket 8) and generations crossing them."""
        sess = GenerationSession(lm, max_len=self.MAX_LEN)
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 3, 1, 4, 1, 5, 9, 2]]
        n = self.MAX_LEN  # run to the cache limit -> crosses buckets
        inc = sess.generate(prompts, n, greedy=True)
        for p, got in zip(prompts, inc):
            ref = _full_greedy(lm, p, n, 29, self.MAX_LEN)
            assert got == ref, f"prompt {p}: {got} != {ref}"

    def test_lstm_path(self):
        tg = TextGenerationLSTM(vocab_size=13, hidden=16, layers=2)
        model = tg.init()
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8]]
        inc = TextGenerationLSTM.generate(model, prompts, 8, max_len=32,
                                          greedy=True)
        for p, got in zip(prompts, inc):
            ref = _full_greedy(model, p, 8, 13, 32, one_hot=True)
            assert got == ref

    def test_recurrent_attention_path(self):
        from deeplearning4j_tpu.nn import (
            Activation, InputType, LossFunction, NeuralNetConfiguration,
            WeightInit)
        from deeplearning4j_tpu.nn.layers import (
            RecurrentAttentionLayer, RnnOutputLayer)
        from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork

        conf = (NeuralNetConfiguration.builder().seed(5)
                .weight_init(WeightInit.XAVIER).list()
                .layer(RecurrentAttentionLayer(n_in=11, n_out=16, causal=True))
                .layer(RnnOutputLayer(n_out=11, loss=LossFunction.MCXENT,
                                      activation=Activation.SOFTMAX))
                .set_input_type(InputType.recurrent(11)).build())
        model = MultiLayerNetwork(conf).init()
        sess = GenerationSession(model, max_len=16)
        prompts = [[1, 2, 3], [4, 5]]
        inc = sess.generate(prompts, 6, greedy=True)
        for p, got in zip(prompts, inc):
            ref = _full_greedy(model, p, 6, 11, 16, one_hot=True)
            assert got == ref

    def test_seeded_sampling_reproducible(self, lm):
        sess = GenerationSession(lm, max_len=self.MAX_LEN)
        a = sess.generate([[1, 2, 3]], 6, greedy=False, temperature=0.9,
                          top_k=8, seed=42)
        b = sess.generate([[1, 2, 3]], 6, greedy=False, temperature=0.9,
                          top_k=8, seed=42)
        assert a == b

    def test_bidirectional_model_rejected(self):
        from deeplearning4j_tpu.nn import (
            Activation, InputType, LossFunction, NeuralNetConfiguration,
            WeightInit)
        from deeplearning4j_tpu.nn.layers import (
            RnnOutputLayer, SelfAttentionLayer)
        from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork

        conf = (NeuralNetConfiguration.builder().seed(5)
                .weight_init(WeightInit.XAVIER).list()
                .layer(SelfAttentionLayer(n_in=8, n_out=8, n_heads=2))
                .layer(RnnOutputLayer(n_out=8, loss=LossFunction.MCXENT,
                                      activation=Activation.SOFTMAX))
                .set_input_type(InputType.recurrent(8)).build())
        model = MultiLayerNetwork(conf).init()
        with pytest.raises(ValueError, match="decode"):
            GenerationSession(model, max_len=8)

    def test_causal_self_attention_matches_masked_reference(self):
        """causal=True on SelfAttentionLayer == explicit future-masked
        softmax attention (training-path spot check)."""
        from deeplearning4j_tpu.nn.layers import SelfAttentionLayer
        from deeplearning4j_tpu.nn.layers.base import LayerContext

        rng = np.random.RandomState(0)
        lay = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2, causal=True)
        params = lay.init(jax.random.PRNGKey(0), jnp.float32)
        x = jnp.asarray(rng.randn(2, 8, 5), jnp.float32)
        y, _ = lay.apply(params, {}, x, LayerContext())
        # manual: per-position prefix attention
        for t in range(5):
            lay_nc = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2)
            y_pref, _ = lay_nc.apply(params, {}, x[:, :, : t + 1],
                                     LayerContext())
            np.testing.assert_allclose(np.asarray(y[:, :, t]),
                                       np.asarray(y_pref[:, :, t]),
                                       atol=1e-5)

    def test_bucket_length(self):
        assert [bucket_length(n, 16) for n in (1, 2, 3, 5, 8, 9, 16, 99)] \
            == [1, 2, 4, 8, 8, 16, 16, 16]


# ---------------------------------------------------------------------------
# DecodeEngine
# ---------------------------------------------------------------------------


class TestDecodeEngine:
    MAX_LEN = 24

    @pytest.fixture()
    def lm(self):
        return TransformerLM(vocab_size=23, hidden=32, n_layers=2,
                             n_heads=4, max_len=self.MAX_LEN).init()

    def _engine(self, lm, **kw):
        reg = kw.pop("registry", MetricsRegistry())
        return DecodeEngine(lm, max_len=self.MAX_LEN, registry=reg, **kw), reg

    def test_matches_session_and_batches_mixed_positions(self, lm):
        """Requests submitted together at different prompt lengths decode
        in one cache and still match the single-sequence session."""
        eng, reg = self._engine(lm, slots=4, name="eng-eq")
        try:
            handles = [eng.submit([1, 2, 3], max_tokens=6),
                       eng.submit([4, 5, 6, 7, 8], max_tokens=6),
                       eng.submit([2, 2], max_tokens=6)]
            got = [h.result(timeout=120) for h in handles]
        finally:
            eng.shutdown()
        sess = GenerationSession(lm, max_len=self.MAX_LEN)
        exp = sess.generate([[1, 2, 3], [4, 5, 6, 7, 8], [2, 2]], 6,
                            greedy=True)
        assert got == exp

    def test_staggered_arrival_continuous_batching(self, lm):
        """A request arriving while another is mid-decode joins the same
        cache (different position) without corrupting either stream."""
        eng, reg = self._engine(lm, slots=4, name="eng-stagger")
        try:
            h1 = eng.submit([1, 2, 3], max_tokens=10)
            # wait for a few tokens before the second arrives
            ev = iter(h1.events(timeout=60))
            for _ in range(3):
                next(ev)
            h2 = eng.submit([4, 5, 6, 7, 8], max_tokens=6)
            got1 = h1.result(timeout=120)
            got2 = h2.result(timeout=120)
        finally:
            eng.shutdown()
        sess = GenerationSession(lm, max_len=self.MAX_LEN)
        assert got1 == sess.generate([[1, 2, 3]], 10, greedy=True)[0]
        assert got2 == sess.generate([[4, 5, 6, 7, 8]], 6, greedy=True)[0]

    def test_admission_shed_and_metrics(self, lm):
        import threading

        from deeplearning4j_tpu.core.resilience import AdmissionRejectedError

        gate = threading.Event()
        eng, reg = self._engine(lm, slots=1, queue_limit=2, name="eng-shed",
                                step_hook=lambda: gate.wait(0.02))
        try:
            h1 = eng.submit([1, 2, 3], max_tokens=self.MAX_LEN)
            h2 = eng.submit([1, 2], max_tokens=4)  # queued behind the slot
            with pytest.raises(AdmissionRejectedError) as ei:
                eng.submit([1], max_tokens=2)
            assert ei.value.retry_after is not None
            gate.set()
            h1.result(timeout=120)
            h2.result(timeout=120)
            s = eng.stats()
            assert s["shed"] == 1 and s["completed"] == 2
            assert s["in_flight"] == 0
            assert int(eng._c_tokens.value) > 0
        finally:
            eng.shutdown()

    def test_deadline_mid_stream_partial_output(self, lm):
        import time as _t

        eng, reg = self._engine(lm, slots=2, name="eng-dl",
                                step_hook=lambda: _t.sleep(0.05))
        try:
            h = eng.submit([1, 2, 3], max_tokens=self.MAX_LEN, timeout=0.4)
            evs = list(h.events(timeout=60))
        finally:
            eng.shutdown()
        assert evs[-1]["done"] and evs[-1]["reason"] == "deadline"
        assert 1 <= evs[-1]["count"] < self.MAX_LEN - 3
        # ordered partial output
        assert [e["index"] for e in evs[:-1]] == list(range(evs[-1]["count"]))

    def test_cancel_frees_slot(self, lm):
        import time as _t

        eng, reg = self._engine(lm, slots=1, name="eng-cancel",
                                step_hook=lambda: _t.sleep(0.01))
        try:
            h = eng.submit([1, 2, 3], max_tokens=self.MAX_LEN)
            next(iter(h.events(timeout=60)))  # it is decoding
            h.cancel()
            for _ in range(200):
                if eng.stats()["active_slots"] == 0:
                    break
                _t.sleep(0.02)
            s = eng.stats()
            assert s["active_slots"] == 0 and s["in_flight"] == 0
            assert s["cancelled"] == 1
            # the freed slot serves a new request
            assert eng.submit([4, 5], max_tokens=3).result(timeout=120)
        finally:
            eng.shutdown()

    def test_gauge_and_histogram_series(self, lm):
        eng, reg = self._engine(lm, slots=2, name="eng-obs")
        try:
            eng.submit([1, 2, 3], max_tokens=4).result(timeout=120)
        finally:
            eng.shutdown()
        # read back through the engine's held children (the registry is
        # the single source of truth; exposition is covered by the
        # generate contract tool)
        assert int(eng._c_tokens.value) == 4
        assert eng._g_inflight.value == 0
        assert eng._h_prefill.count >= 1
        assert eng._h_decode.count >= 1
        # the prefill hands out token 0, three greedy steps the rest
        sampler = reg.get("dl4j_tpu_decode_sampler_steps_total")
        assert [sampler.labels("eng-obs", path).value
                for path in S.PATHS] == [3, 0, 0]
        assert eng.stats()["sampler_sort_share"] == 0.0

    def test_sampler_counter_follows_the_active_rows_specs(self, lm,
                                                           monkeypatch):
        """A mixed batch counts ``sort``; once the sampled request has ended
        its slot's stale spec holds no later step there; and what the host
        counts is what the step's program branched on."""
        from collections import Counter

        from deeplearning4j_tpu.parallel import decode as D

        on_device = []

        def spy(logits, seeds, steps, gmask, temps, ks, ps):
            if logits.shape[0] == 2:  # the step's call, not a prefill's
                jax.debug.callback(lambda i: on_device.append(int(i)),
                                   S.sampler_path(gmask, ks, ps))
            return sample_tokens(logits, seeds, steps, gmask, temps, ks, ps)

        monkeypatch.setattr(D, "sample_tokens", spy)
        eng, reg = self._engine(lm, slots=2, name="eng-path")
        assert eng.stats()["sampler_sort_share"] is None
        sampler = reg.get("dl4j_tpu_decode_sampler_steps_total")

        def counts():
            return {path: int(sampler.labels("eng-path", path).value)
                    for path in S.PATHS}

        try:
            long = eng.submit([1, 2, 3], max_tokens=16)  # slot 0, greedy
            next(iter(long.events(timeout=60)))
            # slot 1: three tokens, two of them from steps beside slot 0's
            eng.submit([4, 5], max_tokens=3, greedy=False, top_k=5,
                       seed=3).result(timeout=120)
            long.result(timeout=120)
            first = counts()
            share = eng.stats()["sampler_sort_share"]
            # slot 1 still holds top_k = 5; slot 0 serves again
            eng.submit([6, 7], max_tokens=8).result(timeout=120)
            second = counts()
            jax.effects_barrier()
            assert not eng._greedy[1] and eng._ks[1] == 5
            assert eng.stats()["sampler_sort_share"] < share
        finally:
            eng.shutdown()
        assert 1 <= first["sort"] <= 3 and first["sample"] == 0
        assert first["argmax"] + first["sort"] == 15
        assert second == {"argmax": first["argmax"] + 7, "sample": 0,
                          "sort": first["sort"]}
        assert Counter(S.PATHS[i] for i in on_device) == Counter(
            {k: v for k, v in second.items() if v})

    def test_prompt_too_long_rejected(self, lm):
        eng, _ = self._engine(lm, slots=1, name="eng-long")
        try:
            with pytest.raises(ValueError, match="max_len"):
                eng.submit(list(range(1, self.MAX_LEN + 2)), max_tokens=2)
        finally:
            eng.shutdown()
