"""Speculative decoding (ISSUE 11).

The load-bearing contract is DISTRIBUTION EXACTNESS: exact acceptance
sampling (accept-or-resample against the target/draft probability ratio)
must keep the output law byte-identical to plain sampling under the same
``(seed, step)`` keying — greedy streams token-for-token identical to
non-speculative decode for every k, every prompt bucket, all the way to
the cache limit (where the k+1 window no longer fits and the boundary
fallback takes over) — plus cache rewind under rejection, the engine's
per-request ``speculative_k``, the decode-side AIMD controller, and the
slot-release regression for cancelled/expired bursts.
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu.generate import (
    GenerationSession,
    SpeculativeGenerationSession,
    sample_tokens,
    speculative_accept,
)
from deeplearning4j_tpu.generate.sampling import _warped_probs
from deeplearning4j_tpu.model.zoo import TextGenerationLSTM, TransformerLM
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.parallel import DecodeAIMD, DecodeEngine


MAX_LEN = 16
VOCAB = 23


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(vocab_size=VOCAB, hidden=32, n_layers=2,
                         n_heads=4, max_len=MAX_LEN).init()


@pytest.fixture(scope="module")
def draft_lm():
    # deliberately uncorrelated with the target (different arch + seed):
    # acceptance is near-chance, so the rejection/rewind path dominates
    return TransformerLM(vocab_size=VOCAB, hidden=16, n_layers=1,
                         n_heads=2, max_len=MAX_LEN, seed=99).init()


# ---------------------------------------------------------------------------
# the acceptance primitive
# ---------------------------------------------------------------------------


class TestAcceptPrimitive:
    def test_closed_form_exactness(self):
        """The accept-or-resample law emits exactly the target
        distribution: q(x)·min(1, p/q)(x) + P(reject)·residual == p,
        for arbitrary draft/target pairs (the algorithm's defining
        identity, checked in float64)."""
        rng = np.random.RandomState(0)
        for _ in range(20):
            p = rng.dirichlet(np.ones(11))
            q = rng.dirichlet(np.ones(11))
            accept = q * np.minimum(1.0, p / np.maximum(q, 1e-300))
            p_reject = 1.0 - accept.sum()
            resid = np.maximum(p - q, 0.0)
            resid = resid / resid.sum() if resid.sum() > 0 else p
            emitted = accept + p_reject * resid
            np.testing.assert_allclose(emitted, p, atol=1e-12)

    def test_monte_carlo_marginal_matches_target(self):
        """The jitted primitive's first-emitted-token marginal equals the
        warped target distribution (deterministic: fixed seed ensemble),
        under temperature + top-p warping."""
        rng = np.random.RandomState(1)
        V, B = 8, 4000
        zt = rng.randn(V).astype(np.float32)
        zd = rng.randn(V).astype(np.float32)
        seeds = jnp.arange(B, dtype=jnp.uint32)
        steps = jnp.zeros((B,), jnp.int32)
        gmask = jnp.zeros((B,), bool)
        temps = jnp.full((B,), 0.9, jnp.float32)
        ks = jnp.zeros((B,), jnp.int32)
        ps = jnp.full((B,), 0.95, jnp.float32)
        d_logits = jnp.broadcast_to(jnp.asarray(zd), (B, 1, V))
        d_toks = sample_tokens(d_logits[:, 0], seeds, steps, gmask, temps,
                               ks, ps)[:, None]
        t_logits = jnp.broadcast_to(jnp.asarray(zt), (B, 2, V))
        toks, n_acc, n_emit = speculative_accept(
            d_toks, d_logits, t_logits, seeds, steps,
            jnp.ones((B,), jnp.int32), gmask, temps, ks, ps)
        assert np.array_equal(np.asarray(n_emit), np.asarray(n_acc) + 1)
        emp = np.bincount(np.asarray(toks[:, 0]), minlength=V) / B
        pt = np.asarray(_warped_probs(
            jnp.asarray(zt), jnp.asarray(False), jnp.asarray(0.9),
            jnp.asarray(0), jnp.asarray(0.95)))
        assert 0.5 * np.abs(emp - pt).sum() < 0.05

    def test_greedy_rows_accept_iff_argmax_matches(self):
        rng = np.random.RandomState(2)
        V, K = 9, 3
        t_logits = jnp.asarray(rng.randn(2, K + 1, V), jnp.float32)
        d_logits = jnp.asarray(rng.randn(2, K, V), jnp.float32)
        t_argmax = np.asarray(jnp.argmax(t_logits, axis=-1))
        # row 0 proposes the target's argmax everywhere -> full accept +
        # bonus; row 1 mismatches at position 0 -> correction emitted
        d_toks = np.stack([t_argmax[0, :K],
                           (t_argmax[1, :K] + 1) % V]).astype(np.int32)
        toks, n_acc, n_emit = speculative_accept(
            jnp.asarray(d_toks), d_logits, t_logits,
            jnp.asarray([5, 5], jnp.uint32), jnp.zeros((2,), jnp.int32),
            jnp.full((2,), K, jnp.int32), jnp.ones((2,), bool),
            jnp.ones((2,), jnp.float32), jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), jnp.float32))
        assert int(n_acc[0]) == K and int(n_emit[0]) == K + 1
        assert np.asarray(toks[0]).tolist() == t_argmax[0].tolist()
        assert int(n_acc[1]) == 0 and int(n_emit[1]) == 1
        assert int(toks[1, 0]) == int(t_argmax[1, 0])

    def test_k0_row_reproduces_plain_sampler(self):
        """spec_ks == 0 degenerates to plain sampling with the SAME
        (seed, step) key — a non-speculative request inside a speculative
        batch keeps its exact stream."""
        rng = np.random.RandomState(3)
        V = 12
        t_logits = jnp.asarray(rng.randn(6, 2, V), jnp.float32)
        d_logits = jnp.asarray(rng.randn(6, 1, V), jnp.float32)
        seeds = jnp.arange(6, dtype=jnp.uint32)
        steps = jnp.full((6,), 4, jnp.int32)
        gmask = jnp.zeros((6,), bool)
        temps = jnp.full((6,), 0.8, jnp.float32)
        ks = jnp.full((6,), 5, jnp.int32)
        ps = jnp.ones((6,), jnp.float32)
        toks, n_acc, n_emit = speculative_accept(
            jnp.zeros((6, 1), jnp.int32), d_logits, t_logits, seeds, steps,
            jnp.zeros((6,), jnp.int32), gmask, temps, ks, ps)
        plain = sample_tokens(t_logits[:, 0], seeds, steps, gmask, temps,
                              ks, ps)
        assert np.asarray(n_acc).tolist() == [0] * 6
        assert np.asarray(toks[:, 0]).tolist() == np.asarray(plain).tolist()


# ---------------------------------------------------------------------------
# SpeculativeGenerationSession
# ---------------------------------------------------------------------------


class TestSpeculativeSession:
    def test_greedy_identity_across_buckets_and_k(self, lm, draft_lm):
        """Greedy speculative == plain greedy token-for-token, for k in
        {1, 2, 4}, prompts straddling bucket boundaries, run to the cache
        limit (exercising the boundary fallback AND heavy rejection /
        cache rewind — the draft is uncorrelated with the target)."""
        plain = GenerationSession(lm, max_len=MAX_LEN)
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 3, 1, 4, 1, 5, 9, 2]]
        ref = plain.generate(prompts, MAX_LEN, greedy=True)
        for k in (1, 2, 4):
            spec = SpeculativeGenerationSession(lm, draft_lm,
                                                max_len=MAX_LEN, k=k)
            got = spec.generate(prompts, MAX_LEN, greedy=True)
            assert got == ref, f"k={k}: {got} != {ref}"
            st = spec.last_stats
            assert st["spec_steps"] > 0 and st["proposed"] > 0

    def test_greedy_identity_full_acceptance(self, lm):
        """Draft == target: every proposal accepted (the bonus-token
        path), stream still identical and accepted/step == k + 1."""
        plain = GenerationSession(lm, max_len=MAX_LEN)
        spec = SpeculativeGenerationSession(lm, lm, max_len=MAX_LEN, k=2)
        prompts = [[1, 2, 3]]
        n = 9  # stays clear of max_len so every step is a full window
        assert spec.generate(prompts, n, greedy=True) \
            == plain.generate(prompts, n, greedy=True)
        st = spec.last_stats
        assert st["acceptance_rate"] == 1.0
        assert st["accepted_per_step"] == 3.0

    def test_sampled_deterministic_and_batch_independent(self, lm, draft_lm):
        spec = SpeculativeGenerationSession(lm, draft_lm, max_len=MAX_LEN,
                                            k=2)
        kw = dict(greedy=False, temperature=0.9, top_k=8, seed=42)
        a = spec.generate([[1, 2, 3]], 6, **kw)
        b = spec.generate([[1, 2, 3]], 6, **kw)
        assert a == b
        # the same row, batched with a neighbor, keeps its exact stream
        both = spec.generate([[1, 2, 3], [4, 5]], 6, **kw)
        assert both[0] == a[0]

    DIST_B = 512
    DIST_KW = dict(greedy=False, temperature=0.8, top_k=8, top_p=0.95,
                   seed=0)

    @pytest.fixture(scope="class")
    def dist_ref(self, lm):
        plain = GenerationSession(lm, max_len=MAX_LEN)
        return plain.generate([[1, 2, 3]] * self.DIST_B, 2, **self.DIST_KW)

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_sampled_distribution_equivalence(self, lm, draft_lm, dist_ref,
                                              k):
        """temperature/top-p speculative sampling matches plain sampling
        in distribution under the same (seed, step) keys: over a fixed
        512-seed ensemble (one batched call, rows = seeds), the
        first-speculative-token empirical distribution matches plain
        decode's. Deterministic — fixed seeds, no flake."""
        B = self.DIST_B
        prompts = [[1, 2, 3]] * B
        ref = dist_ref
        spec = SpeculativeGenerationSession(lm, draft_lm, max_len=MAX_LEN,
                                            k=k)
        got = spec.generate(prompts, 2, **self.DIST_KW)
        # token 0 comes from the (shared) prefill sampler: exact equality
        assert [r[0] for r in got] == [r[0] for r in ref]
        emp_ref = np.bincount([r[1] for r in ref], minlength=VOCAB) / B
        emp_got = np.bincount([r[1] for r in got], minlength=VOCAB) / B
        tv = 0.5 * np.abs(emp_ref - emp_got).sum()
        assert tv < 0.15, f"k={k}: TV {tv}"

    def test_recurrent_models_rejected(self, lm):
        lstm = TextGenerationLSTM(vocab_size=VOCAB, hidden=16,
                                  layers=1).init()
        with pytest.raises(ValueError, match="position-indexed"):
            SpeculativeGenerationSession(lstm, lstm, max_len=MAX_LEN)
        with pytest.raises(ValueError, match="position-indexed"):
            SpeculativeGenerationSession(lm, lstm, max_len=MAX_LEN)

    def test_vocab_mismatch_rejected(self, lm):
        other = TransformerLM(vocab_size=VOCAB + 1, hidden=16, n_layers=1,
                              n_heads=2, max_len=MAX_LEN).init()
        with pytest.raises(ValueError, match="vocab"):
            SpeculativeGenerationSession(lm, other, max_len=MAX_LEN)


# ---------------------------------------------------------------------------
# DecodeEngine with a draft model
# ---------------------------------------------------------------------------


class TestSpeculativeEngine:
    def _engine(self, lm, draft, **kw):
        reg = kw.pop("registry", MetricsRegistry())
        return DecodeEngine(lm, draft_model=draft, max_len=MAX_LEN,
                            registry=reg, **kw), reg

    def test_matches_plain_engine_mixed_k(self, lm, draft_lm):
        """Speculative engine greedy output == plain session, with
        per-request speculative_k (0 = plain decode) mixed in one batch
        and one request running to the cache limit."""
        eng, _ = self._engine(lm, draft_lm, speculative_k=3, slots=4,
                              name="spec-eq")
        try:
            handles = [eng.submit([1, 2, 3], max_tokens=6),
                       eng.submit([4, 5, 6, 7, 8], max_tokens=6,
                                  speculative_k=1),
                       eng.submit([2, 2], max_tokens=6, speculative_k=0),
                       eng.submit([9, 3, 1], max_tokens=MAX_LEN)]
            got = [h.result(timeout=180) for h in handles]
        finally:
            eng.shutdown()
        sess = GenerationSession(lm, max_len=MAX_LEN)
        full = sess.generate([[1, 2, 3], [4, 5, 6, 7, 8], [2, 2],
                              [9, 3, 1]], MAX_LEN, greedy=True)
        exp = [full[0][:6], full[1][:6], full[2][:6], full[3]]
        assert got == exp

    def test_staggered_arrival(self, lm, draft_lm):
        eng, _ = self._engine(lm, draft_lm, speculative_k=2, slots=4,
                              name="spec-stagger")
        try:
            h1 = eng.submit([1, 2, 3], max_tokens=10)
            ev = iter(h1.events(timeout=60))
            for _ in range(3):
                next(ev)
            h2 = eng.submit([4, 5, 6, 7, 8], max_tokens=6)
            got1 = h1.result(timeout=180)
            got2 = h2.result(timeout=180)
        finally:
            eng.shutdown()
        sess = GenerationSession(lm, max_len=MAX_LEN)
        assert got1 == sess.generate([[1, 2, 3]], 10, greedy=True)[0]
        assert got2 == sess.generate([[4, 5, 6, 7, 8]], 6, greedy=True)[0]

    def test_slot_release_regression(self, lm, draft_lm):
        """ISSUE 11 small fix: a burst of cancelled/expired requests —
        mid-speculation AND still queued — releases every draft/target
        cache slot and admission slot; full capacity serves afterwards."""
        gate = {"delay": 0.05}
        eng, _ = self._engine(lm, draft_lm, speculative_k=2, slots=2,
                              queue_limit=6, name="spec-leak",
                              step_hook=lambda: time.sleep(gate["delay"]))
        try:
            long = [eng.submit([1, 2, 3], max_tokens=MAX_LEN - 4)
                    for _ in range(2)]
            queued = [eng.submit([4, 5], max_tokens=4, timeout=0.2)
                      for _ in range(4)]
            # both slots decoding, four waiting
            deadline = time.monotonic() + 30
            while eng.stats()["active_slots"] < 2:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            for h in long:
                h.cancel()
            # queued requests expire in place (0.2s deadline) without
            # ever reaching a slot; cancelled actives free mid-window
            deadline = time.monotonic() + 60
            while eng.stats()["in_flight"] > 0:
                assert time.monotonic() < deadline, eng.stats()
                time.sleep(0.02)
            gate["delay"] = 0.0
            s = eng.stats()
            assert s["active_slots"] == 0 and s["in_flight"] == 0
            assert s["cancelled"] >= 2
            for h in queued:
                h.result(timeout=10)  # all terminal (deadline/cancel)
            # recovered: full capacity (slots + queue) completes
            again = [eng.submit([6, 7], max_tokens=3) for _ in range(6)]
            for h in again:
                assert len(h.result(timeout=180)) == 3
            assert eng.stats()["in_flight"] == 0
        finally:
            eng.shutdown()

    def test_stats_zero_guarded_and_metrics(self, lm, draft_lm):
        reg = MetricsRegistry()
        eng, _ = self._engine(lm, draft_lm, speculative_k=2, slots=2,
                              name="spec-stats", registry=reg)
        try:
            s = eng.stats()
            assert s["speculative"]["enabled"] is True
            assert s["speculative"]["current_k"] == 2
            assert s["speculative"]["acceptance_rate"] is None
            assert s["speculative"]["accepted_tokens_per_step"] is None
            assert s["per_token_p95_s"] is None
            assert s["slot_target"] == 2
            eng.submit([1, 2, 3], max_tokens=5).result(timeout=180)
            s = eng.stats()
            assert s["speculative"]["proposed"] > 0
            assert s["speculative"]["acceptance_rate"] is not None
            assert s["per_token_p95_s"] is not None
        finally:
            eng.shutdown()
        from deeplearning4j_tpu.obs.prom import render_prometheus

        text = render_prometheus(reg)
        for series in ("dl4j_tpu_generate_spec_proposed_total",
                       "dl4j_tpu_generate_spec_accepted_total",
                       "dl4j_tpu_generate_spec_steps_total",
                       "dl4j_tpu_generate_speculative_k",
                       "dl4j_tpu_generate_slot_target",
                       "dl4j_tpu_generate_token_latency_seconds"):
            assert series in text, f"missing {series}"

    def test_plain_step_leaves_spec_rows_committed_cache(self, lm, draft_lm):
        """ISSUE 27: a turn that splits its rows — one near ``max_len``
        (no room for a k+1 window) or with ``speculative_k=0`` takes the
        plain step, the others the speculative one — runs the plain step
        with the speculative rows live but inactive. The plain step
        donates the carry and freezes idle rows by dropping their writes:
        a speculative row's committed cache ``[0, pos)`` and its ``pos``
        come out of it bit-identical, and every stream is the session's."""
        eng, _ = self._engine(lm, draft_lm, speculative_k=3, slots=3,
                              name="spec-split")
        real, seen = eng._step, []

        def host(rows):
            return [(name, k, r, np.array(v[r, :, :int(eng._pos[r])]))
                    for name, st in eng._carry.items()
                    for k, v in st.items() if k != "pos"
                    for r in rows], [
                        np.array(st["pos"]) for st in eng._carry.values()]

        def step(rows=None, parent=None):
            idle = [r for r in range(eng.slots)
                    if eng._requests[r] is not None and not rows[r]]
            before, pos = host(idle)
            real(rows, parent)
            after, pos2 = host(idle)
            same = all(np.array_equal(a[3], b[3])
                       for a, b in zip(before, after)) and all(
                np.array_equal(a[idle], b[idle])
                for a, b in zip(pos, pos2))
            seen.append((len(idle), int(rows.sum()), same))

        eng._step = step
        near = list(range(1, MAX_LEN - 4))  # 11 tokens: plain from pos 13
        try:
            handles = [eng.submit(near, max_tokens=MAX_LEN),
                       eng.submit([1, 2, 3], max_tokens=9),
                       eng.submit([4, 5], max_tokens=9, speculative_k=0)]
            got = [h.result(timeout=180) for h in handles]
        finally:
            eng.shutdown()
        # some plain step ran beside a live speculative row, none moved one
        assert any(n_idle and n_rows for n_idle, n_rows, _ in seen), seen
        assert all(same for _, _, same in seen), seen
        sess = GenerationSession(lm, max_len=MAX_LEN)
        exp = sess.generate([near, [1, 2, 3], [4, 5]], MAX_LEN, greedy=True)
        assert got == [exp[0], exp[1][:9], exp[2][:9]]

    def test_plain_engine_unchanged(self, lm):
        """No draft model: speculative surface reports disabled and the
        engine path is the PR-9 one."""
        eng = DecodeEngine(lm, max_len=MAX_LEN, slots=2,
                           registry=MetricsRegistry(), name="no-spec")
        try:
            s = eng.stats()
            assert s["speculative"]["enabled"] is False
            assert s["speculative"]["current_k"] == 0
            assert eng.speculative_k == 0
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# decode-side AIMD
# ---------------------------------------------------------------------------


class TestDecodeAIMD:
    @pytest.fixture()
    def eng(self, lm, draft_lm):
        e = DecodeEngine(lm, draft_model=draft_lm, speculative_k=4,
                         max_len=MAX_LEN, slots=8,
                         registry=MetricsRegistry(), name="aimd")
        yield e
        e.shutdown(drain=False)

    def test_no_traffic_no_action(self, eng):
        assert eng.adjust() is None

    def test_breach_shrinks_k_and_slots(self, eng):
        ctl = DecodeAIMD(eng, target_p95_s=0.05)
        for _ in range(20):
            eng._h_token.observe(0.2)  # way over budget
        obs = ctl.tick()
        assert obs["action"] == "shrink"
        assert eng.speculative_k == 2 and eng.slot_target == 4
        for _ in range(20):
            eng._h_token.observe(0.2)
        ctl.tick()
        ctl_obs = ctl.tick()  # no new traffic between ticks -> None
        assert ctl_obs is None
        assert eng.speculative_k == 1 and eng.slot_target == 2

    def test_under_budget_grows_slots_then_k(self, eng):
        ctl = DecodeAIMD(eng, target_p95_s=0.05)
        eng.set_decode_control(2, 4)
        # fake queued demand: admitted-but-unplaced requests
        eng._admission.max_pending = 100
        for _ in range(3):
            eng._admission.admit()
        for _ in range(20):
            eng._h_token.observe(0.001)
        obs = ctl.tick()
        assert obs["action"] == "grow_slots"
        assert eng.slot_target == 5 and eng.speculative_k == 2
        for _ in range(3):
            eng._admission.release()
        for _ in range(20):
            eng._h_token.observe(0.001)
        obs = ctl.tick()
        assert obs["action"] == "grow_k"
        assert eng.speculative_k == 3 and eng.slot_target == 5

    def test_hold_at_max(self, eng):
        ctl = DecodeAIMD(eng, target_p95_s=0.05)
        eng.set_decode_control(4, 8)
        for _ in range(20):
            eng._h_token.observe(0.001)
        assert ctl.tick()["action"] == "hold"
        assert eng.speculative_k == 4 and eng.slot_target == 8

    def test_control_clamps(self, eng):
        assert eng.set_decode_control(99, 99) == (4, 8)
        assert eng.set_decode_control(0, 0) == (1, 1)

    def test_adaptive_loop_ticks(self, lm, draft_lm):
        """adaptive=True: the engine loop itself ticks the controller
        (observable as a k shrink under an artificially slow step)."""
        eng = DecodeEngine(lm, draft_model=draft_lm, speculative_k=4,
                           max_len=MAX_LEN, slots=2,
                           adaptive=True, target_p95_s=1e-4,
                           adjust_interval=0.05,
                           registry=MetricsRegistry(), name="aimd-loop")
        try:
            eng.submit([1, 2, 3], max_tokens=MAX_LEN - 4).result(timeout=180)
            deadline = time.monotonic() + 30
            while eng.speculative_k == 4:
                if time.monotonic() > deadline:
                    break
                eng.submit([1, 2], max_tokens=4).result(timeout=180)
            assert eng.speculative_k < 4
        finally:
            eng.shutdown()
