"""The decode engine's KV carry is updated in place (ISSUE 27): the step,
the prefill that installs its own row (ISSUE 37) and both installs of a
handed-over row donate it, an idle row's write is dropped by the cache
write itself (no select over the cache), and an engine whose donated
program died at run time comes back with a fresh carry.

Engines compile real jit programs: the layouts share module-scoped
engines where a test leaves them as it found them."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.generate.paged import (attach_block_table,
                                               mask_inactive_writes)
from deeplearning4j_tpu.generate.session import GenerationSession
from deeplearning4j_tpu.model.zoo import TextGenerationLSTM, TransformerLM
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.ops import (flash_masked_cache_write,
                                    masked_cache_write_reference,
                                    set_attention_impl)
from deeplearning4j_tpu.parallel.decode import DecodeEngine, _Request

MAX_LEN = 16
VOCAB = 23
SLOTS = 4
BS = 4
LAYOUTS = {"static": {}, "int8": {"cache_dtype": "int8"},
           "paged": {"block_size": BS}}
_PLANES = ("cache_k", "cache_v", "cache_k_scale", "cache_v_scale")


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(vocab_size=VOCAB, hidden=32, n_layers=2,
                         n_heads=4, max_len=MAX_LEN).init()


def _engine(lm, **kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("slots", SLOTS)
    return DecodeEngine(lm, max_len=MAX_LEN, **kw)


@pytest.fixture(scope="module", params=list(LAYOUTS))
def eng(request, lm):
    e = _engine(lm, **LAYOUTS[request.param])
    e.layout = request.param
    yield e
    e.shutdown(drain=False)


def _host(x):
    # a copy: on the CPU np.asarray shares the buffer, and a buffer that
    # is shared is not donated
    return np.array(x, copy=True)


def _step_args(e, active):
    # the step's operands as the loop uploads them (ISSUE 30: the token
    # vector that stays on the device first)
    return e._step_args(active)


def _planes(carry):
    return [v for st in carry.values() for k, v in st.items()
            if k in _PLANES]


def _programs(e):
    """(name, lowered program) for every program of this layout that takes
    a carry and returns one."""
    sess = e.session
    active = np.ones((e.slots,), bool)
    out = [("decode_step", e._decode_step_fn().lower(
        sess.model.params, sess.model.state, e._carry,
        *_step_args(e, active), e._table))]
    # an admitted prompt: the prefill installs its row and its first token
    req = _Request([1, 2, 3], 4, None, None, 7, True, 1.0, 0, 1.0, None, None)
    out.append(("prefill_4", e._prefill_fn(4).lower(
        sess.model.params, sess.model.state, e._carry,
        *e._prefill_args(4, 1, req))))
    # a handed-over row: the install alone
    slot = jnp.asarray(1, jnp.int32)
    if e._allocator is None:
        out.append(("install_row", e._write_row_fn().lower(
            e._carry, e._row_template, slot)))
    else:
        dest = jnp.zeros((MAX_LEN // BS,), jnp.int32)
        out.append(("paged_install", e._paged_install_fn().lower(
            e._carry, e._row_template, dest, slot)))
    return out


def test_programs_alias_every_cache_plane(eng):
    """(a) the compiled step, prefill and install write their carry where
    it lies: every cache plane of the input is aliased to its output."""
    want = sum(p.size * p.dtype.itemsize for p in _planes(eng._carry))
    for name, lowered in _programs(eng):
        got = lowered.compile().memory_analysis().alias_size_in_bytes
        assert got >= want, (name, got, want)


def test_a_step_and_an_install_consume_the_previous_carry(eng):
    """(a) after one `_step` (and one prefill before it) the planes the
    engine held are deleted: nothing keeps a second carry alive. (e) the
    paged engine's ONE shared table is no part of what is donated."""
    before = _planes(eng._carry)
    assert all("block_table" not in st for st in eng._carry.values())
    table = eng._table
    got = eng.submit([1, 2, 3], max_tokens=3).result(timeout=120)
    assert len(got) == 3
    assert all(p.is_deleted() for p in before)
    assert not any(p.is_deleted() for p in _planes(eng._carry))
    if eng.layout == "paged":
        assert not table.is_deleted() and eng._table.shape == table.shape
    else:
        assert eng._table is None
    assert eng.stats()["carry_rebuilds"] == 0


def _fill(e, rs, pos):
    """A carry of random planes with the given per-row positions (and,
    for a paged engine, a block list per row), installed in the engine's
    place of a fresh one; returns a host copy."""
    def rnd(leaf):
        if leaf.dtype == jnp.int8:
            return jnp.asarray(rs.randint(-127, 128, leaf.shape), jnp.int8)
        return jnp.asarray(rs.randn(*leaf.shape), leaf.dtype)

    carry = {}
    for name, st in e._fresh_carry().items():
        carry[name] = {k: (jnp.asarray(pos, v.dtype) if k == "pos"
                           else rnd(v)) for k, v in st.items()}
    if e._allocator is not None:
        nbr = MAX_LEN // BS
        e._block_tables[:] = 1 + np.arange(e.slots * nbr).reshape(
            e.slots, nbr)
        e._push_tables()
    return carry, jax.tree_util.tree_map(_host, carry)


def test_inactive_rows_are_bit_identical_across_a_step(eng):
    """(b) active, idle-but-live and free rows in one step — one live row
    at pos == max_len - 1, a free slot at pos == max_len (where a write
    would clamp onto position max_len - 1): every inactive row's planes
    and pos come back bit-identical, the active rows advance."""
    _check_inactive_rows(eng)


def _check_inactive_rows(e):
    rs = np.random.RandomState(7)
    L = e.max_len
    pos = np.asarray([3, L - 1, L, 5], np.int32)
    active = np.asarray([True, False, False, True])
    carry, host = _fill(e, rs, pos)
    sess = e.session
    new, toks, _ = e._decode_step_fn()(
        sess.model.params, sess.model.state, carry, *_step_args(e, active),
        e._table)
    assert all(p.is_deleted() for p in _planes(carry))
    new = jax.tree_util.tree_map(np.asarray, new)
    assert set(new) == set(host)
    for name, st in new.items():
        assert set(st) == set(host[name])
        np.testing.assert_array_equal(
            st["pos"], np.where(active, pos + 1, pos))
        for k in set(st) & set(_PLANES):
            if e._allocator is None:
                idle = ~active
                np.testing.assert_array_equal(st[k][idle],
                                              host[name][k][idle])
                # the active rows wrote their own position, only that
                for r in np.nonzero(active)[0]:
                    same = np.ones((L,), bool)
                    same[pos[r]] = False
                    np.testing.assert_array_equal(
                        st[k][r][:, same], host[name][k][r][:, same])
                    assert (st[k][r][:, pos[r]]
                            != host[name][k][r][:, pos[r]]).any()
            else:
                # every block but the trash block and the two blocks the
                # active rows wrote into is as it was
                wrote = {0} | {int(e._block_tables[r, pos[r] // BS])
                               for r in np.nonzero(active)[0]}
                keep = np.asarray([b not in wrote
                                   for b in range(st[k].shape[0])])
                np.testing.assert_array_equal(st[k][keep],
                                              host[name][k][keep])
    assert (np.asarray(toks)[~active] == 0).all()


@pytest.fixture()
def pallas_kernels():
    """The step's TPU spelling on the CPU: the decode kernel and the
    in-place cache write, interpreted."""
    set_attention_impl("flash")
    yield
    set_attention_impl("auto")


def test_inactive_rows_are_bit_identical_under_the_kernels(lm, pallas_kernels):
    """(b) again with the step as the chip runs it: the Pallas cache write
    moves one block a row and leaves a masked row's block as it was."""
    e = _engine(lm)
    try:
        _check_inactive_rows(e)
        got = e.submit([1, 2, 3], max_tokens=5).result(timeout=120)
    finally:
        e.shutdown(drain=False)
    assert got == GenerationSession(lm, max_len=MAX_LEN).generate(
        [[1, 2, 3]], 5)[0]


@pytest.mark.parametrize("layout", ["static", "int8"])
def test_the_decode_kernel_writes_the_steps_planes(layout, pallas_kernels):
    """A cache of 128 positions (one block, one tile) under the step's TPU
    spelling: the float planes are written by the decode kernel itself
    (2 planes a layer a step, ``path="fused"``), an int8 cache's four by
    writes of their own. Inactive rows stay bit-identical either way, and
    the tokens are the XLA spelling's."""
    L = 128
    lm = TransformerLM(vocab_size=VOCAB, hidden=32, n_layers=2, n_heads=4,
                       max_len=L).init()
    reg = MetricsRegistry()
    e = DecodeEngine(lm, max_len=L, slots=SLOTS, registry=reg, name="kvw",
                     **LAYOUTS[layout])
    try:
        assert e.stats()["kv_write_fused_share"] is None
        _check_inactive_rows(e)
        n, m = 3, 6
        got = e.submit(list(range(1, n + 1)), max_tokens=m).result(
            timeout=120)
        share = e.stats()["kv_write_fused_share"]
    finally:
        e.shutdown(drain=False)
    c = reg.get("dl4j_tpu_decode_kv_writes_total")
    fused, separate = (c.labels("kvw", p).value for p in ("fused", "separate"))
    steps = m - 1  # the prefill hands out the first token
    if layout == "static":
        assert (fused, separate, share) == (2 * 2 * steps, 0, 1.0)
    else:
        assert (fused, separate, share) == (0, 4 * 2 * steps, 0.0)
    set_attention_impl("xla")
    want = GenerationSession(lm, max_len=L).generate([list(range(1, n + 1))],
                                                     m)[0]
    assert got == want


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.int8])
@pytest.mark.parametrize("L", [24, 256])
def test_the_cache_write_kernel_is_the_scatter(dtype, L):
    """One token a row, planes and scale planes: the kernel (interpreted)
    and the scatter agree to the bit, masked rows, rows at the cache's end
    and past it (clamped as ``dynamic_update_slice`` clamps) among them."""
    rs = np.random.RandomState(L)
    pos = jnp.asarray([0, 5, L - 1, L, L + 4, 130 % L], jnp.int32)
    mask = jnp.asarray([True, False, True, False, True, True])
    for shape in ((6, 3, L, 8), (6, 3, L)):
        cache = jnp.asarray(rs.randint(-90, 90, shape), dtype)
        new = jnp.asarray(rs.randint(-90, 90, shape[:2] + (1,) + shape[3:]),
                          dtype)
        for m in (mask, jnp.ones_like(mask)):
            np.testing.assert_array_equal(
                np.asarray(flash_masked_cache_write(cache, new, pos, m,
                                                    interpret=True),
                           np.float32),
                np.asarray(masked_cache_write_reference(cache, new, pos, m),
                           np.float32))


def test_only_layers_that_declare_planes_take_the_write_mask():
    """Layers that declare no planes keep the whole-leaf select: no mask is
    attached to a recurrent carry, and a paged layer gets the redirected
    table, not a mask. The declaration is the layer's (``decode_planes``),
    handed in as the session has it; no leaf is known by its name."""
    active = jnp.asarray([True, False])
    planes = {"att": frozenset({"cache_k", "cache_v"})}
    rec = {"lstm": {"h": jnp.zeros((2, 3)), "c": jnp.zeros((2, 3))},
           "cx": {"h": jnp.zeros((2, 3)), "cache_x": jnp.zeros((2, 4, 3)),
                  "pos": jnp.zeros((2,), jnp.int32)},
           "att": {"cache_k": jnp.zeros((2, 1, 4, 2)),
                   "cache_v": jnp.zeros((2, 1, 4, 2)),
                   "pos": jnp.zeros((2,), jnp.int32)}}
    out = mask_inactive_writes(rec, active, planes)
    assert "write_mask" not in out["lstm"] and "write_mask" not in out["cx"]
    assert out["att"]["write_mask"] is active
    assert "write_mask" not in mask_inactive_writes(rec, active)["att"]
    paged = attach_block_table(
        {"att": {"cache_k": jnp.zeros((3, 1, 2, 2)),
                 "cache_v": jnp.zeros((3, 1, 2, 2)),
                 "pos": jnp.zeros((2,), jnp.int32)},
         "posemb": {"pos": jnp.zeros((2,), jnp.int32)}},
        jnp.asarray([[1, 2], [2, 1]], jnp.int32))
    assert "block_table" not in paged["posemb"]
    out = mask_inactive_writes(paged, active, planes)
    assert "write_mask" not in out["att"]
    np.testing.assert_array_equal(out["att"]["block_table"],
                                  [[1, 2], [0, 0]])


def test_lstm_engine_freezes_idle_rows():
    """The recurrent carry (h, c: small per-row leaves) is donated too and
    keeps its where: an idle row's state does not advance."""
    model = TextGenerationLSTM(vocab_size=11, hidden=8, layers=1).init()
    e = DecodeEngine(model, max_len=MAX_LEN, slots=2,
                     registry=MetricsRegistry())
    try:
        rs = np.random.RandomState(3)
        carry = jax.tree_util.tree_map(
            lambda l: jnp.asarray(rs.randn(*l.shape), l.dtype)
            if jnp.issubdtype(l.dtype, jnp.floating) else l,
            e._fresh_carry())
        host = jax.tree_util.tree_map(_host, carry)
        active = np.asarray([True, False])
        sess = e.session
        new, _, _ = e._decode_step_fn()(
            sess.model.params, sess.model.state, carry,
            *_step_args(e, active), e._table)
        for a, b in zip(jax.tree_util.tree_leaves(new),
                        jax.tree_util.tree_leaves(host)):
            np.testing.assert_array_equal(np.asarray(a)[1], b[1])
            assert (np.asarray(a)[0] != b[0]).any()
    finally:
        e.shutdown(drain=False)


PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [2, 2], [9, 3, 1, 7], [5],
           [8, 8, 1], [3, 4, 5, 6, 7, 8, 9]]
LENGTHS = [6, 3, 9, 4, 7, 5, 8]


@pytest.mark.parametrize("sampling", [
    {"greedy": True},
    {"greedy": False, "temperature": 0.9, "top_k": 7, "top_p": 0.95},
], ids=["greedy", "sampled"])
def test_streams_under_slot_churn_equal_the_session(lm, sampling):
    """(c) more requests than slots, so installs reuse rows that idle rows
    were frozen in: every stream equals the single-sequence session's."""
    e = _engine(lm, slots=2)
    try:
        hs = [e.submit(p, max_tokens=n, seed=11 + i, **sampling)
              for i, (p, n) in enumerate(zip(PROMPTS, LENGTHS))]
        got = [h.result(timeout=180) for h in hs]
        assert e.stats()["failed"] == 0
    finally:
        e.shutdown()
    sess = GenerationSession(lm, max_len=MAX_LEN)
    for i, (p, n) in enumerate(zip(PROMPTS, LENGTHS)):
        assert got[i] == sess.generate([p], n, seed=11 + i, **sampling)[0]


def _wait(cond, timeout=60.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end
        time.sleep(0.01)


@pytest.mark.parametrize("layout", ["static", "paged"])
def test_a_poisoned_step_leaves_a_carry_to_go_on_with(lm, layout):
    """(d) a step that raises after it consumed the carry fails the active
    requests, counts one rebuild, and the next request completes with the
    tokens a fresh engine gives; one that raises while tracing (nothing
    consumed) rebuilds nothing."""
    reg = MetricsRegistry()
    e = _engine(lm, slots=2, registry=reg, **LAYOUTS[layout])
    real = e._decode_step_fn()
    mode = {"poison": None}

    def step(*args):
        if mode["poison"] == "trace":
            raise RuntimeError("failed while tracing")
        out = real(*args)
        if mode["poison"] == "run":
            mode["poison"] = None
            raise RuntimeError("device halted")
        return out

    try:
        want = e.submit([1, 2, 3], max_tokens=5).result(timeout=120)
        e._fns["decode"] = step
        rebuilds = reg.get("dl4j_tpu_decode_carry_rebuilds_total").labels(
            e.name)

        mode["poison"] = "trace"
        last = list(e.submit([1, 2, 3], max_tokens=5).events(timeout=60))[-1]
        assert last["reason"] == "failed" and "tracing" in last["error"]
        assert rebuilds.value == 0 and not e._carry_lost()

        mode["poison"] = "run"
        hs = [e.submit([1, 2, 3], max_tokens=5),
              e.submit([4, 5], max_tokens=5)]
        _wait(lambda: all(h.done for h in hs))
        assert [h.reason for h in hs] == ["failed", "failed"]
        assert rebuilds.value == 1 and e.stats()["carry_rebuilds"] == 1
        assert not e._carry_lost()
        if layout == "paged":
            assert e._allocator.free_blocks == e._allocator.total_blocks

        e._breaker.record_success()
        assert e.submit([1, 2, 3], max_tokens=5).result(timeout=120) == want
    finally:
        e.shutdown(drain=False)


class _DeadToken:
    """A first token whose program died on the device: the dispatch
    returned, the fetch raises."""

    def __int__(self):
        raise RuntimeError("prefill halted")


@pytest.mark.parametrize("how", ["install", "prefill", "prefill-at-fetch"])
def test_a_poisoned_admission_fails_every_row_and_rebuilds(lm, how):
    """An admission's program that dies at run time takes every row's cache
    with it, whether it is the install of a handed-over row or a prefill
    that installs its own (ISSUE 37), and whether the failure shows at the
    call or only where the first token is fetched: the request being
    admitted and the rows mid-decode all fail, and the engine serves
    again."""
    from deeplearning4j_tpu.serving.disagg import PrefillEngine

    reg = MetricsRegistry()
    gate = {"delay": 0.05}
    e = _engine(lm, slots=2, registry=reg,
                step_hook=lambda: time.sleep(gate["delay"]))
    key = "write" if how == "install" else ("prefill", 2)
    real = e._write_row_fn() if how == "install" else e._prefill_fn(2)
    mode = {"poison": False}

    def program(*args):
        out = real(*args)
        if mode["poison"]:
            mode["poison"] = False
            if how == "prefill-at-fetch":
                carry, toks, _, counts = out
                return carry, toks, _DeadToken(), counts
            raise RuntimeError("install halted")
        return out

    try:
        want = e.submit([1, 2, 3], max_tokens=4).result(timeout=120)
        handoff = PrefillEngine(lm, max_len=MAX_LEN, registry=reg).prefill(
            [4, 5], max_tokens=4)
        e._fns[key] = program
        first = e.submit([1, 2, 3], max_tokens=MAX_LEN - 4)
        _wait(lambda: len(first.tokens) >= 2)
        mode["poison"] = True
        second = (e.submit_prefilled(handoff) if how == "install"
                  else e.submit([4, 5], max_tokens=4))
        _wait(lambda: first.done and second.done)
        assert first.reason == second.reason == "failed"
        assert e.stats()["carry_rebuilds"] == 1 and not e._carry_lost()
        gate["delay"] = 0.0
        e._breaker.record_success()
        assert e.submit([1, 2, 3], max_tokens=4).result(timeout=120) == want
    finally:
        e.shutdown(drain=False)


def test_a_prefill_that_fails_while_tracing_consumes_nothing(lm):
    """ISSUE 37: the carry is donated when the program runs, not when it is
    traced or compiled: a prefill that fails before that fails its own
    request, and the row mid-decode goes on to the tokens it owes."""
    e = _engine(lm, slots=2, step_hook=lambda: time.sleep(0.02))
    try:
        want = e.submit([1, 2, 3], max_tokens=MAX_LEN - 4).result(timeout=120)

        def program(*args):
            raise RuntimeError("failed while tracing")

        e._fns[("prefill", 2)] = program
        first = e.submit([1, 2, 3], max_tokens=MAX_LEN - 4)
        _wait(lambda: len(first.tokens) >= 2)
        second = e.submit([4, 5], max_tokens=4)
        last = list(second.events(timeout=60))[-1]
        assert last["reason"] == "failed" and "tracing" in last["error"]
        e._breaker.record_success()
        assert first.result(timeout=120) == want
        assert e.stats()["carry_rebuilds"] == 0 and not e._carry_lost()
    finally:
        e.shutdown(drain=False)


def test_paged_and_static_streams_agree_under_donation(lm):
    """(e) a paged engine whose block table is one array under every layer
    steps and installs under donation, rows crossing block boundaries and
    slots reused: its streams are the static engine's."""
    out = {}
    for layout in ("static", "paged"):
        e = _engine(lm, slots=2, **LAYOUTS[layout])
        try:
            hs = [e.submit(p, max_tokens=n)
                  for p, n in zip(PROMPTS, LENGTHS)]
            out[layout] = [h.result(timeout=180) for h in hs]
            s = e.stats()
            assert s["failed"] == 0 and s["carry_rebuilds"] == 0
        finally:
            e.shutdown()
    assert out["static"] == out["paged"]
