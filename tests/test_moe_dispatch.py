"""Sort-based MoE dispatch (ops/moe_dispatch.py, ISSUE 3 + 18).

Tier-1 contract: ``dispatch_mode="sort"`` (gather/scatter), ``"einsum"``
(legacy dense one-hot) and ``"grouped"`` (sorted grouped expert matmul,
ops.grouped_matmul) implement the SAME GShard routing — identical slot
assignment (first-come-first-served in (round, token) order), identical
capacity drops, matching outputs and gradients across the full
{mode} × {top_k} × {capacity_factor} × {mask} × {dtype} matrix — plus
the routing-observability state and the micro-bench tool smoke.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import (
    Activation, InputType, LossFunction, NeuralNetConfiguration, WeightInit,
)
from deeplearning4j_tpu.nn.layers import MixtureOfExpertsLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork
from deeplearning4j_tpu.ops import (
    gather_dispatch, make_dispatch_plan, scatter_combine, top_k_routing,
)
from deeplearning4j_tpu.train.updaters import Sgd
from deeplearning4j_tpu.utils import check_gradients


def _pair(e=4, d=8, h=16, o=8, k=2, cap=1.5, seed=0, dtype=jnp.float32):
    """(sort layer, einsum layer, shared params)."""
    mk = lambda mode: MixtureOfExpertsLayer(
        n_in=d, n_out=o, num_experts=e, hidden=h, top_k=k,
        capacity_factor=cap, activation=Activation.RELU, dispatch_mode=mode)
    sort, einsum = mk("sort"), mk("einsum")
    params = sort.init(jax.random.PRNGKey(seed), dtype)
    return sort, einsum, params


def _apply(lay, params, x, mask=None):
    return lay.apply(params, lay.init_state(jnp.float32), x,
                     LayerContext(mask=mask))


# ---- plan unit tests ------------------------------------------------------


def test_plan_fcfs_slot_assignment():
    """Deterministic 3-token example: slots are granted per expert in
    (round, token) order and overflow drops exactly the late arrivals."""
    # round-major flat list with capacity 2: expert 0 sees token0(r0),
    # token2(r0), token1(r1) -> token1's round-1 assignment overflows
    expert_idx = jnp.asarray([[0, 1], [1, 0], [0, 1]], jnp.int32)
    plan = make_dispatch_plan(expert_idx, num_experts=2, capacity=2)
    # expert buffers: e0 = [t0, t2], e1 = [t1, t0]
    np.testing.assert_array_equal(np.asarray(plan.slot_token), [0, 2, 1, 0])
    np.testing.assert_array_equal(np.asarray(plan.expert_tokens), [2, 2])
    assert int(plan.dropped_tokens) == 2  # t1->e0 and t2->e1 overflow
    # kept flags, round-major: [t0r0, t1r0, t2r0, t0r1, t1r1, t2r1]
    np.testing.assert_array_equal(
        np.asarray(plan.keep), [True, True, True, True, False, False])


def test_plan_masked_tokens_claim_no_slot():
    expert_idx = jnp.zeros((4, 1), jnp.int32)  # all want expert 0
    mask = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    plan = make_dispatch_plan(expert_idx, num_experts=2, capacity=4,
                              token_mask=mask)
    # masked tokens 1 and 3 appear in no buffer and count nowhere
    np.testing.assert_array_equal(np.asarray(plan.slot_token),
                                  [0, 2, 4, 4, 4, 4, 4, 4])
    np.testing.assert_array_equal(np.asarray(plan.expert_tokens), [2, 0])
    assert int(plan.dropped_tokens) == 0


def test_gather_scatter_roundtrip_identity():
    """With capacity >= tokens and top-1 routing, dispatch->combine of the
    identity expert returns each token times its (renormalized=1) gate."""
    x = jnp.asarray(np.random.RandomState(0).rand(6, 3), jnp.float32)
    gates = jax.nn.softmax(jnp.asarray(
        np.random.RandomState(1).randn(6, 2), jnp.float32))
    gate_vals, idx = top_k_routing(gates, 1)
    plan = make_dispatch_plan(idx, num_experts=2, capacity=6)
    buf = gather_dispatch(x, plan, 2, 6)
    y = scatter_combine(buf, gate_vals, plan)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                               rtol=1e-6, atol=1e-6)


# ---- mode equivalence -----------------------------------------------------


@pytest.mark.parametrize("k,cap", [(1, 100.0), (2, 1.5), (2, 0.3),
                                   (4, 0.26)])
def test_modes_agree_outputs_and_state(k, cap):
    """sort == einsum on outputs, per-expert loads, drops and the aux
    balance term — including under heavy capacity overflow."""
    sort, einsum, params = _pair(k=k, cap=cap)
    x = jnp.asarray(np.random.RandomState(3).rand(12, 8), jnp.float32)
    ys, ss = _apply(sort, params, x)
    ye, se = _apply(einsum, params, x)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ye),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ss["expert_tokens"]),
                                  np.asarray(se["expert_tokens"]))
    assert float(ss["dropped_tokens"]) == float(se["dropped_tokens"])
    np.testing.assert_allclose(float(ss["aux_load_balance"]),
                               float(se["aux_load_balance"]), rtol=1e-5)


def test_modes_agree_gradients():
    sort, einsum, params = _pair(k=2, cap=0.8)
    x = jnp.asarray(np.random.RandomState(4).rand(10, 8), jnp.float32)

    def loss(lay):
        def f(p):
            y, _ = _apply(lay, p, x)
            return jnp.sum(jnp.square(y))
        return jax.grad(f)

    gs, ge = loss(sort)(params), loss(einsum)(params)
    for name in gs:
        np.testing.assert_allclose(np.asarray(gs[name]),
                                   np.asarray(ge[name]),
                                   rtol=1e-4, atol=1e-6, err_msg=name)


def test_modes_agree_recurrent_token_mask():
    """Masked recurrent tokens claim no capacity slot in either mode, and
    padding CONTENT is irrelevant (adversarial values in masked steps)."""
    sort, einsum, params = _pair(k=1, cap=0.5)
    rs = np.random.RandomState(6)
    b, d, t = 2, 8, 6
    x = np.asarray(rs.rand(b, d, t), np.float32)
    mask = np.ones((b, t), np.float32)
    mask[:, t // 2:] = 0.0
    x_adv = x.copy()
    x_adv[:, :, t // 2:] = 50.0  # would win every router argmax unmasked

    ys, ss = _apply(sort, params, jnp.asarray(x), jnp.asarray(mask))
    ys_adv, _ = _apply(sort, params, jnp.asarray(x_adv), jnp.asarray(mask))
    ye, se = _apply(einsum, params, jnp.asarray(x), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ye),
                               rtol=1e-5, atol=1e-6)
    # adversarial padding changes nothing: no slot stolen, no output drift
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ys_adv),
                               rtol=1e-5, atol=1e-6)
    # padding rows produce exactly zero (residual carries them)
    np.testing.assert_allclose(np.asarray(ys)[:, :, t // 2:], 0.0)
    np.testing.assert_array_equal(np.asarray(ss["expert_tokens"]),
                                  np.asarray(se["expert_tokens"]))
    # only real tokens were routed at all
    assert float(np.sum(np.asarray(ss["expert_tokens"]))) \
        + float(ss["dropped_tokens"]) == b * (t // 2)


def test_capacity_overflow_drops_sort_mode():
    """Tight capacity drops most tokens in sort mode exactly as the
    einsum contract: dropped rows get zero output."""
    sort, _, params = _pair(k=1, cap=0.26)  # capacity = 1 per expert
    x = jnp.asarray(np.random.RandomState(3).rand(12, 8), jnp.float32)
    y, state = _apply(sort, params, x)
    zero_rows = int(np.sum(np.all(np.asarray(y) == 0.0, axis=-1)))
    assert zero_rows >= 8  # at most one token per expert survives
    assert float(state["dropped_tokens"]) == 12 - float(
        np.sum(np.asarray(state["expert_tokens"])))
    assert np.asarray(state["expert_tokens"]).max() <= 1


# ---- full mode-equivalence matrix (ISSUE 18) ------------------------------


def _moe(mode, k, cap, dtype, e=4, d=6, h=8, o=6, seed=0):
    lay = MixtureOfExpertsLayer(
        n_in=d, n_out=o, num_experts=e, hidden=h, top_k=k,
        capacity_factor=cap, activation=Activation.RELU,
        dispatch_mode=mode)
    params = lay.init(jax.random.PRNGKey(seed), dtype)
    return lay, params


# Curated slice of the mode × top_k × capacity × mask × dtype cross:
# "grouped" (the bit-identical claim) gets the full k × cap cross in
# f32 plus masked/bf16 spot checks; "einsum" (float-tolerance
# reference) gets one spot check per varied dimension. The full
# 48-case cross costs ~1 min of tier-1 budget for no extra coverage.
_MATRIX = [
    ("grouped", 1, 1.0, False, "float32"),
    ("grouped", 2, 1.0, False, "float32"),
    ("grouped", 4, 1.0, False, "float32"),
    ("grouped", 1, 1.5, False, "float32"),
    ("grouped", 2, 1.5, False, "float32"),
    ("grouped", 4, 1.5, False, "float32"),
    ("grouped", 2, 1.5, True, "float32"),
    ("grouped", 2, 1.0, False, "bfloat16"),
    ("grouped", 4, 1.5, True, "bfloat16"),
    ("einsum", 1, 1.0, False, "float32"),
    ("einsum", 2, 1.5, False, "float32"),
    ("einsum", 4, 1.0, False, "float32"),
    ("einsum", 2, 1.0, True, "float32"),
    ("einsum", 2, 1.5, False, "bfloat16"),
]


@pytest.mark.parametrize(
    "mode,k,cap,masked,dtype", _MATRIX,
    ids=[f"{m}-{k}-{c}-{'masked' if mk else 'flat'}-{d}"
         for m, k, c, mk, d in _MATRIX])
def test_mode_equivalence_matrix(mode, k, cap, masked, dtype):
    """Every non-default dispatch mode matches "sort" on outputs AND
    parameter gradients across top_k × capacity_factor × mask × dtype.
    "grouped" shares the sort plan and combine arithmetic, so its
    outputs must be exact in f32; "einsum" reassociates reductions, so
    it gets float tolerance."""
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    base, params = _moe("sort", k, cap, dt)
    other, _ = _moe(mode, k, cap, dt)
    rs = np.random.RandomState(11)
    if masked:
        b, t = 2, 5
        x = jnp.asarray(rs.rand(b, 6, t), dt)
        mask = jnp.asarray((np.arange(t) < 3)[None, :].repeat(b, 0)
                           .astype(np.float32))
    else:
        x = jnp.asarray(rs.rand(10, 6), dt)
        mask = None

    def run(lay):
        def loss(p):
            y, state = lay.apply(p, lay.init_state(dt), x,
                                 LayerContext(mask=mask))
            return jnp.sum(jnp.square(y.astype(jnp.float32))), (y, state)
        (l, (y, state)), grads = jax.value_and_grad(
            loss, has_aux=True)(params)
        return np.asarray(y, np.float32), state, grads

    ys, ss, gs = run(base)
    yo, so, go = run(other)
    scale = max(float(np.abs(ys).max()), 1e-6)
    if mode == "grouped" and dtype == "float32":
        out_tol = dict(rtol=0, atol=1e-6 * scale)
    elif dtype == "float32":
        out_tol = dict(rtol=1e-5, atol=1e-6)
    else:  # bf16: accumulation order differs between spellings
        out_tol = dict(rtol=0, atol=3e-2 * scale)
    np.testing.assert_allclose(yo, ys, err_msg="outputs", **out_tol)
    np.testing.assert_array_equal(np.asarray(ss["expert_tokens"]),
                                  np.asarray(so["expert_tokens"]))
    assert float(ss["dropped_tokens"]) == float(so["dropped_tokens"])
    assert float(ss["capacity_slots"]) == float(so["capacity_slots"]) > 0
    # tolerance scaled by the GLOBAL gradient magnitude: with k=1 the
    # renormalized gate makes the true router gradient exactly zero and
    # both spellings produce only roundoff noise there — a per-param
    # scale would compare noise against noise
    gscale = max(max(np.abs(np.asarray(g, np.float32)).max()
                     for g in gs.values()), 1e-6)
    gtol = 1e-5 if dtype == "float32" else 6e-2
    for name in gs:
        a = np.asarray(gs[name], np.float32)
        b = np.asarray(go[name], np.float32)
        np.testing.assert_allclose(b, a, rtol=0, atol=gtol * gscale,
                                   err_msg=f"grad {name}")


# ---- gradcheck (float64, reference GradCheckUtil harness) -----------------


def test_gradcheck_sort_dispatch():
    conf = (NeuralNetConfiguration.builder().seed(7).data_type("float64")
            .updater(Sgd(0.1)).weight_init(WeightInit.XAVIER).list()
            .layer(MixtureOfExpertsLayer(n_out=6, num_experts=3, hidden=8,
                                         top_k=2, capacity_factor=4.0,
                                         activation=Activation.TANH,
                                         dispatch_mode="sort"))
            .layer(OutputLayer(n_out=2, loss=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(5)).build())
    model = MultiLayerNetwork(conf).init()
    rs = np.random.default_rng(8)
    x = rs.normal(size=(6, 5))
    y = np.eye(2)[np.arange(6) % 2]
    assert check_gradients(model, x, y, subset=60, print_results=True)


def test_gradcheck_modes_agree_with_balance_loss():
    """Analytic grads of the full score (incl. aux balance loss) match
    between modes in float64."""
    def build(mode):
        conf = (NeuralNetConfiguration.builder().seed(9)
                .data_type("float64").updater(Sgd(0.1))
                .weight_init(WeightInit.XAVIER).list()
                .layer(MixtureOfExpertsLayer(
                    n_out=6, num_experts=3, hidden=8, top_k=2,
                    capacity_factor=1.0, balance_loss_weight=0.5,
                    activation=Activation.TANH, dispatch_mode=mode))
                .layer(OutputLayer(n_out=2, loss=LossFunction.MCXENT,
                                   activation=Activation.SOFTMAX))
                .set_input_type(InputType.feed_forward(5)).build())
        return MultiLayerNetwork(conf).init()

    rs = np.random.default_rng(10)
    x = rs.normal(size=(9, 5))
    y = np.eye(2)[np.arange(9) % 2]
    ms = build("sort")
    gs = ms.calculate_gradients(x, y)
    flat_s = jax.tree_util.tree_leaves(gs)
    for mode in ("einsum", "grouped"):
        mo = build(mode)
        mo.params = jax.tree_util.tree_map(lambda a: a, ms.params)
        go = mo.calculate_gradients(x, y)
        for a, b in zip(flat_s, jax.tree_util.tree_leaves(go)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-8, atol=1e-10,
                                       err_msg=mode)


# ---- observability --------------------------------------------------------


def test_record_moe_metrics_counters():
    from deeplearning4j_tpu.obs import MetricsRegistry, record_moe_metrics

    sort, _, params = _pair(k=2, cap=0.5)
    x = jnp.asarray(np.random.RandomState(5).rand(12, 8), jnp.float32)
    _, state = _apply(sort, params, x)

    reg = MetricsRegistry()
    seen = record_moe_metrics({"layer_0": state}, reg)
    assert seen == 1
    tok = reg.get("dl4j_tpu_moe_expert_tokens_total")
    drop = reg.get("dl4j_tpu_moe_dropped_tokens_total")
    per_expert = np.asarray(state["expert_tokens"])
    for e_idx, expect in enumerate(per_expert.tolist()):
        assert tok.labels("layer_0", str(e_idx)).value == expect
    assert drop.labels("layer_0").value == float(state["dropped_tokens"])
    # counters are cumulative across steps
    record_moe_metrics({"layer_0": state}, reg)
    assert tok.labels("layer_0", "0").value == 2 * per_expert[0]
    # conservation: kept + dropped == top_k * tokens
    assert float(per_expert.sum()) + float(state["dropped_tokens"]) == 24


def test_moe_metrics_listener_end_to_end():
    from deeplearning4j_tpu.obs import MetricsRegistry, MoEMetricsListener

    conf = (NeuralNetConfiguration.builder().seed(5).updater(Sgd(0.3))
            .weight_init(WeightInit.XAVIER).list()
            .layer(MixtureOfExpertsLayer(n_out=8, num_experts=4, hidden=16,
                                         top_k=2, capacity_factor=2.0))
            .layer(OutputLayer(n_out=3, loss=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(8)).build())
    net = MultiLayerNetwork(conf).init()
    reg = MetricsRegistry()
    net.set_listeners(MoEMetricsListener(reg))
    rs = np.random.RandomState(0)
    x = rs.rand(16, 8).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 16)]
    net.fit(x, y, epochs=2)
    tok = reg.get("dl4j_tpu_moe_expert_tokens_total")
    total = sum(child.value for _, child in tok.items())
    drop = reg.get("dl4j_tpu_moe_dropped_tokens_total")
    dropped = sum(child.value for _, child in drop.items())
    # 2 iterations (one full batch per epoch) x 16 tokens x top_k=2
    # assignments, kept + dropped
    assert total + dropped == 2 * 16 * 2


# ---- serialization + tooling ---------------------------------------------


def test_dispatch_mode_json_roundtrip():
    from deeplearning4j_tpu.core.config import from_json, to_json

    lay = MixtureOfExpertsLayer(n_in=8, n_out=4, num_experts=4,
                                dispatch_mode="einsum")
    back = from_json(to_json(lay))
    assert back.dispatch_mode == "einsum"
    assert from_json(to_json(MixtureOfExpertsLayer(
        n_in=8, n_out=4))).dispatch_mode == "sort"
    with pytest.raises(ValueError):
        MixtureOfExpertsLayer(n_in=8, n_out=4, dispatch_mode="scatter")


def test_bench_tool_smoke(capsys):
    """tools/bench_moe_dispatch.py runs on tiny shapes and reports the
    modes numerically agreeing."""
    import json as _json
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import bench_moe_dispatch

    rc = bench_moe_dispatch.main(["--tokens", "64", "--d", "8",
                                  "--hidden", "16", "--iters", "1"])
    row = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert row["modes_agree"]
    assert row["sort_grad_step_ms"] > 0
    assert row["einsum_grad_step_ms"] > 0
    assert row["grouped_grad_step_ms"] > 0
    assert row["grouped_max_abs_output_diff"] == 0.0


# ---------------------------------------------------------------------------
# routing of a served share (ISSUE 34)
# ---------------------------------------------------------------------------
def test_biased_routing_chooses_by_the_sum_and_weighs_by_the_score():
    from deeplearning4j_tpu.ops import biased_top_k_routing

    scores = jnp.asarray([[0.5, 0.3, 0.15, 0.05]])
    bias = jnp.asarray([0.0, -0.2, 0.0, 0.2])
    vals, idx = biased_top_k_routing(scores, bias, 2, scale=6.0)
    assert idx.tolist() == [[0, 3]]  # 0.5, then 0.05 + 0.2 over 0.15
    np.testing.assert_allclose(np.asarray(vals), [[3.0, 0.3]], rtol=1e-6)
    plain, pidx = biased_top_k_routing(scores, 0 * bias, 2)
    assert pidx.tolist() == [[0, 1]] and plain.tolist() == [[0.5, 0.3]]


def test_held_expert_choices_sort_choices_by_where_they_went():
    """Experts 4..7 of 16 routed are held; outputs 16.. are zero-compute."""
    from deeplearning4j_tpu.ops import held_expert_choices

    idx = jnp.asarray([[4, 7, 0, 16], [5, 5, 23, 15], [3, 8, 9, 10]])
    local, counts = held_expert_choices(idx, first=4, held=4, routed=16)
    assert local.tolist() == [[0, 3, 4, 4], [1, 1, 4, 4], [4, 4, 4, 4]]
    #                     held 4  5  6  7  absent zero
    assert counts.tolist() == [[1, 0, 0, 1, 1, 1],
                               [0, 2, 0, 0, 1, 1],
                               [0, 0, 0, 0, 4, 0]]
    # a choice that went elsewhere claims no slot in the dispatch plan
    plan = make_dispatch_plan(local, 4, 2)
    assert plan.expert_tokens.tolist() == [1, 2, 0, 1]
    assert int(plan.dropped_tokens) == 0
