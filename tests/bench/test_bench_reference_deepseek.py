"""The DeepSeek-V3 family's plain reference against the program, on the
CPU at the program's reduced shape (query low-rank path, every expert
held, no routed scaling, capacity that drops no token).

The program runs here in float32, as the reference does, so what parts
them is only the order of float32 operations: a few units of float32's
2**-23 relative rounding per sum, over contractions of at most 128 terms
and 4 layers.  Each tolerance below is some hundred times what was read
(logits 2.1e-6 of a largest logit 3.7, gradients 8.6e-7 of a leaf's
norm, the loss equal), and far under what the float8 control gives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as bt
from bench import harness
from bench import model as bm
from bench.reference import deepseek_v3 as ref

#: largest logit difference, as a share of the largest logit
LOGITS_TOL = 1e-4
#: loss difference, as a share of the loss
LOSS_TOL = 1e-5
#: a leaf's gradient difference, as a share of the reference leaf's norm
GRAD_TOL = 1e-4

ref_logits = jax.jit(ref.logits, static_argnums=(2, 3))
ref_loss_grad = jax.jit(jax.value_and_grad(ref.lm_loss), static_argnums=(3, 4))


@pytest.fixture(scope="module", params=[("sigmoid", True), ("softmax", False)],
                ids=["sigmoid_normalised", "softmax_unnormalised"])
def tiny(request):
    from repro.configs import make_model

    conf = harness.load_config(bt.DATA / "deepseek-v3-tiny.json")
    conf["program"]["dtype"] = "float32"
    conf["hf"]["scoring_func"], conf["hf"]["norm_topk_prob"] = request.param
    conf = ref._Frozen(conf)
    cfg = bm.arch_config(conf)
    model = make_model(cfg)
    params = bm.make_params(model, cfg, conf, 2**33 + 11)
    toks = jnp.asarray(np.random.default_rng(11).integers(0, 512, (3, 33)), jnp.int32)
    return conf, cfg, model, params, toks[:, :-1], toks[:, 1:]


@pytest.fixture(scope="module")
def program(tiny):
    from repro.launch.steps import make_loss_fn

    conf, cfg, model, params, x, y = tiny
    (loss, _), grads = jax.jit(jax.value_and_grad(make_loss_fn(model, cfg), has_aux=True))(
        params, {"inputs": x, "labels": y})
    return jax.jit(model.train_logits)(params, x)[0], loss, grads


def gaps(tiny, program, quant=None) -> dict:
    conf, _, _, params, x, y = tiny
    lg_p, loss_p, g_p = program
    lg_r = ref_logits(params, x, conf, quant)
    loss_r, g_r = ref_loss_grad(params, x, y, conf, quant)
    leaf = [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            for a, b in zip(jax.tree_util.tree_leaves(g_p), jax.tree_util.tree_leaves(g_r))]
    return {"logits": float(jnp.max(jnp.abs(lg_p - lg_r)) / jnp.max(jnp.abs(lg_r))),
            "loss": abs(float(loss_p - loss_r)) / float(loss_r), "grad": max(leaf)}


def test_logits_loss_and_gradients_agree_with_the_program(tiny, program):
    got = gaps(tiny, program)
    assert got["logits"] <= LOGITS_TOL and got["loss"] <= LOSS_TOL and got["grad"] <= GRAD_TOL, got


def test_the_float8_control_fails_a_tolerance(tiny, program):
    got = gaps(tiny, program, quant="fp8")
    assert got["logits"] > LOGITS_TOL or got["loss"] > LOSS_TOL or got["grad"] > GRAD_TOL, got


def test_rows_with_the_batchs_expert_shares_give_the_batchs_loss(tiny):
    """The load-balance term is over the whole batch: each row's loss with
    the batch's first-choice shares (``batch_stats``), averaged, gives the
    whole batch's loss and gradient, as the train kind computes them."""
    conf, _, _, params, x, y = tiny
    whole, g_whole = ref_loss_grad(params, x, y, conf, None)
    stats_of = jax.jit(ref.batch_stats, static_argnums=(2,))
    stats = sum(stats_of(params, x[r:r + 1], conf) for r in range(3)) / 3
    rows = [ref_loss_grad(params, x[r:r + 1], y[r:r + 1], conf, None, stats) for r in range(3)]
    assert abs(float(sum(lo for lo, _ in rows) / 3 - whole)) <= LOSS_TOL * float(whole)
    g_rows = jax.tree_util.tree_map(lambda *g: sum(g) / 3, *[g for _, g in rows])
    for a, b in zip(jax.tree_util.tree_leaves(g_rows), jax.tree_util.tree_leaves(g_whole)):
        assert float(jnp.linalg.norm(a - b)) <= GRAD_TOL * float(jnp.linalg.norm(b))
    alone = sum(ref_loss_grad(params, x[r:r + 1], y[r:r + 1], conf, None)[0] for r in range(3)) / 3
    assert abs(float(alone - whole)) > 10 * LOSS_TOL * float(whole)  # rows' own shares differ


def test_the_expert_shares_add_up_to_the_whole_layer(tiny):
    """8 chips holding one expert each: their parts of the routed result,
    with the shared expert counted once, are the uncut layer."""
    conf, _, _, params, x, _ = tiny
    hf = conf["hf"]
    p = jax.tree_util.tree_map(lambda a: a[0], params["periods"]["slot_0"]["ffn"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 32, hf["hidden_size"]), jnp.float32)
    whole, _, _ = ref.moe(p, h, hf, 8)
    gates = ref.route(p, h, hf, 8)[0]
    parts = [ref.held_part({k: p[k][e:e + 1] for k in ("w_gate", "w_up", "w_down")}, h, gates, e)
             for e in range(8)]
    shared = ref.mlp(p["shared"], h)
    total = sum(parts) + shared
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))
    assert float(jnp.max(jnp.abs(sum(parts) + 2 * shared - whole))) > 1e-2 * float(jnp.max(jnp.abs(whole)))


def test_a_null_query_rank_projects_the_query_directly(tiny):
    """With ``q_lora_rank`` null the query is ``x @ wq``: the same as the
    low-rank path through an identity ``wq_a`` and a unit norm, for a
    unit-RMS input (that norm then divides by sqrt(1 + eps) alone)."""
    conf, _, _, params, _, _ = tiny
    d = conf["hf"]["hidden_size"]
    mixer = jax.tree_util.tree_map(lambda a: a[0], params["periods"]["slot_0"]["mixer"])
    wq_b = jax.random.normal(jax.random.PRNGKey(7), (d,) + mixer["wq_b"].shape[1:], jnp.float32) / d ** 0.5
    lora = dict(mixer, wq_a=jnp.eye(d), q_a_norm=jnp.ones(d), wq_b=wq_b)
    direct = {k: v for k, v in mixer.items() if k not in ("wq_a", "q_a_norm", "wq_b")} | {"wq": wq_b}
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 32, d), jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True))
    hf = dict(conf["hf"], q_lora_rank=d)
    want = ref.attention(lora, h, hf)
    got = ref.attention(direct, h, dict(hf, q_lora_rank=None))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))


def test_the_routed_scaling_multiplies_every_gate(tiny):
    conf, _, _, params, _, _ = tiny
    p = jax.tree_util.tree_map(lambda a: a[0], params["periods"]["slot_0"]["ffn"])
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 32, conf["hf"]["hidden_size"]), jnp.float32)
    one = ref.route(p, h, dict(conf["hf"], routed_scaling_factor=1.0), 8)[0]
    scaled = ref.route(p, h, dict(conf["hf"], routed_scaling_factor=2.446), 8)[0]
    assert int(jnp.sum(one > 0)) == 2 * 32 * conf["hf"]["num_experts_per_tok"]
    np.testing.assert_allclose(np.asarray(scaled), 2.446 * np.asarray(one), rtol=1e-6)
