"""The second reference workload (``tpu_resiliency/models/kimi_linear.py``)
against its plain reference (``kimi_linear_reference.py``): seeded random
weights, tiny sizes, CPU, the program in float32 against the float32
reference.  Each block forward and gradients, the whole model's loss and every
leaf's gradient, three train steps with the router's bias and load, the share
test (the shares of a layer add up to the uncut layer), no token dropped, one
compilation over batches of different routing, the benchmark's copy of the
reference, and the cell's counts from shapes.
"""

import dataclasses
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_CONFIG = os.path.join(ROOT, "chipbench", "configs", "kimi-linear-48b-a3b-1chip.json")

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_resiliency.models import kimi_linear as kl  # noqa: E402
from tpu_resiliency.models import kimi_linear_reference as ref  # noqa: E402

CFG = kl.KimiLinearConfig(
    hidden_size=32, intermediate_size=64, moe_intermediate_size=16, num_experts=16,
    experts_held=4, expert_offset=4, num_experts_per_token=2, heads_held=2,
    kda_head_dim=8, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, vocab_rows=64, kda_chunk=8, dtype=jnp.float32)
DIMS = ref.Dims(heads=2, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                experts_per_token=2, expert_offset=4)
ROWS, SEQ = 2, 20  # not a multiple of the chunk


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(float(np.max(np.abs(b))), 1e-12)
    assert a.shape == b.shape
    assert float(np.max(np.abs(a - b))) <= tol * scale, (
        float(np.max(np.abs(a - b))), scale)


def leaf_names(tree):
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.fixture(scope="module")
def params():
    return kl.init_params(CFG, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(2), (ROWS, SEQ), 0, CFG.vocab_rows)
    return tokens, jnp.roll(tokens, -1, axis=-1)


@pytest.fixture(scope="module")
def hidden():
    return jax.random.normal(jax.random.PRNGKey(3), (ROWS, SEQ, CFG.hidden_size))


@pytest.fixture(scope="module")
def bias():
    return 0.01 * jax.random.normal(jax.random.PRNGKey(4),
                                    (CFG.n_expert_layers, CFG.num_experts))


# -- each block against the reference, forward and gradients -----------------------

def delta_rule_inputs(seq, key=5):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    shape = (ROWS, seq, 2, 8)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)  # noqa: E731
    q, k = unit(jax.random.normal(ks[0], shape)), unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    g = -2.0 * jax.random.uniform(ks[3], shape)       # decays down to exp(-2) a token
    beta = jax.random.uniform(ks[4], shape[:3])
    return q, k, v, g, beta


def delta_rule_token_by_token(q, k, v, g, beta):
    """The recurrence as the reference's ``kda`` scans it."""
    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("rhk,rhkv->rhv", k_t, state)
        state = state + (b_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("rhk,rhkv->rhv", q_t, state)

    start = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]))
    _, o = jax.lax.scan(token, start, tuple(jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


@pytest.mark.parametrize("seq", [8, 24, 20, 3], ids=lambda s: f"T{s}")
def test_kda_chunked_is_the_token_by_token_recurrence(seq):
    """Sequence lengths that are and are not multiples of the chunk (8)."""
    inputs = delta_rule_inputs(seq)
    close(kl.kda_chunked(*inputs, chunk=8), delta_rule_token_by_token(*inputs))
    weigh = jax.random.normal(jax.random.PRNGKey(6), inputs[2].shape)
    grads = jax.grad(lambda *a: jnp.sum(kl.kda_chunked(*a, chunk=8) * weigh),
                     argnums=(0, 1, 2, 3, 4))(*inputs)
    wanted = jax.grad(lambda *a: jnp.sum(delta_rule_token_by_token(*a) * weigh),
                      argnums=(0, 1, 2, 3, 4))(*inputs)
    for got, want in zip(grads, wanted):
        close(got, want)


def test_kda_chunked_stays_finite_under_decays_no_quotient_could_hold():
    """64 tokens of log-decay -8 a token: exp(+512) in any one-sided factor."""
    q, k, v, g, beta = delta_rule_inputs(64)
    g = jnp.full_like(g, -8.0)
    out = kl.kda_chunked(q, k, v, g, beta, chunk=64)
    assert bool(jnp.all(jnp.isfinite(out)))
    close(out, delta_rule_token_by_token(q, k, v, g, beta))


BLOCKS = {
    "kda": (lambda x, p: kl.kda_block(x, p, CFG), lambda x, p: ref.kda(x, p, DIMS), 0),
    "mla": (lambda x, p: kl.mla_block(x, p, CFG), lambda x, p: ref.mla(x, p, DIMS), 3),
    "ffn": (lambda x, p: kl._swiglu(x, p), lambda x, p: ref.swiglu(x, p), 0),
}


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_block_forward_and_gradients_match_the_reference(name, params, hidden):
    program, reference, layer = BLOCKS[name]
    p = params["layers"][layer][name]
    close(program(hidden, p), reference(hidden, p))
    weigh = jax.random.normal(jax.random.PRNGKey(7), hidden.shape)
    got = jax.grad(lambda x, p: jnp.sum(program(x, p) * weigh), argnums=(0, 1))(hidden, p)
    want = jax.grad(lambda x, p: jnp.sum(reference(x, p) * weigh), argnums=(0, 1))(hidden, p)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        close(g, w)


def test_expert_layer_forward_load_and_gradients_match_the_reference(params, hidden, bias):
    p, x = params["layers"][1]["moe"], hidden.reshape(ROWS * SEQ, -1)
    out, load = kl.moe_block(x, p, bias[0], CFG)
    want, want_load = ref.moe(x, p, bias[0], DIMS)
    close(out, want)
    assert np.array_equal(load, want_load)
    assert int(load.sum()) == ROWS * SEQ * CFG.num_experts_per_token
    weigh = jax.random.normal(jax.random.PRNGKey(8), x.shape)
    got = jax.grad(lambda x, p: jnp.sum(kl.moe_block(x, p, bias[0], CFG)[0] * weigh),
                   argnums=(0, 1))(x, p)
    want = jax.grad(lambda x, p: jnp.sum(ref.moe(x, p, bias[0], DIMS)[0] * weigh),
                    argnums=(0, 1))(x, p)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        close(g, w)


# -- the whole model: loss and every leaf's gradient -------------------------------

@pytest.fixture(scope="module")
def model_grads(params, batch, bias):
    got = jax.value_and_grad(lambda p: kl.loss_fn(p, batch, CFG, bias), has_aux=True)(params)
    want = jax.value_and_grad(
        lambda p: ref.loss_of(p, *batch, bias, DIMS), has_aux=True)(params)
    return got, want


def test_model_loss_and_load_match_the_reference(model_grads):
    ((loss, load), _), ((want, want_load), _) = model_grads
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    assert load.shape == (CFG.n_expert_layers, CFG.num_experts)
    assert np.array_equal(load, want_load)


N_LEAVES = 113  # 16 a KDA block, 5 an MLA block, 2 norms, 3 dense / 7 expert-layer, + 3


@pytest.mark.parametrize("leaf", range(N_LEAVES))
def test_every_leafs_gradient_matches_the_reference(leaf, model_grads, params):
    (_, grads), (_, wanted) = model_grads
    names = leaf_names(params)
    assert len(names) == N_LEAVES
    got = jax.tree_util.tree_leaves(grads)[leaf]
    want = jax.tree_util.tree_leaves(wanted)[leaf]
    assert float(jnp.max(jnp.abs(want))) > 0, names[leaf]
    close(got, want, tol=5e-4)


# -- three steps of the train step against the reference's AdamW -------------------

def test_three_train_steps_follow_the_reference_with_bias_and_load(params):
    feed = []
    for i in range(3):
        tokens = jax.random.randint(jax.random.PRNGKey(20 + i), (ROWS, SEQ), 0, CFG.vocab_rows)
        feed.append((tokens, jnp.roll(tokens, -1, axis=-1)))
    start = jax.tree_util.tree_map(lambda p: jnp.array(p, copy=True), params)
    live = jax.tree_util.tree_map(lambda p: jnp.array(p, copy=True), params)
    opt = kl.init_opt_state(live, CFG)
    assert jax.tree_util.tree_leaves(opt["master"]) == []  # float32 leaves need none
    step = kl.make_train_step(CFG)
    losses, loads = [], []
    for b in feed:
        live, opt, loss = step(live, opt, b)
        losses.append(float(loss))
        loads.append(np.asarray(opt["router_load"]).tolist())
    assert step._cache_size() == 1  # one compilation over batches of different routing
    assert loads[0] != loads[1]
    want = ref.first_steps(start, feed, DIMS, n_steps=3)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-4)
    assert loads == want["router_load"]
    assert np.array_equal(np.asarray(opt["router_bias"], np.float64), want["router_bias"])
    assert int(opt["count"]) == 3
    change = [float(jnp.linalg.norm(a - b)) for a, b in zip(
        jax.tree_util.tree_leaves(live), jax.tree_util.tree_leaves(start))]
    np.testing.assert_allclose(change, want["change_norm"], rtol=0.02)
    mu = [float(jnp.linalg.norm(m)) for m in jax.tree_util.tree_leaves(opt["mu"])]
    assert all(m > 0 for m in mu)


def test_a_bfloat16_tree_has_float32_only_leaves_and_buffers_no_gradient_touches():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = kl.init_params(cfg, jax.random.PRNGKey(1))
    opt = kl.init_opt_state(params, cfg)
    names = leaf_names(params)
    f32 = [n for n, p in zip(names, jax.tree_util.tree_leaves(params)) if p.dtype == jnp.float32]
    assert len(f32) == 8 and all(n.endswith("['A_log']") or n.endswith("['dt_bias']") for n in f32)
    assert len(jax.tree_util.tree_leaves(opt["master"])) == N_LEAVES - 8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (ROWS, SEQ), 0, cfg.vocab_rows)
    before = jax.tree_util.tree_structure((params, opt))
    params, opt, loss = kl.make_train_step(cfg)(params, opt, (tokens, jnp.roll(tokens, -1, -1)))
    assert jax.tree_util.tree_structure((params, opt)) == before
    assert np.isfinite(float(loss))
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves((params, opt))} == {
        "bfloat16", "float32", "int32"}
    assert opt["router_load"].dtype == jnp.int32 and opt["router_bias"].dtype == jnp.float32
    assert float(jnp.max(jnp.abs(opt["router_bias"]))) == pytest.approx(1e-3)


# -- the share test: the shares of a layer add up to the uncut layer ---------------

def columns(w, heads, width, held):
    """The columns of ``w`` [.., all heads x width] that ``held`` heads own."""
    return w.reshape(*w.shape[:-1], heads, width)[..., held, :].reshape(*w.shape[:-1], -1)


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer(hidden):
    """Four chips with four of sixteen experts each; the shared expert, which
    every chip computes alike, counted once."""
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = kl.init_params(whole, jax.random.PRNGKey(9))["layers"][1]["moe"]
    x = hidden.reshape(ROWS * SEQ, -1)
    b = 0.01 * jax.random.normal(jax.random.PRNGKey(10), (16,))
    uncut, _ = ref.moe(x, p, b, dataclasses.replace(DIMS, expert_offset=0))
    shared = ref.swiglu(x, p["shared"])
    total, loads = shared, []
    for chip in range(4):
        share = dataclasses.replace(CFG, experts_held=4, expert_offset=4 * chip)
        held = {**p, "experts": {k: w[4 * chip:4 * chip + 4] for k, w in p["experts"].items()}}
        out, load = kl.moe_block(x, held, b, share)
        total = total + (out - shared)
        loads.append(np.asarray(load))
    close(total, uncut)
    assert all(np.array_equal(loads[0], load) for load in loads)  # every chip routes alike


def test_shares_of_the_kda_heads_add_up_to_the_uncut_block(hidden):
    whole = dataclasses.replace(CFG, heads_held=4)
    p = kl.init_params(whole, jax.random.PRNGKey(11))["layers"][0]["kda"]
    uncut = ref.kda(hidden, p, dataclasses.replace(DIMS, heads=4))
    by_head = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "wf2", "dt_bias", "wg2", "bg")
    total = 0.0
    for held in ([0, 1], [2, 3]):
        share = {**p, **{k: columns(p[k], 4, 8, held) for k in by_head}}
        share["A_log"] = p["A_log"][jnp.array(held)]
        share["wb"] = p["wb"][:, jnp.array(held)]
        share["wo"] = p["wo"].reshape(4, 8, -1)[jnp.array(held)].reshape(16, -1)
        total = total + kl.kda_block(hidden, share, CFG)
    close(total, uncut)


def test_shares_of_the_mla_heads_add_up_to_the_uncut_block(hidden):
    whole = dataclasses.replace(CFG, heads_held=4)
    p = kl.init_params(whole, jax.random.PRNGKey(12))["layers"][3]["mla"]
    uncut = ref.mla(hidden, p, dataclasses.replace(DIMS, heads=4))
    total = 0.0
    for held in ([0, 1], [2, 3]):
        share = {**p, "wq": columns(p["wq"], 4, 12, held),
                 "wkvb": columns(p["wkvb"], 4, 16, held),
                 "wo": p["wo"].reshape(4, 8, -1)[jnp.array(held)].reshape(16, -1)}
        total = total + kl.mla_block(hidden, share, CFG)
    close(total, uncut)


def test_slices_of_the_vocabulary_concatenate_to_the_uncut_logits(params, batch, bias):
    uncut, _ = ref.logits_of(params, batch[0], bias, DIMS)
    slices = [kl.forward({**params, "head": params["head"][:, lo:lo + 16]}, batch[0], CFG,
                         bias)[0] for lo in range(0, CFG.vocab_rows, 16)]
    close(jnp.concatenate(slices, axis=-1), uncut)


# -- no token dropped -----------------------------------------------------------------

@pytest.mark.parametrize("favoured, all_held", [((4, 7), True), ((0, 15), False)],
                         ids=["every-token-routes-here", "no-token-routes-here"])
def test_no_token_is_dropped_at_either_end_of_the_load(favoured, all_held, params, hidden):
    p, x = params["layers"][2]["moe"], hidden.reshape(ROWS * SEQ, -1)
    b = jnp.zeros((CFG.num_experts,)).at[jnp.array(favoured)].set(10.0)
    chosen, weights, load = kl.route(x, p["router"], b, CFG)
    assert int(load[jnp.array(favoured)].sum()) == 2 * ROWS * SEQ  # every choice of every token
    mine = kl.held_experts(x, chosen, weights, p["experts"], CFG)
    want, _ = ref.routed(x, p, b, DIMS)
    if all_held:
        close(mine, want)
        assert float(jnp.min(jnp.max(jnp.abs(mine), axis=-1))) > 0  # every token got its part
    else:
        assert float(jnp.max(jnp.abs(mine))) == 0.0 and float(jnp.max(jnp.abs(want))) == 0.0
    grad = jax.grad(lambda x: jnp.sum(kl.held_experts(x, chosen, weights, p["experts"], CFG)))(x)
    assert bool(jnp.all(jnp.isfinite(grad)))


# -- spans and counters -------------------------------------------------------------------

def test_the_lowered_step_names_its_blocks(params, batch):
    opt = kl.init_opt_state(params, CFG)
    text = kl.make_train_step(CFG).lower(params, opt, batch).as_text(debug_info=True)
    for scope in ("kda", "mla", "moe.route", "moe.experts", "moe.shared", "ffn.dense",
                  "head.loss"):
        assert f"jit(step)/jvp({scope})/" in text, scope
    assert "module @jit_step" in text  # the trace readers find ``jit_step``


def test_routing_stats_reads_the_state_and_sets_the_gauges(params):
    from tpu_resiliency.telemetry import get_registry

    load = np.zeros((CFG.n_expert_layers, CFG.num_experts), np.int32)
    load[:, 4:8] = [[1, 2, 3, 10]] * CFG.n_expert_layers
    load[:, 0] = 16
    stats = kl.routing_stats({"router_load": load}, CFG)
    assert stats == {"max": 10.0, "mean": 4.0, "share": 0.5}
    snapshot = get_registry().snapshot()
    assert snapshot["tpurx_model_expert_load_max"]["samples"][0]["value"] == 10.0
    assert snapshot["tpurx_model_expert_load_mean"]["samples"][0]["value"] == 4.0


# -- the benchmark's copy, and the cell's counts --------------------------------------------

def test_the_benchmarks_reference_is_this_repositorys_byte_for_byte():
    with open(os.path.join(ROOT, "tpu_resiliency/models/kimi_linear_reference.py"), "rb") as f:
        ours = f.read()
    with open(os.path.join(ROOT, "chipbench/reference/kimi_linear.py"), "rb") as f:
        assert f.read() == ours


def test_the_benchmarks_reference_gives_equal_numbers(params, batch, bias):
    import sys

    sys.path.insert(0, ROOT)
    from chipbench.reference import kimi_linear as copy

    ours = ref.loss_of(params, *batch, bias, DIMS)
    theirs = copy.loss_of(params, *batch, bias, copy.Dims(**dataclasses.asdict(DIMS)))
    assert float(ours[0]) == float(theirs[0]) and np.array_equal(ours[1], theirs[1])


def test_the_cells_counts_from_shapes_nothing_allocated():
    import sys

    sys.path.insert(0, ROOT)
    from chipbench import families, weights

    family, sizes = families.of_file(CELL_CONFIG)
    assert sizes.n_params == 441_886_480 and sizes.tokens_per_step == 4096
    assert sizes.state_bytes == 6_186_414_788
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(weights.make_state_fn(family, sizes), key)
    leaves = jax.tree_util.tree_leaves(state)
    assert sum(x.size * x.dtype.itemsize for x in leaves) == sizes.state_bytes
    assert len(leaves) == 447
    draw = jax.eval_shape(lambda k: family.draw_params(sizes, k, jnp.bfloat16), key)
    assert sum(x.size for x in jax.tree_util.tree_leaves(draw)) == sizes.n_params
    assert family.train_flops_per_token(sizes) * sizes.tokens_per_step == pytest.approx(
        4.39e12, rel=0.01)
