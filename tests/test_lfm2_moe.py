"""The fifth reference workload (``tpu_resiliency/models/lfm2_moe.py``) against
its plain reference (``lfm2_moe_reference.py``): seeded random weights, tiny
sizes, CPU, the program in float32 against the float32 reference.  The
convolution against a sum over its taps written out in numpy and against
causality, the router's bias (it picks, it does not weigh), each block
forward and gradients, the whole model's loss and every leaf's gradient, the
tied leaf's two gradient paths, three train steps with the buffers, the share
test, the state through ``async_save`` / ``load_checkpoint`` and the sealed
ring slot, a recovery under ``Wrapper`` that continues the no-fault losses bit
for bit, the benchmark's copy of the reference, and the cell's counts from
shapes.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_CONFIG = os.path.join(ROOT, "chipbench", "configs", "lfm2-8b-a1b-1chip.json")

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_resiliency.models import keye_vl2, kimi_linear, qwen3_next  # noqa: E402
from tpu_resiliency.models import lfm2_moe as lm  # noqa: E402
from tpu_resiliency.models import lfm2_moe_reference as ref  # noqa: E402

# the cell's five layers (conv + dense, then attention, conv, conv, conv with
# experts); 8 query heads on 2 key/value heads (query head j reads head j //
# 4); 4 chips of 4 experts each, this one the second; blocks of 8 queries
CFG = lm.Lfm2MoeConfig(
    hidden_size=32, num_attention_heads=8, num_key_value_heads=2, head_dim=4,
    intermediate_size=48, moe_intermediate_size=16, num_experts=16, experts_held=4,
    expert_offset=4, num_experts_per_token=3, vocab_rows=64, attn_block=8, dtype=jnp.float32)
DIMS = ref.Dims(experts_per_token=3, expert_offset=4, query_block=8)
ROWS, SEQ = 2, 20  # no multiple of the block of queries
N_LEAVES = 49  # 8 (conv, dense) + 12 (attention, experts) + 3 x 9 (conv, experts) + 2
BUFFERS = ("router_bias", "router_load")


@pytest.fixture(scope="module", autouse=True)
def quick_compilation():
    """Some forty small programs are compiled here and none is timed."""
    before = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", True)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


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
def draw():
    return jax.jit(lambda key: lm.init_params(CFG, key))  # compiled once for the file


@pytest.fixture(scope="module")
def params(draw):
    """The draw with every norm's scale moved off 1, where a missing scale
    would not show."""
    @jax.jit
    def moved(drawn, key):
        keys = iter(jax.random.split(key, 100))
        return jax.tree_util.tree_map(
            lambda p: p + 0.1 * jax.random.normal(next(keys), p.shape) if p.ndim == 1 else p,
            drawn)

    return moved(draw(jax.random.PRNGKey(1)), jax.random.PRNGKey(13))


@pytest.fixture(scope="module")
def bias():
    """A router bias large enough to change which experts are chosen."""
    return 0.3 * jax.random.normal(jax.random.PRNGKey(6), (CFG.n_expert_layers, CFG.num_experts))


@pytest.fixture(scope="module")
def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(2), (ROWS, SEQ), 0, CFG.vocab_rows)
    return tokens, jnp.roll(tokens, -1, axis=-1)


@pytest.fixture(scope="module")
def hidden():
    return jax.random.normal(jax.random.PRNGKey(3), (ROWS, SEQ, CFG.hidden_size))


# -- the convolution -------------------------------------------------------------------

CONVS = {"program": lm.causal_conv, "reference": ref.short_conv}
GATED = {"program": lm.conv_block, "reference": ref.gated_conv}


@pytest.mark.parametrize("side", sorted(CONVS))
def test_the_convolution_is_a_sum_over_three_taps_with_zeros_before_the_start(side, hidden):
    w = jax.random.normal(jax.random.PRNGKey(4), (3, CFG.hidden_size))
    got = np.asarray(CONVS[side](hidden, w))
    z, taps = np.asarray(hidden, np.float64), np.asarray(w, np.float64)
    want = np.zeros_like(z)
    for t in range(SEQ):
        for j in range(3):
            if t - 2 + j >= 0:  # tap 2 meets the current token
                want[:, t] += taps[j] * z[:, t - 2 + j]
    close(got, want, tol=1e-6)


@pytest.mark.parametrize("side", sorted(CONVS))
def test_position_t_does_not_move_when_t_plus_1_does(side, params, hidden):
    p = params["layers"][0]["conv"]
    moved = hidden.at[:, 11].add(1.0)
    assert np.array_equal(CONVS[side](hidden, p["conv"])[:, :11],
                          CONVS[side](moved, p["conv"])[:, :11])
    before, after = GATED[side](hidden, p), GATED[side](moved, p)
    assert np.array_equal(before[:, :11], after[:, :11])
    # ... and positions 11, 12, 13 do (three taps), and none after them
    changed = np.flatnonzero(np.max(np.abs(np.asarray(before - after)), axis=(0, 2)))
    assert changed.tolist() == [11, 12, 13]


def test_the_gated_convolution_written_out(params, hidden):
    """``W_out (C * conv(B * x))`` with the chunks in the order B, C, x, and no
    activation anywhere."""
    p = jax.tree_util.tree_map(lambda z: np.asarray(z, np.float64), params["layers"][2]["conv"])
    u, d = np.asarray(hidden, np.float64), CFG.hidden_size
    bcx = u @ p["in_proj"]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    bx = np.pad(b * x, ((0, 0), (2, 0), (0, 0)))
    conv = sum(p["conv"][j] * bx[:, j:j + SEQ] for j in range(3))
    want = (c * conv) @ p["out_proj"]
    for side in sorted(GATED):
        close(GATED[side](hidden, params["layers"][2]["conv"]), want, tol=1e-5)


# -- the router: the bias picks, the scores weigh ------------------------------------------

ROUTES = {
    "program": lambda x, router, b: kimi_linear.route(x, router, b, CFG, eps=lm.ROUTE_EPS),
    "reference": lambda x, router, b: ref.route(x, router, b, DIMS),
}


@pytest.mark.parametrize("side", sorted(ROUTES))
def test_the_router_picks_by_score_plus_bias_and_weighs_by_the_score(side, params, hidden):
    router = params["layers"][1]["moe"]["router"]
    x = hidden.reshape(ROWS * SEQ, -1)
    scores = np.asarray(jax.nn.sigmoid(x @ router), np.float64)
    none = jnp.zeros((CFG.num_experts,), jnp.float32)
    chosen, weights, load = ROUTES[side](x, router, none)
    order = np.argsort(-scores[0])
    assert sorted(np.asarray(chosen[0]).tolist()) == sorted(order[:3].tolist())
    assert int(load.sum()) == ROWS * SEQ * 3
    # a bias that puts token 0's fourth expert over its third: the choice flips ...
    third, fourth = order[2], order[3]
    lift = none.at[fourth].set(float(scores[0, third] - scores[0, fourth]) + 1e-3)
    flipped, reweighed, _ = ROUTES[side](x, router, lift)
    assert sorted(np.asarray(flipped[0]).tolist()) == sorted([order[0], order[1], fourth])
    # ... the weights are the UNBIASED scores over their sum + 1e-6 ...
    picked = np.take_along_axis(scores, np.asarray(flipped), axis=-1)
    close(reweighed, picked / (picked.sum(-1, keepdims=True) + 1e-6), tol=1e-5)
    assert float(np.max(np.sum(np.asarray(reweighed), -1))) < 1.0  # the 1e-6 is there
    # ... and the two that stayed keep their ratio
    before = dict(zip(np.asarray(chosen[0]).tolist(), np.asarray(weights[0]).tolist()))
    after = dict(zip(np.asarray(flipped[0]).tolist(), np.asarray(reweighed[0]).tolist()))
    assert after[order[0]] / after[order[1]] == pytest.approx(
        before[order[0]] / before[order[1]], rel=1e-5)
    assert after[fourth] / after[order[0]] == pytest.approx(
        scores[0, fourth] / scores[0, order[0]], rel=1e-5)


def test_the_third_configurations_router_is_untouched_by_the_new_keyword(params, hidden):
    """``eps`` defaults to what ``kimi_linear`` computes: the same weights, bit
    for bit, and they sum to the scaling factor."""
    router = params["layers"][1]["moe"]["router"]
    x, none = hidden.reshape(ROWS * SEQ, -1), jnp.zeros((CFG.num_experts,), jnp.float32)
    cfg = dataclasses.replace(CFG, routed_scaling_factor=2.446)
    _, weights, _ = kimi_linear.route(x, router, none, cfg)
    _, again, _ = kimi_linear.route(x, router, none, cfg, eps=0.0)
    assert np.array_equal(weights, again)
    close(jnp.sum(weights, -1), jnp.full((ROWS * SEQ,), 2.446), tol=1e-6)


def test_the_pair_buffers_size_changes_the_work_and_not_the_result(params, hidden):
    """``kimi_linear.held_experts`` under its own ladder of three sizes (the
    default, which the other three models keep) and under this model's one
    size: the same output and the same gradients."""
    assert kimi_linear.held_experts.__defaults__ == (kimi_linear.BUFFER_LADDER,)
    assert lm.PAIR_BUFFER_LADDER[-1] == 1 and kimi_linear.BUFFER_LADDER[-1] == 1
    p, x = params["layers"][2]["moe"], hidden.reshape(ROWS * SEQ, -1)
    none = jnp.zeros((CFG.num_experts,), jnp.float32)
    chosen, weights, _ = kimi_linear.route(x, p["router"], none, CFG, eps=lm.ROUTE_EPS)

    def out_and_grads(*ladder):
        total = lambda x, e: jnp.sum(jnp.sin(kimi_linear.held_experts(  # noqa: E731
            x, chosen, weights, e, CFG, *ladder)))
        return jax.jit(lambda x, e: (kimi_linear.held_experts(
            x, chosen, weights, e, CFG, *ladder), jax.grad(total, argnums=(0, 1))(x, e)))(
                x, p["experts"])

    three, one = out_and_grads(), out_and_grads(lm.PAIR_BUFFER_LADDER)
    for a, b in zip(jax.tree_util.tree_leaves(three), jax.tree_util.tree_leaves(one)):
        assert float(jnp.max(jnp.abs(a))) > 0
        close(b, a, tol=1e-6)


def test_the_shared_functions_are_the_other_models_by_import():
    assert lm.route is kimi_linear.route and lm.held_experts is kimi_linear.held_experts
    assert lm._swiglu is kimi_linear._swiglu and lm._rmsnorm is kimi_linear._rmsnorm
    assert lm.next_token_loss is kimi_linear.next_token_loss
    assert lm.init_opt_state is kimi_linear.init_opt_state  # the same buffers ride along
    assert lm.moved_bias is kimi_linear.moved_bias            # under the same rule
    assert lm._rotate is keye_vl2._rotate and keye_vl2._rope is qwen3_next._rope
    assert lm.causal_attention_in_blocks is qwen3_next.causal_attention_in_blocks


# -- each block against the reference, forward and gradients -----------------------

def block_and_grads(block, *args):
    weigh = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)

    def first(*a):
        out = block(*a)
        return out[0] if isinstance(out, tuple) else out

    return jax.jit(lambda *a: (block(*a), jax.grad(
        lambda *a: jnp.sum(first(*a) * weigh), argnums=tuple(range(len(a))))(*a)))(*args)


def all_close(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0
        close(g, w)


def test_gated_convolution_forward_and_gradients_match_the_reference(params, hidden):
    p = params["layers"][3]["conv"]
    all_close(block_and_grads(lm.conv_block, hidden, p),
              block_and_grads(ref.gated_conv, hidden, p))


def test_attention_forward_and_gradients_match_the_reference(params, hidden):
    p = params["layers"][1]["attn"]
    all_close(block_and_grads(lambda x, p: lm.attn_block(x, p, CFG), hidden, p),
              block_and_grads(lambda x, p: ref.attention(x, p, DIMS), hidden, p))


def test_attention_written_out_head_by_head(params, hidden):
    """Query head j on key/value head j // 4, q and k normed with their scale
    and then rotated over the whole head, causal, scale 1/sqrt(4)."""
    p = jax.tree_util.tree_map(lambda z: np.asarray(z, np.float64), params["layers"][1]["attn"])
    u, dh = np.asarray(hidden[0], np.float64), CFG.head_dim

    def normed(z, w):
        return z / np.sqrt(np.mean(z * z, -1, keepdims=True) + 1e-5) * w

    def turn(z):  # [T, heads, width]
        inv = 1e6 ** (-np.arange(0, dh, 2) / dh)
        angle = np.arange(SEQ)[:, None] * inv[None, :]
        angle = np.concatenate([angle, angle], -1)[:, None, :]
        half = np.concatenate([-z[..., dh // 2:], z[..., :dh // 2]], -1)
        return z * np.cos(angle) + half * np.sin(angle)

    q = turn(normed((u @ p["q_proj"]).reshape(SEQ, 8, dh), p["q_norm"]))
    k = turn(normed((u @ p["k_proj"]).reshape(SEQ, 2, dh), p["k_norm"]))
    v = (u @ p["v_proj"]).reshape(SEQ, 2, dh)
    out = np.zeros((SEQ, 8 * dh))
    for t in range(SEQ):
        for j in range(8):
            scores = np.array([q[t, j] @ k[s, j // 4] for s in range(t + 1)]) / np.sqrt(dh)
            probs = np.exp(scores - scores.max())
            out[t, j * dh:(j + 1) * dh] = (probs / probs.sum()) @ v[:t + 1, j // 4]
    want = out @ p["o_proj"]
    close(lm.attn_block(hidden[:1], params["layers"][1]["attn"], CFG)[0], want)
    close(ref.attention(hidden[:1], params["layers"][1]["attn"], DIMS)[0], want)


def test_expert_layer_forward_load_and_gradients_match_the_reference(params, hidden, bias):
    p, x = params["layers"][2]["moe"], hidden.reshape(ROWS * SEQ, -1)
    got = block_and_grads(lambda x, p: lm.moe_block(x, p, bias[1], CFG), x, p)
    want = block_and_grads(lambda x, p: ref.moe(x, p, bias[1], DIMS), x, p)
    (out, load), (wanted, want_load) = got[0], want[0]
    close(out, wanted)
    assert np.array_equal(load, want_load)
    assert int(load.sum()) == ROWS * SEQ * CFG.num_experts_per_token
    all_close(got[1], want[1])
    unbiased = lm.moe_block(x, p, jnp.zeros_like(bias[1]), CFG)[1]
    assert not np.array_equal(load, unbiased)  # the bias changed who was chosen
    # no held expert chosen, and no shared expert: nothing is left
    nobody = dataclasses.replace(CFG, expert_offset=CFG.num_experts)
    assert float(jnp.max(jnp.abs(lm.moe_block(x, p, bias[1], nobody)[0]))) == 0.0


# -- the whole model: loss, load and every leaf's gradient -------------------------

@pytest.fixture(scope="module")
def model_grads(params, batch, bias):
    got = jax.jit(jax.value_and_grad(
        lambda p: lm.loss_fn(p, batch, CFG, bias), has_aux=True))(params)
    want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_of(p, *batch, bias, DIMS), has_aux=True))(params)
    return got, want


def test_model_loss_and_load_match_the_reference(model_grads):
    ((loss, load), _), ((want, want_load), _) = model_grads
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    assert load.shape == (CFG.n_expert_layers, CFG.num_experts) == (4, 16)
    assert np.array_equal(load, want_load)


@pytest.mark.parametrize("leaf", range(N_LEAVES))
def test_every_leafs_gradient_matches_the_reference(leaf, model_grads, params):
    (_, grads), (_, wanted) = model_grads
    names = leaf_names(params)
    assert len(names) == N_LEAVES
    got = jax.tree_util.tree_leaves(grads)[leaf]
    want = jax.tree_util.tree_leaves(wanted)[leaf]
    assert float(jnp.max(jnp.abs(want))) > 0, names[leaf]
    close(got, want, tol=5e-4)


def test_the_layers_are_the_cells_kinds_in_the_cells_order(params):
    kinds = [("conv" if "conv" in p else "attn", "ffn" if "ffn" in p else "moe")
             for p in params["layers"]]
    assert kinds == [("conv", "ffn"), ("attn", "moe"), ("conv", "moe"), ("conv", "moe"),
                     ("conv", "moe")]
    assert [len(jax.tree_util.tree_leaves(p)) for p in params["layers"]] == [8, 12, 9, 9, 9]
    assert "head" not in params and set(params) == {"embed", "layers", "embedding_norm"}


# -- the tied leaf: one leaf, two gradient paths --------------------------------------------

def test_the_tied_leafs_gradient_is_the_sum_of_the_lookups_and_the_heads(
        model_grads, params, batch, bias):
    """Each path taken alone in the reference (``head`` given apart from the
    embedding), their sum against the tied reference and against the program."""
    embed = params["embed"]
    lookup = jax.jit(jax.grad(lambda e: ref.loss_of(
        {**params, "embed": e}, *batch, bias, DIMS, head=embed)[0]))(embed)
    head = jax.jit(jax.grad(lambda h: ref.loss_of(
        params, *batch, bias, DIMS, head=h)[0]))(embed)
    (_, grads), (_, wanted) = model_grads
    assert float(jnp.max(jnp.abs(lookup))) > 0 and float(jnp.max(jnp.abs(head))) > 0
    # the lookup reaches the rows that occur, the head every row
    seen = np.zeros(CFG.vocab_rows, bool)
    seen[np.asarray(batch[0]).reshape(-1)] = True
    assert np.array_equal(np.any(np.asarray(lookup) != 0, axis=-1), seen) and not seen.all()
    assert np.all(np.any(np.asarray(head) != 0, axis=-1))
    close(wanted["embed"], lookup + head, tol=1e-5)
    close(grads["embed"], lookup + head)
    assert float(jnp.max(jnp.abs(grads["embed"] - head))) > 1e-3 * float(jnp.max(jnp.abs(head)))


# -- three steps of the train step against the reference's AdamW -------------------

def three_batches():
    tokens = [jax.random.randint(jax.random.PRNGKey(20 + i), (ROWS, SEQ), 0, CFG.vocab_rows)
              for i in range(3)]
    return [(t, jnp.roll(t, -1, axis=-1)) for t in tokens]


def test_three_train_steps_follow_the_reference_with_their_buffers(draw):
    start, live = draw(jax.random.PRNGKey(1)), draw(jax.random.PRNGKey(1))  # the step donates
    feed = three_batches()
    opt = jax.jit(lambda p: lm.init_opt_state(p, CFG))(live)
    assert jax.tree_util.tree_leaves(opt["master"]) == []  # float32 leaves need none
    step = lm.make_train_step(CFG)
    norms = jax.jit(lambda tree: jnp.stack(
        [jnp.linalg.norm(x) for x in jax.tree_util.tree_leaves(tree)]))
    losses, loads, first_grad = [], [], None
    for b in feed:
        live, opt, loss = step(live, opt, b)
        losses.append(float(loss))
        loads.append(np.asarray(opt["router_load"]).tolist())
        if first_grad is None:
            first_grad = norms(opt["mu"]) / (1 - 0.9)
    assert step._cache_size() == 1  # one compilation over batches of different routing
    want = ref.first_steps(start, feed, DIMS, n_steps=3)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-4)
    np.testing.assert_allclose(first_grad, want["grad_norm"], rtol=2e-3)
    assert loads == want["router_load"]
    np.testing.assert_allclose(opt["router_bias"], want["router_bias"], atol=1e-7)
    assert sorted(set(np.round(np.abs(np.asarray(opt["router_bias"])), 6).reshape(-1))) == [
        0.001, 0.003]  # three steps of 1e-3 towards the mean, each way
    assert int(opt["count"]) == 3
    change = norms(jax.tree_util.tree_map(lambda a, b: a - b, live, start))
    np.testing.assert_allclose(change, want["change_norm"], rtol=0.02)
    assert bool(jnp.all(norms(opt["mu"]) > 0))


@pytest.mark.parametrize("control", ["half_batch", "state_unchanged", "bf16_everywhere"])
def test_the_controls_are_faults_the_comparison_can_see(control, draw):
    sys.path.insert(0, ROOT)
    from chipbench import correct

    start, feed = draw(jax.random.PRNGKey(1)), three_batches()
    want = ref.first_steps(start, feed, DIMS, n_steps=3)
    found = ref.first_steps(start, feed, DIMS, n_steps=3, precision=control)
    gaps = correct.gaps(found, want)
    if control == "state_unchanged":
        assert gaps["grad_norm_gap"] == gaps["change_norm_gap"] == 1.0
        assert found["loss"][0] == pytest.approx(want["loss"][0], rel=1e-6)
    elif control == "half_batch":
        assert gaps["grad_norm_gap"] > 0.2 and gaps["loss_gap"] > 1e-3
    else:
        assert gaps["change_norm_gap"] > 0.05  # scales at 1 cannot move in bfloat16
    with pytest.raises(ValueError, match="not one of"):
        ref.first_steps(start, feed, DIMS, precision="fp8")


def test_a_bfloat16_tree_has_a_master_copy_a_leaf_and_two_buffers_no_gradient_touches():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.jit(lambda k: lm.init_params(cfg, k))(jax.random.PRNGKey(1))
    opt = jax.jit(lambda p: lm.init_opt_state(p, cfg))(params)
    assert len(jax.tree_util.tree_leaves(opt["master"])) == N_LEAVES
    assert len(jax.tree_util.tree_leaves((params, opt))) == 4 * N_LEAVES + 1 + 2
    assert set(opt) == {"mu", "nu", "count", "master", *BUFFERS}
    tokens = jax.random.randint(jax.random.PRNGKey(2), (ROWS, SEQ), 0, cfg.vocab_rows)
    before = jax.tree_util.tree_structure((params, opt))
    assert float(params["layers"][0]["operator_norm"][0]) == 1.0  # every scale starts at 1 ...
    params, opt, loss = lm.make_train_step(cfg)(params, opt, (tokens, jnp.roll(tokens, -1, -1)))
    assert jax.tree_util.tree_structure((params, opt)) == before
    assert np.isfinite(float(loss))
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves((params, opt))} == {
        "bfloat16", "float32", "int32"}
    assert opt["router_load"].dtype == jnp.int32 and opt["router_bias"].dtype == jnp.float32
    assert int(opt["router_load"].sum()) == 4 * ROWS * SEQ * cfg.num_experts_per_token
    assert float(jnp.max(jnp.abs(opt["router_bias"]))) == pytest.approx(1e-3)
    # ... where a 1e-3 step cannot move a bfloat16 scale, and moves its master copy
    assert float(params["layers"][0]["operator_norm"][0]) == 1.0
    assert float(jnp.max(jnp.abs(opt["master"]["layers"][0]["operator_norm"] - 1.0))) > 0
    # the one tied leaf has one master copy, and both moved
    assert opt["master"]["embed"].shape == params["embed"].shape == (64, 32)


# -- the share test: the shares of a layer add up to the uncut layer ---------------

@pytest.mark.parametrize("which", [1, 2], ids=["attention-layer", "convolution-layer"])
def test_all_4_shares_add_up_to_the_uncut_references_whole_layer(which, params, hidden, bias):
    """4 chips with 4 of 16 experts each; the mixer, which every chip computes
    alike, counted once."""
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = dict(params["layers"][which])
    p["moe"] = jax.jit(lambda k: lm.init_params(whole, k)["layers"][which]["moe"])(
        jax.random.PRNGKey(9))
    dims = dataclasses.replace(DIMS, expert_offset=0)
    uncut = jax.jit(lambda h: ref.layer(h, p, bias[0], dims)[0])

    @jax.jit
    def mixer_once(h):
        u = lm._rmsnorm(h, p["operator_norm"], CFG.norm_eps)
        h = h + (lm.conv_block(u, p["conv"]) if "conv" in p else lm.attn_block(u, p["attn"], CFG))
        return h, lm._rmsnorm(h, p["ffn_norm"], CFG.norm_eps).reshape(ROWS * SEQ, -1)

    @jax.jit
    def one_share(x, chip):  # one compilation: the offset is an argument
        share = dataclasses.replace(CFG, experts_held=4, expert_offset=4 * chip)
        mine = {k: jax.lax.dynamic_slice_in_dim(w, 4 * chip, 4)
                for k, w in p["moe"]["experts"].items()}
        return lm.moe_block(x, {**p["moe"], "experts": mine}, bias[0], share)

    total, x = mixer_once(hidden)
    loads = []
    for chip in range(4):
        out, load = one_share(x, chip)
        assert float(jnp.max(jnp.abs(out))) > 0
        total = total + out.reshape(total.shape)
        loads.append(np.asarray(load))
    close(total, uncut(hidden))
    assert all(np.array_equal(loads[0], load) for load in loads)  # every chip routes alike


def test_the_dense_layer_has_no_share_every_chip_computes_it_whole(params, hidden):
    p = params["layers"][0]
    got = jax.jit(lambda h: lm._layer(h, p, None, CFG))(hidden)
    want = jax.jit(lambda h: ref.layer(h, p, None, DIMS))(hidden)
    assert got[1] is None and want[1] is None
    close(got[0], want[0])


# -- spans and counters -------------------------------------------------------------------

def test_the_lowered_step_names_its_blocks(batch):
    params = jax.eval_shape(lambda k: lm.init_params(CFG, k), jax.random.PRNGKey(1))
    opt = jax.eval_shape(lambda p: lm.init_opt_state(p, CFG), params)
    text = lm.make_train_step(CFG).lower(params, opt, batch).as_text(debug_info=True)
    for scope in ("conv.mix", "attn", "ffn.dense", "moe.route", "moe.experts", "head.loss"):
        assert f"{scope}/" in text or f"{scope})/" in text, scope
    assert "module @jit_step" in text  # the trace readers find ``jit_step``
    assert "stablehlo.while" not in text  # unrolled: no scan over layers, blocks or experts
    assert "stablehlo.case" not in text   # one size of pair buffer: no switch between sizes


def test_routing_stats_reads_the_state_and_sets_its_gauges():
    from tpu_resiliency.telemetry import get_registry

    load = np.zeros((4, 16), np.int32)
    load[:, 4:8] = [[10, 10, 10, 10], [30, 0, 0, 10], [5, 5, 5, 5], [20, 20, 20, 20]]
    load[:, 0] = [120, 120, 60, 120]    # an expert held elsewhere takes the rest
    stats = lm.routing_stats({"router_load": load}, CFG)
    assert stats["max"] == 30.0 and stats["mean"] == pytest.approx(180 / 16)
    assert stats["share"] == pytest.approx(180 / 600)
    assert stats["held_share_min"] == pytest.approx(0.25)   # 40 of 160, twice; 20 of 80
    snapshot = get_registry().snapshot()
    assert snapshot["tpurx_model_held_share_min"]["samples"][0]["value"] == pytest.approx(0.25)
    assert snapshot["tpurx_model_expert_load_max"]["samples"][0]["value"] == 30.0
    assert lm.routing_stats({"router_load": np.zeros((4, 16), np.int32)}, CFG)[
        "held_share_min"] == 0.0    # before the first step: no division by zero


# -- the state through the checkpoint paths and the wrapper -----------------------------

def bfloat16_state(seed=1):
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.jit(lambda k: lm.init_params(cfg, k))(jax.random.PRNGKey(seed))
    return cfg, params, jax.jit(lambda p: lm.init_opt_state(p, cfg))(params)


def feed_of(cfg, n):
    tokens = [jax.random.randint(jax.random.PRNGKey(40 + i), (ROWS, SEQ), 0, cfg.vocab_rows)
              for i in range(n)]
    return [(t, jnp.roll(t, -1, axis=-1)) for t in tokens]


@pytest.fixture
def fingerprint():
    sys.path.insert(0, ROOT)
    from chipbench import weights

    return weights.make_fingerprint_fn()


@pytest.mark.parametrize("rung", ["device-slot", "disk"])
def test_the_state_with_its_buffers_round_trips_with_an_equal_fingerprint(
        rung, tmp_path, fingerprint):
    """After two steps (bias and load are no longer 0): through ``async_save``
    and ``load_checkpoint``; from the sealed ring slot (snapshot mode through
    a ring of two, which the CPU default ``sync`` does not keep) and, read
    past both warm rungs, from disk."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.checkpointing.async_ckpt import resident

    cfg, params, opt = bfloat16_state()
    step = lm.make_train_step(cfg)
    for b in feed_of(cfg, 2):
        params, opt, _ = step(params, opt, b)
    assert float(jnp.max(jnp.abs(opt["router_bias"]))) > 0 and int(opt["router_load"].sum()) > 0
    tree = {"params": params, "opt": opt}
    want = np.asarray(fingerprint(tree))
    assert want.shape == (4 * N_LEAVES + 3, 2)
    cp = AsyncCheckpointer(digest=True, resident=True, stage_mode="snapshot", stage_buffers=2)
    d = str(tmp_path / "ck")
    try:
        cp.async_save(tree, d, extra_metadata={"iteration": 2})
        cp.maybe_finalize(blocking=True)
        assert resident.lookup(d).device is not None
        stats = {}
        back = load_checkpoint(d, tree, stats=stats, resident=(rung == "device-slot"))
        total = sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))
        assert stats["bytes_read"] == total
        assert stats["bytes_device"] == (total if rung == "device-slot" else 0)
        assert stats["bytes_shm"] == 0
    finally:
        cp.close()
        resident.invalidate()
    assert np.array_equal(np.asarray(fingerprint(back)), want)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(tree)
    for name in BUFFERS:
        assert np.array_equal(back["opt"][name], opt[name])
        assert back["opt"][name].dtype == opt[name].dtype


def test_a_recovery_under_the_wrapper_continues_the_no_fault_losses_bit_for_bit(
        store_server, tmp_path):
    """Six steps without a fault; then the same under ``Wrapper``: a save
    after step 2, an exception after step 4, and the re-entered function
    restores the save and runs steps 3-6 again: every loss equals the
    no-fault run's, bit for bit, and so do bias and load at the end."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.inprocess import Wrapper
    from tpu_resiliency.store import StoreClient

    cfg, params, opt = bfloat16_state(seed=3)
    step, feed = lm.make_train_step(cfg), feed_of(cfg, 6)
    wanted = []
    for b in feed:
        params, opt, loss = step(params, opt, b)
        wanted.append(np.float32(loss).tobytes())
    end = {name: np.asarray(opt[name]) for name in BUFFERS}

    cp = AsyncCheckpointer()
    d = str(tmp_path / "ck")
    seen = {"entries": 0, "losses": {}}

    def train(call_wrapper=None):
        seen["entries"] += 1
        _, params, opt = bfloat16_state(seed=3)
        first = 0
        if seen["entries"] > 1:
            back = load_checkpoint(d, {"params": params, "opt": opt})
            params, opt, first = back["params"], back["opt"], 3
        for i in range(first, 6):
            call_wrapper.ping()
            params, opt, loss = step(params, opt, feed[i])
            seen["losses"].setdefault(i, []).append(np.float32(loss).tobytes())
            if i == 2 and seen["entries"] == 1:
                cp.save({"params": params, "opt": opt}, d, extra_metadata={"iteration": i})
            if i == 4 and seen["entries"] == 1:
                raise RuntimeError("injected fault")
        return {name: np.asarray(opt[name]) for name in BUFFERS}

    wrapper = Wrapper(
        store_factory=lambda: StoreClient("127.0.0.1", store_server.port, timeout=10.0),
        group="lfm2-moe", soft_timeout=3600.0, hard_timeout=7200.0,
        enable_monitor_process=False, enable_sibling_monitor=False)
    try:
        found = wrapper(train)()
    finally:
        cp.close()
    assert seen["entries"] == 2
    assert [len(seen["losses"][i]) for i in range(6)] == [1, 1, 1, 2, 2, 1]
    for i in range(6):
        assert set(seen["losses"][i]) == {wanted[i]}, i
    for name in BUFFERS:
        assert np.array_equal(found[name], end[name])


# -- the benchmark's copy, and the cell's counts --------------------------------------------

def test_the_benchmarks_reference_is_this_repositorys_byte_for_byte():
    with open(os.path.join(ROOT, "tpu_resiliency/models/lfm2_moe_reference.py"), "rb") as f:
        ours = f.read()
    with open(os.path.join(ROOT, "chipbench/reference/lfm2_moe.py"), "rb") as f:
        assert f.read() == ours


def test_the_benchmarks_reference_gives_equal_numbers(params, batch, bias):
    sys.path.insert(0, ROOT)
    from chipbench.reference import lfm2_moe as copy

    ours = jax.jit(lambda p: ref.loss_of(p, *batch, bias, DIMS))(params)
    theirs = jax.jit(lambda p: copy.loss_of(
        p, *batch, bias, copy.Dims(**dataclasses.asdict(DIMS))))(params)
    assert float(ours[0]) == float(theirs[0])
    assert np.array_equal(ours[1], theirs[1])


def test_the_cells_counts_from_shapes_nothing_allocated():
    sys.path.insert(0, ROOT)
    from chipbench import families, weights

    family, sizes = families.of_file(CELL_CONFIG)
    assert sizes.n_params == 507_820_160 and sizes.tokens_per_step == 4096
    assert sizes.conv_matmul_params + 3 * 2048 == 16_783_360
    assert sizes.attn_matmul_params + 128 == 10_485_888 and sizes.expert_params == 11_010_048
    assert sizes.state_bytes == 7_109_483_268  # 14 B a parameter, 1,024 B of buffers, the count
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(weights.make_state_fn(family, sizes), key)
    leaves = jax.tree_util.tree_leaves(state)
    assert sum(x.size * x.dtype.itemsize for x in leaves) == sizes.state_bytes
    assert len(leaves) == 199  # 49 trained leaves x 4, the count, two buffers
    assert {str(x.dtype) for x in leaves} == {"bfloat16", "float32", "int32"}
    draw = jax.eval_shape(lambda k: family.draw_params(sizes, k, jnp.bfloat16), key)
    assert sum(x.size for x in jax.tree_util.tree_leaves(draw)) == sizes.n_params
    assert draw["embed"].shape == (16384, 2048) and "head" not in draw
    # a token's forward pass: 415.8 MFLOP (convolution mixers 134, held experts 88,
    # dense feed-forward 88, head 67, attention 38); 5.1 TFLOP a step of 4,096 tokens
    assert family.forward_flops_per_token(sizes) == pytest.approx(415.76e6, rel=1e-4)
    assert family.train_flops_per_token(sizes) * sizes.tokens_per_step == pytest.approx(
        5.109e12, rel=0.001)
    assert family.CONTROLS == ("bf16_everywhere", "half_batch", "state_unchanged")
    # the widths are the source's; only depth, the experts held and the vocabulary are cut
    with open(CELL_CONFIG) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[cfg["name"]]
    assert set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts", "vocab_size"}
    assert cfg["model_type"] == "lfm2_moe" and entry["source"] == cfg["source"]
    assert (cfg["hidden_size"], cfg["conv_L_cache"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["rope_theta"]) == (
                2048, 3, 32, 8, 64, 1_000_000)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["deployment"]["experts"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (7168, 1792, 32, 4, 1)
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 1, 8, 16384)
    assert cfg["layer_types"] == cfg["published"]["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["published"]["layer_types"].count("conv") == 18
    assert cfg["norm_eps"] == 1e-5 and cfg["deployment"]["chips_sharing_a_layer"] == 4
    assert cfg["tie_word_embeddings"] is True and cfg["conv_bias"] is False
    fits = cfg["compiled_for_v5e"]
    assert (2 * fits["state_on_device_bytes"] + fits["train_step"]["temp_bytes"]
            + fits["other_resident_bytes"]) <= 16.6e9
    # the cell is on every list the fifth cell is on, right after it
    cell, fifth = "lfm2-8b-a1b-1chip.stall-inproc", "keye-vl-2.0-30b-a3b-1chip.stall-inproc"
    mine = {w["name"]: w for w in bench["workloads"]}[cell]
    assert mine["chips"] == 1
    lists = [m["workloads"] for m in bench["end_to_end"] + bench["per_layer"]
             if fifth in m.get("workloads", [])]
    assert len(lists) == 19 and all(
        names[names.index(fifth) + 1] == cell for names in lists)
