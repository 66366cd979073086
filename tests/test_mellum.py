"""The sixth reference workload (``tpu_resiliency/models/mellum.py``) against
its plain reference (``mellum_reference.py``): seeded random weights, tiny
sizes, CPU.  Attention inside a window in blocks against a dense band mask
(windows below, equal to and above the block, a multiple of it and not) and
against the window's two edges; both rotary tables against numbers worked by
hand; the full layers' call against ``qwen3_next.causal_attention_in_blocks``;
each block forward and gradients, the whole model's loss and every leaf's
gradient, three train steps in float32 and at the configuration's precision,
the share test (all 8 shares of an expert layer, all 8 slices of the
vocabulary), the state through ``async_save`` / ``load_checkpoint`` and the
sealed ring slot, a recovery under ``Wrapper`` that continues the no-fault
losses bit for bit, the benchmark's copy of the reference, and the cell's
counts from shapes.
"""

import dataclasses
import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_CONFIG = os.path.join(ROOT, "chipbench", "configs", "mellum2-12b-a2.5b-1chip.json")

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_resiliency.models import kimi_linear, lfm2_moe, qwen3_next  # noqa: E402
from tpu_resiliency.models import mellum as lm  # noqa: E402
from tpu_resiliency.models import mellum_reference as ref  # noqa: E402

# the cell's four layers (sliding, sliding, sliding, full); 8 query heads on 2
# key/value heads (query head j reads head j // 4); 8 chips of 2 experts each,
# this one the third; blocks of 8 queries and a window of 11 keys: above the
# block and no multiple of it
KINDS = ("sliding_attention", "full_attention")
CFG = lm.MellumConfig(
    hidden_size=32, num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    sliding_window=11, moe_intermediate_size=16, num_experts=16, experts_held=2,
    expert_offset=4, num_experts_per_token=3, vocab_rows=64, attn_block=8, dtype=jnp.float32)
DIMS = ref.Dims(window=11, experts_per_token=3, expert_offset=4, query_block=8)
ROWS, SEQ = 2, 20  # no multiple of the block of queries
N_LEAVES = 51  # 4 x 12 (two norms, four projections, the q and k norms, router, experts) + 3
BUFFERS = ("router_load",)


@pytest.fixture(scope="module", autouse=True)
def quick_compilation():
    """Some sixty small programs are compiled here and none is timed."""
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
def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(2), (ROWS, SEQ), 0, CFG.vocab_rows)
    return tokens, jnp.roll(tokens, -1, axis=-1)


@pytest.fixture(scope="module")
def hidden():
    return jax.random.normal(jax.random.PRNGKey(3), (ROWS, SEQ, CFG.hidden_size))


# -- attention inside a window, in blocks ---------------------------------------------

@pytest.fixture(scope="module")
def qkv():
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (ROWS, SEQ, 2, 4, 8))
    k, v = (jax.random.normal(key, (ROWS, SEQ, 2, 8)) for key in keys[1:])
    return q, k, v


def dense_band(q, k, v, window):
    """The whole [T, T] matrix under the band mask ``i - window < j <= i``."""
    t = q.shape[1]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    scores = jnp.einsum("rqkgd,rskd->rkgqs", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where((j <= i) & (j > i - window), scores, -jnp.inf), axis=-1)
    return jnp.einsum("rkgqs,rskd->rqkgd", probs, v)


# below the block of 8, equal to it, above it and no multiple, a multiple, the whole sequence
WINDOWS = [1, 3, 8, 11, 16, 20]


@pytest.mark.parametrize("window", WINDOWS)
def test_window_attention_in_blocks_is_the_dense_band_mask(window, qkv):
    got = jax.jit(lambda *a: lm.window_attention_in_blocks(*a, 8, window))(*qkv)
    close(got, dense_band(*qkv, window), tol=1e-5)
    if window == 1:  # a query that sees itself alone reads its own value
        close(got, jnp.broadcast_to(qkv[2][:, :, :, None, :], got.shape), tol=1e-6)


@pytest.mark.parametrize("window", WINDOWS)
def test_window_attention_in_blocks_has_the_dense_band_masks_gradients(window, qkv):
    weigh = jax.random.normal(jax.random.PRNGKey(7), qkv[0].shape)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(
        lm.window_attention_in_blocks(*a, 8, window) * weigh), argnums=(0, 1, 2)))(*qkv)
    want = jax.grad(lambda *a: jnp.sum(dense_band(*a, window) * weigh), argnums=(0, 1, 2))(*qkv)
    for name, g, w in zip("qkv", got, want):
        if window == 1 and name in "qk":  # a softmax over one key does not move with its score
            assert float(jnp.max(jnp.abs(g))) < 1e-6 and float(jnp.max(jnp.abs(w))) < 1e-6
            continue
        assert float(jnp.max(jnp.abs(w))) > 0
        close(g, w, tol=1e-5)


def test_a_window_of_the_whole_sequence_is_the_causal_attention(qkv):
    close(lm.window_attention_in_blocks(*qkv, 8, SEQ),
          qwen3_next.causal_attention_in_blocks(*qkv, 8), tol=1e-6)


def test_the_key_blocks_left_out_are_the_ones_no_query_of_the_block_sees():
    # a block of 512 queries from 1,024 on: the oldest key its first query sees
    # is start - 1023, in the block that starts 1,024 before the queries' own
    assert [lm.first_key_block(start, 512, 1024) for start in (0, 512, 1024, 1536, 7680)] == [
        0, 0, 0, 512, 6656]
    assert lm.first_key_block(1024, 512, 1025) == 0 and lm.first_key_block(1025, 512, 1) == 1024
    # 1, 2, then 3 key blocks a block of queries; a causal layer 1 .. 16
    assert lm.window_key_blocks(8192, 512, 1024) == (1 + 2 + 14 * 3, 136)
    # a window of 11 over blocks of 8: query 16 still sees key 6, in the first block
    assert lm.window_key_blocks(SEQ, 8, 11) == (6, 6)
    assert lm.window_key_blocks(SEQ, 8, 3) == (1 + 2 + 2, 1 + 2 + 3)
    assert lm.window_key_blocks(SEQ, 8, SEQ) == (6, 6)


ATTENTIONS = {
    "program": lambda u, p, kind: lm.attn_block(u, p, CFG, kind),
    "reference": lambda u, p, kind: ref.attention(u, p, DIMS, kind),
}


@pytest.mark.parametrize("side", sorted(ATTENTIONS))
def test_a_sliding_layers_query_sees_1_window_of_keys_itself_among_them(side, params, hidden):
    """Position 3's input moved: positions 3 .. 3 + 10 move (11 of them, the
    window), none before and none after; in a full layer every later one."""
    p = params["layers"][0]["attn"]
    moved = hidden.at[:, 3].add(1.0)
    for kind, want in (("sliding_attention", list(range(3, 14))),
                       ("full_attention", list(range(3, SEQ)))):
        before, after = (ATTENTIONS[side](h, p, kind) for h in (hidden, moved))
        changed = np.flatnonzero(np.max(np.abs(np.asarray(before - after)), axis=(0, 2)) > 1e-7)
        assert changed.tolist() == want, kind
        assert np.array_equal(before[:, :3], after[:, :3])


# -- the two rotary tables, against numbers worked by hand ---------------------------------

PUBLISHED = lm.MellumConfig()
TABLES = {
    "program": lambda kind: lm.inv_freq_and_scale(PUBLISHED, kind),
    "reference": lambda kind: ref.frequencies(ref.Dims(), kind, 128),
}


def test_the_yarn_ramp_runs_from_pair_18_to_pair_35():
    # 128 ln(8192 / (32 x 2 pi)) / (2 ln 500000) = 18.08; with beta 1: 34.98
    ln = math.log
    assert 128 * ln(8192 / (32 * 2 * math.pi)) / (2 * ln(500000)) == pytest.approx(18.08, abs=0.005)
    assert 128 * ln(8192 / (2 * math.pi)) / (2 * ln(500000)) == pytest.approx(34.98, abs=0.005)
    assert lm.yarn_correction_range(PUBLISHED) == (18, 35)
    assert ref.yarn_range(ref.Dims(), 128) == (18, 35)
    assert PUBLISHED.yarn_attention_factor == pytest.approx(0.1 * ln(16) + 1, abs=1e-15)
    # clipped to the head: a model of 8 original positions has no pair that turns so seldom
    short = dataclasses.replace(PUBLISHED, yarn_original_positions=8)
    assert lm.yarn_correction_range(short)[0] == 0
    assert ref.yarn_range(ref.Dims(yarn_original_positions=8), 128)[0] == 0


@pytest.mark.parametrize("side", sorted(TABLES))
def test_the_sliding_layers_table_is_the_default_one(side):
    f, c = TABLES[side]("sliding_attention")
    f = np.asarray(f, np.float64)
    assert c == 1.0 and f.shape == (64,)
    assert f[0] == 1.0
    assert f[1] == pytest.approx(500000 ** (-2 / 128), rel=1e-6)       # 0.81462
    assert f[63] == pytest.approx(500000 ** (-126 / 128), rel=1e-6)    # 2.4551e-6
    assert f[63] == pytest.approx(2.4551e-6, rel=1e-4)


@pytest.mark.parametrize("side", sorted(TABLES))
def test_the_full_layers_table_is_yarns(side):
    f, c = TABLES[side]("full_attention")
    f, default = np.asarray(f, np.float64), 500000.0 ** (-np.arange(64) / 64)
    assert c == 1.2772588722239782
    # up to pair 18 the default frequency, from pair 35 on a 16th of it ...
    np.testing.assert_allclose(f[:19], default[:19], rtol=1e-6)
    np.testing.assert_allclose(f[35:], default[35:] / 16, rtol=1e-6)
    assert f[0] == 1.0 and f[63] == pytest.approx(2.4551e-6 / 16, rel=1e-4)
    # ... and between them the blend: pair 26 is 8/17 of the way
    r = 8 / 17
    assert f[26] == pytest.approx((1 - r) * default[26] + r * default[26] / 16, rel=1e-6)
    assert f[26] == pytest.approx(0.55882 * 500000 ** (-26 / 64), rel=1e-4)
    assert np.all(np.diff(f) < 0)


ROTATIONS = {
    "program": lambda x, kind: lm._rotate(x, PUBLISHED, kind),
    "reference": lambda x, kind: ref.rope(x, ref.Dims(), kind),
}


@pytest.mark.parametrize("side", sorted(ROTATIONS))
def test_cos_and_sin_are_both_scaled_on_the_full_layers_and_on_no_other(side):
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 5, 2, 128))
    norms = lambda z: np.asarray(jnp.linalg.norm(z, axis=-1), np.float64)  # noqa: E731
    sliding, full = (ROTATIONS[side](x, kind) for kind in KINDS)
    np.testing.assert_allclose(norms(sliding), norms(x), rtol=1e-5)   # a rotation
    np.testing.assert_allclose(norms(full), 1.2772588722239782 * norms(x), rtol=1e-5)
    # position 0 is turned by nothing; position 4, channel pair (1, 65), by 4 f_1
    np.testing.assert_allclose(sliding[:, 0], x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(full[:, 0], 1.2772588722239782 * x[:, 0], rtol=1e-6)
    angle = 4 * 500000 ** (-2 / 128)
    a, b = np.asarray(x[0, 4, 0, 1], np.float64), np.asarray(x[0, 4, 0, 65], np.float64)
    assert float(sliding[0, 4, 0, 1]) == pytest.approx(
        a * math.cos(angle) - b * math.sin(angle), abs=1e-5)
    assert float(sliding[0, 4, 0, 65]) == pytest.approx(
        b * math.cos(angle) + a * math.sin(angle), abs=1e-5)


# -- the full layers' call, and what is shared by import -------------------------------------

def test_the_full_layers_call_is_the_third_models_causal_attention_as_it_is(params, hidden):
    p, u = params["layers"][3]["attn"], hidden
    f32 = jnp.float32

    def prepared(z, w):
        normed = kimi_linear._rmsnorm(z.astype(f32), w.astype(f32), CFG.rms_norm_eps)
        return lm._rotate(normed, CFG, "full_attention")

    q = prepared((u @ p["q_proj"]).reshape(ROWS, SEQ, 8, 8), p["q_norm"])
    k = prepared((u @ p["k_proj"]).reshape(ROWS, SEQ, 2, 8), p["k_norm"])
    v = (u @ p["v_proj"]).reshape(ROWS, SEQ, 2, 8)
    want = qwen3_next.causal_attention_in_blocks(
        q.reshape(ROWS, SEQ, 2, 4, 8), k, v, CFG.attn_block).reshape(ROWS, SEQ, 64) @ p["o_proj"]
    assert np.array_equal(lm.attn_block(u, p, CFG, "full_attention"), want)
    assert not np.allclose(lm.attn_block(u, p, CFG, "sliding_attention"), want, atol=1e-3)


def test_the_shared_functions_are_the_other_models_by_import():
    assert lm.route is qwen3_next.route and lm.held_experts is kimi_linear.held_experts
    assert lm._rmsnorm is kimi_linear._rmsnorm
    assert lm.next_token_loss is kimi_linear.next_token_loss
    assert lm.causal_attention_in_blocks is qwen3_next.causal_attention_in_blocks
    assert lm.routing_stats is lfm2_moe.routing_stats
    assert lm.adamw_tree is qwen3_next.adamw_tree
    # the shared functions keep their signatures: no parameter was added for this model
    import inspect

    assert list(inspect.signature(qwen3_next.causal_attention_in_blocks).parameters) == [
        "q", "k", "v", "block"]
    assert list(inspect.signature(qwen3_next._rope).parameters) == ["x", "cfg"]
    assert list(inspect.signature(qwen3_next.route).parameters) == ["x", "router", "cfg"]


def test_the_two_kinds_have_the_same_leaves_and_different_programs(params, hidden):
    shapes = [jax.tree_util.tree_map(lambda x: x.shape, p) for p in params["layers"]]
    assert all(s == shapes[0] for s in shapes)
    assert [len(jax.tree_util.tree_leaves(p)) for p in params["layers"]] == [12] * 4
    assert set(params) == {"embed", "layers", "final_norm", "head"}
    p = params["layers"][0]
    sliding, full = (jax.jit(lambda h, kind=kind: lm._layer(h, p, CFG, kind)[0])(hidden)
                     for kind in KINDS)
    assert float(jnp.max(jnp.abs(sliding - full))) > 1e-2
    with pytest.raises(ValueError, match="layer_types names every layer's kind"):
        lm.make_train_step(dataclasses.replace(CFG, layer_types=("conv",)))


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


@pytest.mark.parametrize("kind", KINDS)
def test_attention_forward_and_gradients_match_the_reference(kind, params, hidden):
    p = params["layers"][1]["attn"]
    all_close(block_and_grads(lambda x, p: lm.attn_block(x, p, CFG, kind), hidden, p),
              block_and_grads(lambda x, p: ref.attention(x, p, DIMS, kind), hidden, p))


@pytest.mark.parametrize("kind", KINDS)
def test_attention_written_out_head_by_head(kind, params, hidden):
    """Query head j on key/value head j // 4, q and k normed with their scale
    and then rotated over the whole head by the kind's table, the kind's
    mask, scale 1/sqrt(8)."""
    p = jax.tree_util.tree_map(lambda z: np.asarray(z, np.float64), params["layers"][1]["attn"])
    u, dh = np.asarray(hidden[0], np.float64), CFG.head_dim
    inv = 500000.0 ** (-np.arange(0, dh, 2) / dh)
    scale, window = 1.0, CFG.sliding_window
    if kind == "full_attention":
        # 8 ln(8192 / (32 x 2 pi)) / (2 ln 500000) = 1.13 -> 1; with beta 1: 2.19 -> 3
        ramp = np.clip((np.arange(dh // 2) - 1) / 2, 0, 1)
        inv, scale, window = (1 - ramp) * inv + ramp * inv / 16, 1.2772588722239782, SEQ

    def normed(z, w):
        return z / np.sqrt(np.mean(z * z, -1, keepdims=True) + 1e-6) * w

    def turn(z):  # [T, heads, width]
        angle = np.arange(SEQ)[:, None] * inv[None, :]
        angle = np.concatenate([angle, angle], -1)[:, None, :]
        half = np.concatenate([-z[..., dh // 2:], z[..., :dh // 2]], -1)
        return scale * (z * np.cos(angle) + half * np.sin(angle))

    q = turn(normed((u @ p["q_proj"]).reshape(SEQ, 8, dh), p["q_norm"]))
    k = turn(normed((u @ p["k_proj"]).reshape(SEQ, 2, dh), p["k_norm"]))
    v = (u @ p["v_proj"]).reshape(SEQ, 2, dh)
    out = np.zeros((SEQ, 8 * dh))
    for t in range(SEQ):
        first = max(0, t - window + 1)
        for j in range(8):
            scores = np.array([q[t, j] @ k[s, j // 4] for s in range(first, t + 1)]) / np.sqrt(dh)
            probs = np.exp(scores - scores.max())
            out[t, j * dh:(j + 1) * dh] = (probs / probs.sum()) @ v[first:t + 1, j // 4]
    want = out @ p["o_proj"]
    close(lm.attn_block(hidden[:1], params["layers"][1]["attn"], CFG, kind)[0], want)
    close(ref.attention(hidden[:1], params["layers"][1]["attn"], DIMS, kind)[0], want)


ROUTES = {
    "program": lambda x, router: qwen3_next.route(x, router, CFG),
    "reference": lambda x, router: ref.route(x, router, DIMS),
}


@pytest.mark.parametrize("side", sorted(ROUTES))
def test_the_router_is_a_softmax_over_all_experts_renormalised_over_the_chosen(
        side, params, hidden):
    router = params["layers"][1]["moe"]["router"]
    x = hidden.reshape(ROWS * SEQ, -1)
    z = np.asarray(x @ router, np.float64)
    probs = np.exp(z - z.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    chosen, weights, load = ROUTES[side](x, router)
    order = np.argsort(-probs, axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(order, -1))
    picked = np.take_along_axis(probs, np.asarray(chosen), axis=-1)
    close(weights, picked / picked.sum(-1, keepdims=True), tol=1e-5)
    np.testing.assert_allclose(np.sum(np.asarray(weights), -1), 1.0, rtol=1e-5)
    assert int(load.sum()) == ROWS * SEQ * 3 and load.shape == (16,)
    assert np.array_equal(load, np.bincount(np.asarray(chosen).reshape(-1), minlength=16))


def test_expert_layer_forward_load_and_gradients_match_the_reference(params, hidden):
    p, x = params["layers"][2]["moe"], hidden.reshape(ROWS * SEQ, -1)
    got = block_and_grads(lambda x, p: lm.moe_block(x, p, CFG), x, p)
    want = block_and_grads(lambda x, p: ref.moe(x, p, DIMS), x, p)
    (out, load), (wanted, want_load) = got[0], want[0]
    close(out, wanted)
    assert np.array_equal(load, want_load)
    assert int(load.sum()) == ROWS * SEQ * CFG.num_experts_per_token
    all_close(got[1], want[1])
    # no held expert chosen, and no shared expert: nothing is left
    nobody = dataclasses.replace(CFG, expert_offset=CFG.num_experts)
    assert float(jnp.max(jnp.abs(lm.moe_block(x, p, nobody)[0]))) == 0.0


def test_the_pair_buffers_ladder_is_given_by_keyword_and_changes_no_number(params, hidden):
    """``kimi_linear.held_experts`` under its own ladder of three sizes (the
    default, which three models keep) and under this model's two, a quarter
    and the whole: the same output and the same gradients."""
    assert kimi_linear.held_experts.__defaults__ == (kimi_linear.BUFFER_LADDER,)
    assert lm.PAIR_BUFFER_LADDER == (4, 1) and kimi_linear.BUFFER_LADDER[-1] == 1
    p, x = params["layers"][2]["moe"], hidden.reshape(ROWS * SEQ, -1)
    chosen, weights, _ = qwen3_next.route(x, p["router"], CFG)

    def out_and_grads(**ladder):
        total = lambda x, e: jnp.sum(jnp.sin(kimi_linear.held_experts(  # noqa: E731
            x, chosen, weights, e, CFG, **ladder)))
        return jax.jit(lambda x, e: (kimi_linear.held_experts(
            x, chosen, weights, e, CFG, **ladder), jax.grad(total, argnums=(0, 1))(x, e)))(
                x, p["experts"])

    three, two = out_and_grads(), out_and_grads(ladder=lm.PAIR_BUFFER_LADDER)
    for a, b in zip(jax.tree_util.tree_leaves(three), jax.tree_util.tree_leaves(two)):
        assert float(jnp.max(jnp.abs(a))) > 0
        close(b, a, tol=1e-6)


# -- the whole model: loss, load and every leaf's gradient -------------------------

@pytest.fixture(scope="module")
def model_grads(params, batch):
    got = jax.jit(jax.value_and_grad(lambda p: lm.loss_fn(p, batch, CFG), has_aux=True))(params)
    want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_of(p, *batch, DIMS), has_aux=True))(params)
    return got, want


def test_model_loss_and_load_match_the_reference(model_grads):
    ((loss, load), _), ((want, want_load), _) = model_grads
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    assert load.shape == (4, CFG.num_experts) == (4, 16)
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


def test_a_layer_of_the_other_kind_is_another_model(params, batch):
    """The reference with the last layer read as a sliding one: another loss,
    so the kinds' order is held by the comparison and not only by the leaves."""
    want = float(jax.jit(lambda p: ref.loss_of(p, *batch, DIMS)[0])(params))
    other = dataclasses.replace(DIMS, layer_types=("sliding_attention",) * 4)
    assert abs(float(jax.jit(lambda p: ref.loss_of(p, *batch, other)[0])(params)) - want) > 1e-4
    with pytest.raises(ValueError, match="names the kind of every layer"):
        ref.loss_of(params, *batch, dataclasses.replace(DIMS, layer_types=KINDS))
    with pytest.raises(ValueError, match="sliding_attention or full_attention"):
        ref.frequencies(DIMS, "conv", 8)


# -- three steps of the train step against the reference's AdamW -------------------

def three_batches():
    tokens = [jax.random.randint(jax.random.PRNGKey(20 + i), (ROWS, SEQ), 0, CFG.vocab_rows)
              for i in range(3)]
    return [(t, jnp.roll(t, -1, axis=-1)) for t in tokens]


NORMS = jax.jit(lambda tree: jnp.stack(
    [jnp.linalg.norm(x.astype(jnp.float32)) for x in jax.tree_util.tree_leaves(tree)]))


def test_three_train_steps_follow_the_reference_in_float32(draw):
    start, live = draw(jax.random.PRNGKey(1)), draw(jax.random.PRNGKey(1))  # the step donates
    feed = three_batches()
    opt = jax.jit(lambda p: lm.init_opt_state(p, CFG))(live)
    assert jax.tree_util.tree_leaves(opt["master"]) == []  # float32 leaves need none
    step = lm.make_train_step(CFG)
    losses, loads, first_grad = [], [], None
    for b in feed:
        live, opt, loss = step(live, opt, b)
        losses.append(float(loss))
        loads.append(np.asarray(opt["router_load"]).tolist())
        if first_grad is None:
            first_grad = NORMS(opt["mu"]) / (1 - 0.9)
    assert step._cache_size() == 1  # one compilation over batches of different routing
    want = ref.first_steps(start, feed, DIMS, n_steps=3)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-4)
    np.testing.assert_allclose(first_grad, want["grad_norm"], rtol=2e-3)
    assert loads == want["router_load"]
    assert int(opt["count"]) == 3
    change = NORMS(jax.tree_util.tree_map(lambda a, b: a - b, live, start))
    np.testing.assert_allclose(change, want["change_norm"], rtol=0.02)
    assert bool(jnp.all(NORMS(opt["mu"]) > 0))


def test_three_train_steps_follow_the_reference_at_the_configurations_precision():
    """bfloat16 leaves and matmuls over a float32 master copy and moments,
    against the float32 reference started from the same rounded draw: by the
    three numbers a run is compared on, under the tiny cut's own limits."""
    sys.path.insert(0, ROOT)
    from chipbench import correct

    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.jit(lambda k: lm.init_params(cfg, k))(jax.random.PRNGKey(1))
    start = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params)
    opt = jax.jit(lambda p: lm.init_opt_state(p, cfg))(params)
    step, feed = lm.make_train_step(cfg), three_batches()
    losses, first_grad = [], None
    for b in feed:
        params, opt, loss = step(params, opt, b)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = NORMS(opt["mu"]) / (1 - 0.9)
    change = NORMS(jax.tree_util.tree_map(lambda m, s: m - s, opt["master"], start))
    found = {"loss": losses, "grad_norm": np.asarray(first_grad, np.float64).tolist(),
             "change_norm": np.asarray(change, np.float64).tolist()}
    want = ref.first_steps(start, feed, DIMS, n_steps=3)
    gaps = correct.gaps(found, want)
    assert correct.within(gaps, correct.load_limits("mellum2-12b-a2.5b-1chip", rehearsal=True))
    assert gaps["loss_gap"] > 0 and gaps["change_norm_gap"] > 0  # bfloat16 did compute it


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


def test_a_bfloat16_tree_has_a_master_copy_a_leaf_and_one_buffer_no_gradient_touches():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.jit(lambda k: lm.init_params(cfg, k))(jax.random.PRNGKey(1))
    opt = jax.jit(lambda p: lm.init_opt_state(p, cfg))(params)
    assert len(jax.tree_util.tree_leaves(opt["master"])) == N_LEAVES
    assert len(jax.tree_util.tree_leaves((params, opt))) == 4 * N_LEAVES + 1 + 1
    assert set(opt) == {"mu", "nu", "count", "master", *BUFFERS}
    tokens = jax.random.randint(jax.random.PRNGKey(2), (ROWS, SEQ), 0, cfg.vocab_rows)
    before = jax.tree_util.tree_structure((params, opt))
    assert float(params["layers"][0]["attn_norm"][0]) == 1.0  # every scale starts at 1 ...
    params, opt, loss = lm.make_train_step(cfg)(params, opt, (tokens, jnp.roll(tokens, -1, -1)))
    assert jax.tree_util.tree_structure((params, opt)) == before
    assert np.isfinite(float(loss))
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves((params, opt))} == {
        "bfloat16", "float32", "int32"}
    assert opt["router_load"].dtype == jnp.int32
    assert int(opt["router_load"].sum()) == 4 * ROWS * SEQ * cfg.num_experts_per_token
    # ... where a 1e-6 step cannot move a bfloat16 scale, and moves its master copy
    assert float(params["layers"][0]["attn_norm"][0]) == 1.0
    assert float(jnp.max(jnp.abs(opt["master"]["layers"][0]["attn_norm"] - 1.0))) > 0
    # embedding and head are leaves of their own, drawn at their own scales
    assert params["embed"].shape == (64, 32) and params["head"].shape == (32, 64)
    drawn = lm.init_params(CFG, jax.random.PRNGKey(1))
    assert float(jnp.std(drawn["embed"])) == pytest.approx(1.0, rel=0.1)
    assert float(jnp.std(drawn["head"])) == pytest.approx(1 / math.sqrt(32), rel=0.1)


# -- the share test: the shares of a layer add up to the uncut layer ---------------

@pytest.mark.parametrize("which", [0, 3], ids=["sliding-layer", "full-layer"])
def test_all_8_shares_add_up_to_the_uncut_references_whole_layer(which, params, hidden):
    """8 chips with 2 of 16 experts each, every value of ``expert_offset``;
    the attention, which every chip computes alike, counted once."""
    kind = CFG.layer_types[which]
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = dict(params["layers"][which])
    p["moe"] = jax.jit(lambda k: lm.init_params(whole, k)["layers"][which]["moe"])(
        jax.random.PRNGKey(9))
    dims = dataclasses.replace(DIMS, expert_offset=0)
    uncut = jax.jit(lambda h: ref.layer(h, p, dims, kind)[0])

    @jax.jit
    def attention_once(h):
        u = lm._rmsnorm(h, p["attn_norm"], CFG.rms_norm_eps)
        h = h + lm.attn_block(u, p["attn"], CFG, kind)
        return h, lm._rmsnorm(h, p["ffn_norm"], CFG.rms_norm_eps).reshape(ROWS * SEQ, -1)

    @jax.jit
    def one_share(x, chip):  # one compilation: the offset is an argument
        share = dataclasses.replace(CFG, experts_held=2, expert_offset=2 * chip)
        mine = {k: jax.lax.dynamic_slice_in_dim(w, 2 * chip, 2)
                for k, w in p["moe"]["experts"].items()}
        return lm.moe_block(x, {**p["moe"], "experts": mine}, share)

    total, x = attention_once(hidden)
    loads, nonzero = [], 0
    for chip in range(8):
        out, load = one_share(x, chip)
        nonzero += float(jnp.max(jnp.abs(out))) > 0
        total = total + out.reshape(total.shape)
        loads.append(np.asarray(load))
    assert nonzero == 8
    close(total, uncut(hidden))
    assert all(np.array_equal(loads[0], load) for load in loads)  # every chip routes alike
    # and in the reference itself: its 8 held parts add up to its uncut layer's
    parts = sum(jax.jit(lambda x, chip: ref.moe(x, {**p["moe"], "experts": {
        k: jax.lax.dynamic_slice_in_dim(w, 2 * chip, 2) for k, w in p["moe"]["experts"].items()}},
        dataclasses.replace(DIMS, expert_offset=2 * chip))[0])(x, chip) for chip in range(8))
    close(parts, ref.moe(x, p["moe"], dims)[0])


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_8_vocabulary_slices_logits_are_the_rows_of_the_whole_heads(side, params, batch):
    logits = {"program": lambda w: lm.forward(w, batch[0], CFG)[0],
              "reference": lambda w: ref.logits_of(w, batch[0], DIMS)[0]}[side]
    whole = jax.jit(logits)(params)
    assert whole.shape == (ROWS, SEQ, 64)
    sliced = jax.jit(lambda w, s: logits(
        {**w, "head": jax.lax.dynamic_slice_in_dim(w["head"], 8 * s, 8, axis=1)}))
    for s in range(8):
        close(sliced(params, s), whole[..., 8 * s:8 * s + 8], tol=1e-6)


# -- spans and counters -------------------------------------------------------------------

def test_the_lowered_step_names_its_blocks_and_sets_the_windows_gauge(batch):
    from tpu_resiliency.telemetry import get_registry

    cfg = dataclasses.replace(CFG, sliding_window=3)
    params = jax.eval_shape(lambda k: lm.init_params(cfg, k), jax.random.PRNGKey(1))
    opt = jax.eval_shape(lambda p: lm.init_opt_state(p, cfg), params)
    text = lm.make_train_step(cfg).lower(params, opt, batch).as_text(debug_info=True)
    for scope in ("mellum.attn.window", "mellum.attn.full", "mellum.moe", "mellum.head"):
        assert f"{scope}/" in text or f"{scope})/" in text, scope
    assert "module @jit_step" in text  # the trace readers find ``jit_step``
    assert "stablehlo.while" not in text  # unrolled: no scan over layers, blocks or experts
    # a layer's switch between the two sizes of pair buffer, forward and backward
    assert text.count("stablehlo.case") == 2 * len(cfg.layer_types)
    # when the step is built: 20 positions, blocks of 8, a window of 3 -> 5 of 6 key blocks
    snapshot = get_registry().snapshot()
    assert snapshot["tpurx_model_window_key_blocks"]["samples"][0]["value"] == pytest.approx(5 / 6)


def test_routing_stats_reads_the_state_and_sets_its_gauges():
    from tpu_resiliency.telemetry import get_registry

    load = np.zeros((4, 16), np.int32)
    load[:, 4:6] = [[10, 10], [30, 10], [5, 5], [20, 20]]
    load[:, 0] = [140, 120, 70, 120]    # an expert held elsewhere takes the rest
    stats = lm.routing_stats({"router_load": load}, CFG)
    assert stats["max"] == 30.0 and stats["mean"] == pytest.approx(110 / 8)
    assert stats["share"] == pytest.approx(110 / 560)
    assert stats["held_share_min"] == pytest.approx(0.125)   # 20 of 160; 10 of 80
    snapshot = get_registry().snapshot()
    assert snapshot["tpurx_model_held_share_min"]["samples"][0]["value"] == pytest.approx(0.125)
    assert snapshot["tpurx_model_expert_load_max"]["samples"][0]["value"] == 30.0
    assert snapshot["tpurx_model_expert_load_mean"]["samples"][0]["value"] == pytest.approx(13.75)
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
def test_the_state_with_its_buffer_round_trips_with_an_equal_fingerprint(
        rung, tmp_path, fingerprint):
    """After two steps (the load is no longer 0): through ``async_save`` and
    ``load_checkpoint``; from the sealed ring slot (snapshot mode through a
    ring of two, which the CPU default ``sync`` does not keep) and, read past
    both warm rungs, from disk."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.checkpointing.async_ckpt import resident

    cfg, params, opt = bfloat16_state()
    step = lm.make_train_step(cfg)
    for b in feed_of(cfg, 2):
        params, opt, _ = step(params, opt, b)
    assert int(opt["router_load"].sum()) > 0
    tree = {"params": params, "opt": opt}
    want = np.asarray(fingerprint(tree))
    assert want.shape == (4 * N_LEAVES + 2, 2)
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
    no-fault run's, bit for bit, and so does the load at the end."""
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
        group="mellum", soft_timeout=3600.0, hard_timeout=7200.0,
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
    with open(os.path.join(ROOT, "tpu_resiliency/models/mellum_reference.py"), "rb") as f:
        ours = f.read()
    with open(os.path.join(ROOT, "chipbench/reference/mellum.py"), "rb") as f:
        assert f.read() == ours
    assert b"tpu_resiliency" not in ours.split(b'"""')[2]  # it imports nothing of the product


def test_the_benchmarks_reference_gives_equal_numbers(params, batch):
    sys.path.insert(0, ROOT)
    from chipbench.reference import mellum as copy

    ours = jax.jit(lambda p: ref.loss_of(p, *batch, DIMS))(params)
    theirs = jax.jit(lambda p: copy.loss_of(
        p, *batch, copy.Dims(**dataclasses.asdict(DIMS))))(params)
    assert float(ours[0]) == float(theirs[0])
    assert np.array_equal(ours[1], theirs[1])


def test_the_family_module_imports_no_jax_and_nothing_of_the_product():
    import subprocess

    done = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]); "
         "from chipbench import families; "
         "family, sizes = families.of_file(sys.argv[2]); "
         "family.train_flops_per_token(sizes); "
         "print(sorted(m for m in sys.modules "
         "if m.split('.')[0] in ('jax', 'jaxlib', 'tpu_resiliency')))",
         ROOT, CELL_CONFIG], capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "[]"


def test_the_cells_counts_from_shapes_nothing_allocated():
    sys.path.insert(0, ROOT)
    from chipbench import families, weights

    family, sizes = families.of_file(CELL_CONFIG)
    assert sizes.n_params == 340_350_208 and sizes.tokens_per_step == 8192
    assert sizes.attn_matmul_params == 21_233_664 and sizes.expert_params == 6_193_152
    assert sizes.state_bytes == 4_764_903_940  # 14 B a parameter, 1,024 B of load, the count
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(weights.make_state_fn(family, sizes), key)
    leaves = jax.tree_util.tree_leaves(state)
    assert sum(x.size * x.dtype.itemsize for x in leaves) == sizes.state_bytes
    assert len(leaves) == 206  # 51 trained leaves x 4, the count, the load
    assert {str(x.dtype) for x in leaves} == {"bfloat16", "float32", "int32"}
    draw = jax.eval_shape(lambda k: family.draw_params(sizes, k, jnp.bfloat16), key)
    assert sum(x.size for x in jax.tree_util.tree_leaves(draw)) == sizes.n_params
    assert draw["embed"].shape == (12288, 2304) and draw["head"].shape == (2304, 12288)
    # an average query sees 960 keys in a sliding layer and 4,096.5 in the full one
    assert sizes.keys_seen("sliding_attention") == pytest.approx(960.06, abs=0.01)
    assert sizes.keys_seen("full_attention") == 4096.5
    # a token's forward pass: 391.5 MFLOP (attention's projections 170, its scores
    # and values 114, head 57, held experts 50, routers 1); 9.62 TFLOP a step
    assert family.forward_flops_per_token(sizes) == pytest.approx(391.52e6, rel=1e-4)
    assert family.train_flops_per_token(sizes) * sizes.tokens_per_step == pytest.approx(
        9.622e12, rel=0.001)
    assert family.CONTROLS == ("bf16_everywhere", "half_batch", "state_unchanged")
    cfg = family.model_config(sizes)
    assert lm.window_key_blocks(sizes.seq, cfg.attn_block, cfg.sliding_window) == (45, 136)
    assert dataclasses.replace(cfg, dtype=None) == lm.MellumConfig()  # the defaults ARE the cell
    assert dataclasses.asdict(family.reference_dims(sizes)) == dataclasses.asdict(ref.Dims())
    # the widths are the source's; only depth, the experts held and the vocabulary are cut
    with open(CELL_CONFIG) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[cfg["name"]]
    assert list(cfg["reduced"]) == entry["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    assert cfg["model_type"] == "mellum" and entry["source"] == cfg["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["sliding_window"]) == (2304, 32, 4, 128, 1024)
    assert (cfg["moe_intermediate_size"], cfg["deployment"]["experts"],
            cfg["num_experts_per_tok"], cfg["intermediate_size"]) == (896, 64, 8, 7168)
    assert cfg["rope_parameters"] == {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32,
                           "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 8, 12288)
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["published"]["layer_types"] == cfg["layer_types"] * 7
    assert cfg["mlp_layer_types"] == ["sparse"] * 28
    assert cfg["rms_norm_eps"] == 1e-6 and cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert cfg["tie_word_embeddings"] is False and cfg["attention_bias"] is False
    cut = cfg["cpu_rehearsal_cut"]  # the band's edges fall inside blocks
    assert cut["sliding_window"] < cut["batch"]["seq"] and cut["sliding_window"] % cut["attn_block"]
    fits = cfg["compiled_for_v5e"]
    assert abs(fits["state_on_device_bytes"] - 4.77e9) < 0.01 * 4.77e9
    assert (2 * fits["state_on_device_bytes"] + fits["train_step"]["temp_bytes"]
            + fits["other_resident_bytes"]) <= 16.9e9
    # the cell is on every list the sixth cell is on, right after it
    cell, sixth = "mellum2-12b-a2.5b-1chip.stall-inproc", "lfm2-8b-a1b-1chip.stall-inproc"
    mine = {w["name"]: w for w in bench["workloads"]}[cell]
    assert (mine["config"], mine["traffic"], mine["chips"]) == (cfg["name"], "stall-inproc", 1)
    lists = [m["workloads"] for m in bench["end_to_end"] + bench["per_layer"]
             if sixth in m.get("workloads", [])]
    assert len(lists) == 19 and all(
        names[names.index(sixth) + 1] == cell for names in lists)
