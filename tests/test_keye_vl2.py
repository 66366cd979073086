"""The fourth reference workload (``tpu_resiliency/models/keye_vl2.py``)
against its plain reference (``keye_vl2_reference.py``): seeded random
weights, tiny sizes with ``topk`` smaller than the sequence, CPU, the program
in float32 against the float32 reference.  The selection against
``jax.lax.top_k`` and against a per-query gather written out in numpy, the
layer with ``topk >= T`` against plain grouped-query attention, each block
forward and gradients, the whole model's loss and every leaf's gradient, the
two gradient paths leaf by leaf (exact zeros), three train steps with the
buffers, the share test, the state through ``async_save`` / ``load_checkpoint``
and the sealed ring slot, a recovery under ``Wrapper`` that continues the
no-fault losses bit for bit, the benchmark's copy of the reference, and the
cell's counts from shapes.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_CONFIG = os.path.join(ROOT, "chipbench", "configs", "keye-vl-2.0-30b-a3b-1chip.json")

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_resiliency.models import keye_vl2 as kv  # noqa: E402
from tpu_resiliency.models import keye_vl2_reference as ref  # noqa: E402
from tpu_resiliency.models import kimi_linear, qwen3_next  # noqa: E402

# 8 query heads on 2 key/value heads (query head j reads head j // 4), 4 index
# heads against one index key head, 6 keys a query of up to 20: positions 6-19
# choose, blocks of 8 queries (so the second and third block select)
CFG = kv.KeyeVL2Config(
    hidden_size=32, num_layers=2, num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    indexer_num_heads=4, indexer_head_dim=4, index_topk=6, moe_intermediate_size=16,
    num_experts=16, experts_held=4, expert_offset=4, num_experts_per_token=3, vocab_rows=64,
    attn_block=8, dtype=jnp.float32)
DIMS = ref.Dims(index_topk=6, experts_per_token=3, expert_offset=4, query_block=8)
ROWS, SEQ = 2, 20  # no multiple of the block of queries
N_LEAVES = 37  # a layer: 2 norms, 6 of attention, 5 of the indexer, 4 of the expert layer; + 3
INDEXER = "['indexer']"


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
    return jax.jit(lambda key: kv.init_params(CFG, key))  # compiled once for the file


@pytest.fixture(scope="module")
def params(draw):
    """The draw with every vector moved off its start (norm scales are drawn
    1 and the LayerNorm's bias 0, where a missing scale or bias would not show)."""
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


# -- the selection ---------------------------------------------------------------------

def top_k_mask(scores, seen, k):
    """The set ``jax.lax.top_k`` returns over the seen positions, as a mask."""
    _, where = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, np.asarray(where), True, axis=-1)
    return mask & np.asarray(seen)


SCORES = {
    "distinct": lambda z: z,
    "many-ties": lambda z: jnp.round(2 * z) / 2,
    "all-equal": lambda z: jnp.zeros_like(z),
    "signed-zeros": lambda z: jnp.where(jnp.abs(z) < 0.8, jnp.sign(z) * 0.0, z),
    "huge-and-tiny": lambda z: jnp.where(z > 0, z * 1e30, z * 1e-40),
}


@pytest.mark.parametrize("kind", sorted(SCORES))
def test_select_keys_is_top_k_with_ties_to_the_lower_position(kind):
    z = SCORES[kind](jax.random.normal(jax.random.PRNGKey(4), (3, 8, 20)))
    z = jnp.where(z == 0, 0.0, z)  # as ``index_scores`` hands them over
    seen = jnp.arange(12, 20)[:, None] >= jnp.arange(20)[None, :]
    got = jax.jit(lambda z: kv.select_keys(z, seen, 6))(z)
    assert np.array_equal(got, top_k_mask(z, seen, 6))
    assert np.array_equal(np.sum(got, axis=-1), np.full((3, 8), 6))
    assert np.array_equal(got, ref.selected(z, seen, 6))


def test_select_keys_takes_every_seen_key_while_they_are_no_more_than_k():
    z = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 8))
    seen = jnp.tril(jnp.ones((8, 8), bool))
    got = kv.select_keys(z, seen, 6)
    assert np.array_equal(got[:, :6], np.broadcast_to(seen[:6], (2, 6, 8)))
    assert np.array_equal(np.sum(got, axis=-1),
                          np.broadcast_to(np.minimum(np.arange(1, 9), 6), (2, 8)))
    assert np.array_equal(got, ref.selected(z, seen, 6))


def per_query_gather(x, p, p_idx, topk):
    """The layer's attention for one row ``x`` [T, d], written out in numpy
    query by query: the index scores of the positions the query sees, the
    ``topk`` largest by a stable sort (ties to the lower position), a GATHER
    of those keys and values, a softmax over them head by head.  Returns the
    output, the chosen sets and the KL."""
    p, p_idx = jax.tree_util.tree_map(lambda z: np.asarray(z, np.float64), (p, p_idx))
    x = np.asarray(x, np.float64)
    t, dh, di = x.shape[0], 8, 4

    def norm(z, g):
        return z / np.sqrt(np.mean(z * z, -1, keepdims=True) + 1e-6) * g

    def turn(z):  # [T, heads, width] rotated as two halves over the whole width
        width = z.shape[-1]
        inv = 1e7 ** (-np.arange(0, width, 2) / width)
        angle = np.arange(t)[:, None] * inv[None, :]
        angle = np.concatenate([angle, angle], -1)[:, None, :]
        half = np.concatenate([-z[..., width // 2:], z[..., :width // 2]], -1)
        return z * np.cos(angle) + half * np.sin(angle)

    q = turn(norm((x @ p["q_proj"]).reshape(t, 8, dh), p["q_norm"]))
    k = turn(norm((x @ p["k_proj"]).reshape(t, 2, dh), p["k_norm"]))
    v = (x @ p["v_proj"]).reshape(t, 2, dh)
    qi = turn((x @ p_idx["q_proj"]).reshape(t, 4, di))
    ki = x @ p_idx["k_proj"]
    ki = ki - ki.mean(-1, keepdims=True)
    ki = ki / np.sqrt(np.mean(ki * ki, -1, keepdims=True) + 1e-6)
    ki = turn((ki * p_idx["k_norm"] + p_idx["k_norm_bias"])[:, None, :])[:, 0]
    wi = x @ p_idx["w_proj"] / np.sqrt(4 * di)
    out, sets, kls = np.zeros((t, 8 * dh)), [], []
    for i in range(t):
        index = np.array([sum(wi[i, j] * max(qi[i, j] @ ki[s], 0.0) for j in range(4))
                          for s in range(i + 1)])
        chosen = np.sort(np.argsort(-index, kind="stable")[:topk])
        sets.append(chosen)
        heads = np.zeros(len(chosen))
        for j in range(8):
            scores = np.array([q[i, j] @ k[s, j // 4] for s in chosen]) / np.sqrt(dh)
            probs = np.exp(scores - scores.max())
            probs /= probs.sum()
            out[i, j * dh:(j + 1) * dh] = probs @ v[chosen, j // 4]
            heads += probs / 8
        guess = np.exp(index[chosen] - index[chosen].max())
        guess /= guess.sum()
        kls.append(float(np.sum(heads * np.log(heads / guess))))
    return out @ p["o_proj"], sets, np.array(kls)


@pytest.fixture(scope="module")
def gathered(params, hidden):
    p = params["layers"][0]
    return per_query_gather(hidden[0], p["attn"], p["indexer"], CFG.index_topk)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_attention_is_a_per_query_gather_of_the_selected_keys(side, gathered, params, hidden):
    p = params["layers"][0]
    want, sets, kls = gathered
    assert [len(s) for s in sets] == [min(i + 1, 6) for i in range(SEQ)]
    assert any(list(s) != list(range(i - 5, i + 1)) for i, s in enumerate(sets) if i >= 6)
    block = {"program": lambda x: kv.attn_block(x, p["attn"], p["indexer"], CFG),
             "reference": lambda x: ref.attention(x, p["attn"], p["indexer"], DIMS)}[side]
    out, kl, _mass = jax.jit(block)(hidden[:1])
    close(out[0], want)
    close(kl[0], kls, tol=1e-3)


def test_the_programs_chosen_sets_are_the_gathers(gathered, params, hidden):
    p = params["layers"][0]["indexer"]
    q, k, w = kv.indexer_inputs(hidden[:1], p, CFG)
    seen = jnp.tril(jnp.ones((SEQ, SEQ), bool))
    chosen = kv.select_keys(kv.index_scores(q, k, w), seen, CFG.index_topk)[0]
    assert [np.flatnonzero(row).tolist() for row in np.asarray(chosen)] == [
        s.tolist() for s in gathered[1]]


def test_with_topk_at_least_T_the_layer_is_plain_grouped_query_attention(params, hidden):
    """No selection, no indexer in the output: the reference family's plain
    causal attention over the same q, k, v; the selected mass is then 1."""
    whole = dataclasses.replace(CFG, index_topk=SEQ)
    p = params["layers"][1]
    out, _kl, mass = jax.jit(lambda x: kv.attn_block(x, p["attn"], p["indexer"], whole))(hidden)
    a = p["attn"]
    rows, t, dh = ROWS, SEQ, CFG.head_dim
    q = kv._rotate(kv._norm((hidden @ a["q_proj"]).reshape(rows, t, 8, dh), a["q_norm"], 1e-6), CFG)
    k = kv._rotate(kv._norm((hidden @ a["k_proj"]).reshape(rows, t, 2, dh), a["k_norm"], 1e-6), CFG)
    v = (hidden @ a["v_proj"]).reshape(rows, t, 2, dh)
    plain = qwen3_next.causal_attention_in_blocks(q.reshape(rows, t, 2, 4, dh), k, v, block=t)
    close(out, plain.reshape(rows, t, 8 * dh) @ a["o_proj"])
    close(mass, jnp.ones_like(mass), tol=1e-5)
    other = jax.tree_util.tree_map(lambda z: z * 1.5 + 0.1, p["indexer"])
    again, _, _ = jax.jit(lambda x: kv.attn_block(x, a, other, whole))(hidden)
    assert np.array_equal(out, again)  # another indexer, the same layer


# -- each block against the reference, forward and gradients -----------------------

def test_attention_forward_losses_and_gradients_match_the_reference(params, hidden):
    p = params["layers"][0]
    weigh = jax.random.normal(jax.random.PRNGKey(7), hidden.shape)

    def out_and_grads(block):
        def total(x, a, i):
            out, kl, _ = block(x, a, i)
            return jnp.sum(out * weigh) + jnp.sum(kl)

        return jax.jit(lambda x, a, i: (block(x, a, i), jax.grad(total, argnums=(0, 1, 2))(
            x, a, i)))(hidden, p["attn"], p["indexer"])

    (out, kl, mass), got = out_and_grads(lambda x, a, i: kv.attn_block(x, a, i, CFG))
    (wanted, want_kl, want_mass), want = out_and_grads(
        lambda x, a, i: ref.attention(x, a, i, DIMS))
    close(out, wanted)
    close(kl, want_kl)
    close(jnp.mean(mass, axis=(1, 2)), want_mass)
    assert 0 < float(jnp.min(mass)) < 0.5 and float(jnp.max(mass)) == pytest.approx(1.0)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0
        close(g, w)


def test_expert_layer_forward_load_and_gradients_match_the_reference(params, hidden):
    p, x = params["layers"][1]["moe"], hidden.reshape(ROWS * SEQ, -1)
    weigh = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def out_and_grads(layer):
        return jax.jit(lambda x, p: (layer(x, p), jax.grad(
            lambda x, p: jnp.sum(layer(x, p)[0] * weigh), argnums=(0, 1))(x, p)))(x, p)

    (out, load), got = out_and_grads(lambda x, p: kv.moe_block(x, p, CFG))
    (wanted, want_load), want = out_and_grads(lambda x, p: ref.moe(x, p, DIMS))
    close(out, wanted)
    assert np.array_equal(load, want_load)
    assert int(load.sum()) == ROWS * SEQ * CFG.num_experts_per_token
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        close(g, w)
    # no held expert chosen, and no shared expert: nothing is left
    nobody = dataclasses.replace(CFG, expert_offset=CFG.num_experts)
    assert float(jnp.max(jnp.abs(kv.moe_block(x, p, nobody)[0]))) == 0.0


def test_the_router_and_the_rotation_are_the_third_models_by_import():
    assert kv.route is qwen3_next.route and kv._rope is qwen3_next._rope
    assert kv.held_experts is kimi_linear.held_experts
    assert kv.next_token_loss is kimi_linear.next_token_loss


# -- the whole model: loss, buffers and every leaf's gradient ----------------------

@pytest.fixture(scope="module")
def model_grads(params, batch):
    got = jax.jit(jax.value_and_grad(
        lambda p: kv.loss_fn(p, batch, CFG), has_aux=True))(params)
    want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_of(p, *batch, DIMS), has_aux=True))(params)
    return got, want


def test_model_loss_and_buffers_match_the_reference(model_grads):
    ((loss, found), _), ((want, wanted), _) = model_grads
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    assert float(loss) == float(found["lm_loss"] + jnp.mean(found["index_kl"]))
    assert found["router_load"].shape == (CFG.num_layers, CFG.num_experts)
    assert np.array_equal(found["router_load"], wanted["router_load"])
    for name in ("lm_loss", "index_kl", "selected_mass"):
        close(found[name], wanted[name], tol=1e-5)
    assert float(jnp.min(found["index_kl"])) > 0


@pytest.mark.parametrize("leaf", range(N_LEAVES))
def test_every_leafs_gradient_matches_the_reference(leaf, model_grads, params):
    (_, grads), (_, wanted) = model_grads
    names = leaf_names(params)
    assert len(names) == N_LEAVES
    got = jax.tree_util.tree_leaves(grads)[leaf]
    want = jax.tree_util.tree_leaves(wanted)[leaf]
    assert float(jnp.max(jnp.abs(want))) > 0, names[leaf]
    close(got, want, tol=5e-4)


# -- the two gradient paths ------------------------------------------------------------

@pytest.fixture(scope="module")
def path_grads(params, batch):
    """Gradients of the two losses apart, program and reference."""
    def apart(loss_and_parts):
        lm = jax.grad(lambda p: loss_and_parts(p)[1]["lm_loss"])
        kl = jax.grad(lambda p: jnp.mean(loss_and_parts(p)[1]["index_kl"]))
        return jax.jit(lambda p: (lm(p), kl(p)))(params)

    return {"program": apart(lambda p: kv.loss_fn(p, batch, CFG)),
            "reference": apart(lambda p: ref.loss_of(p, *batch, DIMS))}


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("leaf", range(N_LEAVES))
def test_each_leaf_gets_the_gradient_of_one_loss_and_exactly_zero_of_the_other(
        leaf, side, path_grads, model_grads, params):
    """d L_LM / d(an indexer leaf) and d L_I / d(any other leaf) are 0.0, not
    small: the indexer reads a detached input and the selection is discrete."""
    name = leaf_names(params)[leaf]
    lm, kl = (jax.tree_util.tree_leaves(g)[leaf] for g in path_grads[side])
    mine, other = (kl, lm) if INDEXER in name else (lm, kl)
    assert float(jnp.max(jnp.abs(other))) == 0.0, name
    assert float(jnp.max(jnp.abs(mine))) > 0.0, name
    # and the step's one backward pass of the sum hands each leaf its own
    both = jax.tree_util.tree_leaves(model_grads[0 if side == "program" else 1][1])[leaf]
    close(both, mine, tol=1e-6)


def test_there_are_five_indexer_leaves_a_layer(params):
    names = [n for n in leaf_names(params) if INDEXER in n]
    assert len(names) == 5 * CFG.num_layers
    assert {n.split("]")[-2] + "]" for n in names} == {
        "['q_proj']", "['k_proj']", "['w_proj']", "['k_norm']", "['k_norm_bias']"}


# -- three steps of the train step against the reference's AdamW -------------------

def test_three_train_steps_follow_the_reference_with_their_buffers(draw):
    start, live = draw(jax.random.PRNGKey(1)), draw(jax.random.PRNGKey(1))  # the step donates
    feed = []
    for i in range(3):
        tokens = jax.random.randint(jax.random.PRNGKey(20 + i), (ROWS, SEQ), 0, CFG.vocab_rows)
        feed.append((tokens, jnp.roll(tokens, -1, axis=-1)))
    opt = jax.jit(lambda p: kv.init_opt_state(p, CFG))(live)
    assert jax.tree_util.tree_leaves(opt["master"]) == []  # float32 leaves need none
    step = kv.make_train_step(CFG)
    norms = jax.jit(lambda tree: jnp.stack(
        [jnp.linalg.norm(x) for x in jax.tree_util.tree_leaves(tree)]))
    losses, found, first_grad = [], {name: [] for name in kv.BUFFERS}, None
    for b in feed:
        live, opt, loss = step(live, opt, b)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = norms(opt["mu"]) / (1 - 0.9)
        for name in kv.BUFFERS:
            found[name].append(np.asarray(opt[name]).tolist())
    assert step._cache_size() == 1  # one compilation over batches of different routing
    want = ref.first_steps(start, feed, DIMS, n_steps=3)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-4)
    np.testing.assert_allclose(first_grad, want["grad_norm"], rtol=2e-3)
    assert found["router_load"] == want["router_load"]
    np.testing.assert_allclose(found["index_kl"], want["index_kl"], rtol=2e-3)
    np.testing.assert_allclose(found["selected_mass"], want["selected_mass"], rtol=2e-3)
    assert int(opt["count"]) == 3
    change = norms(jax.tree_util.tree_map(lambda a, b: a - b, live, start))
    np.testing.assert_allclose(change, want["change_norm"], rtol=0.02)
    assert bool(jnp.all(norms(opt["mu"]) > 0))  # the indexer's leaves move too


def test_a_bfloat16_tree_has_a_master_copy_a_leaf_and_three_buffers_no_gradient_touches():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.jit(lambda k: kv.init_params(cfg, k))(jax.random.PRNGKey(1))
    opt = jax.jit(lambda p: kv.init_opt_state(p, cfg))(params)
    assert len(jax.tree_util.tree_leaves(opt["master"])) == N_LEAVES
    assert len(jax.tree_util.tree_leaves((params, opt))) == 4 * N_LEAVES + 1 + 3
    assert set(opt) == {"mu", "nu", "count", "master", *kv.BUFFERS}
    tokens = jax.random.randint(jax.random.PRNGKey(2), (ROWS, SEQ), 0, cfg.vocab_rows)
    before = jax.tree_util.tree_structure((params, opt))
    assert float(params["layers"][0]["attn_norm"][0]) == 1.0  # every scale starts at 1 ...
    assert float(jnp.max(jnp.abs(params["layers"][0]["indexer"]["k_norm_bias"]))) == 0.0
    params, opt, loss = kv.make_train_step(cfg)(params, opt, (tokens, jnp.roll(tokens, -1, -1)))
    assert jax.tree_util.tree_structure((params, opt)) == before
    assert np.isfinite(float(loss))
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves((params, opt))} == {
        "bfloat16", "float32", "int32"}
    assert opt["router_load"].dtype == jnp.int32
    assert int(opt["router_load"].sum()) == 2 * ROWS * SEQ * cfg.num_experts_per_token
    assert opt["index_kl"].dtype == opt["selected_mass"].dtype == jnp.float32
    assert opt["index_kl"].shape == opt["selected_mass"].shape == (cfg.num_layers,)
    # ... where a 1e-3 step cannot move a bfloat16 scale, and moves its master copy
    assert float(params["layers"][0]["attn_norm"][0]) == 1.0
    assert float(jnp.max(jnp.abs(opt["master"]["layers"][0]["attn_norm"] - 1.0))) > 0


# -- the share test: the shares of a layer add up to the uncut layer ---------------

def test_all_16_shares_add_up_to_the_uncut_references_whole_layer(params, hidden):
    """16 chips with one of 16 experts each; attention and indexer, which
    every chip computes alike, counted once."""
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = dict(params["layers"][1])
    p["moe"] = jax.jit(lambda k: kv.init_params(whole, k)["layers"][1]["moe"])(
        jax.random.PRNGKey(9))
    dims = dataclasses.replace(DIMS, expert_offset=0)

    @jax.jit
    def uncut(h):
        h = h + ref.attention(ref.norm(h, p["attn_norm"], 1e-6), p["attn"], p["indexer"], dims)[0]
        x = ref.norm(h, p["ffn_norm"], 1e-6).reshape(ROWS * SEQ, -1)
        return h + ref.moe(x, p["moe"], dims)[0].reshape(h.shape)

    @jax.jit
    def attention_once(h):
        u = kv._norm(h, p["attn_norm"], CFG.rms_norm_eps)
        h = h + kv.attn_block(u, p["attn"], p["indexer"], CFG)[0]
        return h, kv._norm(h, p["ffn_norm"], CFG.rms_norm_eps).reshape(ROWS * SEQ, -1)

    @jax.jit
    def one_share(x, chip):  # one compilation: the offset is an argument
        share = dataclasses.replace(CFG, experts_held=1, expert_offset=chip)
        mine = {k: jax.lax.dynamic_slice_in_dim(w, chip, 1) for k, w in p["moe"]["experts"].items()}
        return kv.moe_block(x, {**p["moe"], "experts": mine}, share)

    total, x = attention_once(hidden)
    loads = []
    for chip in range(16):
        out, load = one_share(x, chip)
        assert float(jnp.max(jnp.abs(out))) > 0  # every expert got a token
        total = total + out.reshape(total.shape)
        loads.append(np.asarray(load))
    close(total, uncut(hidden))
    assert all(np.array_equal(loads[0], load) for load in loads)  # every chip routes alike


def test_the_8_slices_of_the_vocabulary_concatenate_to_the_uncut_logits(params, batch):
    uncut, _ = jax.jit(lambda p: ref.logits_of(p, batch[0], DIMS))(params)
    one_slice = jax.jit(lambda p, head: kv.forward({**p, "head": head}, batch[0], CFG)[0])
    slices = [one_slice(params, params["head"][:, lo:lo + 8])
              for lo in range(0, CFG.vocab_rows, 8)]
    assert len(slices) == 8
    close(jnp.concatenate(slices, axis=-1), uncut)


# -- spans and counters -------------------------------------------------------------------

def test_the_lowered_step_names_its_blocks(batch):
    params = jax.eval_shape(lambda k: kv.init_params(CFG, k), jax.random.PRNGKey(1))
    opt = jax.eval_shape(lambda p: kv.init_opt_state(p, CFG), params)
    text = kv.make_train_step(CFG).lower(params, opt, batch).as_text(debug_info=True)
    for scope in ("attn.index", "attn.select", "attn.sparse", "index.loss", "moe.route",
                  "moe.experts", "head.loss"):
        # under jit(step) and jvp; inside the scan under its checkpoint
        assert f"{scope}/" in text or f"jvp({scope})/" in text, scope
    assert "module @jit_step" in text  # the trace readers find ``jit_step``
    # the layers are one scan forward and one backward, whatever their number; the
    # only other loops are the selection's bisections: one a block of queries forward,
    # one more when the layer's backward pass computes the layer's elementwise part
    # again, and none when a block is then computed again (its chosen set is kept)
    blocks = -(-SEQ // CFG.attn_block)
    assert text.count("stablehlo.while") == 2 + 2 * blocks
    deeper = dataclasses.replace(CFG, num_layers=4)
    params = jax.eval_shape(lambda k: kv.init_params(deeper, k), jax.random.PRNGKey(1))
    opt = jax.eval_shape(lambda p: kv.init_opt_state(p, deeper), params)
    again = kv.make_train_step(deeper).lower(params, opt, batch).as_text()
    assert again.count("stablehlo.while") == 2 + 2 * blocks


def test_selection_stats_reads_the_state_and_sets_its_gauges():
    from tpu_resiliency.telemetry import get_registry

    assert kv.routing_stats is kimi_linear.routing_stats  # the load gauges are declared once
    opt = {"index_kl": np.array([0.5, 1.5], np.float32),
           "selected_mass": np.array([0.75, 0.5], np.float32)}
    assert kv.selection_stats(opt, CFG) == {"index_kl": 1.0, "selected_mass_min": 0.5}
    snapshot = get_registry().snapshot()
    assert snapshot["tpurx_model_index_kl"]["samples"][0]["value"] == 1.0
    assert snapshot["tpurx_model_selected_mass_min"]["samples"][0]["value"] == 0.5


# -- the state through the checkpoint paths and the wrapper -----------------------------

def bfloat16_state(seed=1):
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.jit(lambda k: kv.init_params(cfg, k))(jax.random.PRNGKey(seed))
    return cfg, params, jax.jit(lambda p: kv.init_opt_state(p, cfg))(params)


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
    """After two steps (the buffers are no longer 0): through ``async_save``
    and ``load_checkpoint``; from the sealed ring slot (snapshot mode through
    a ring of two, which the CPU default ``sync`` does not keep) and, read
    past both warm rungs, from disk."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.checkpointing.async_ckpt import resident

    cfg, params, opt = bfloat16_state()
    step = kv.make_train_step(cfg)
    for b in feed_of(cfg, 2):
        params, opt, _ = step(params, opt, b)
    assert float(jnp.min(opt["index_kl"])) > 0 and int(opt["router_load"].sum()) > 0
    tree = {"params": params, "opt": opt}
    want = np.asarray(fingerprint(tree))
    assert want.shape == (4 * N_LEAVES + 4, 2)
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
    for name in kv.BUFFERS:
        assert np.array_equal(back["opt"][name], opt[name])
        assert back["opt"][name].dtype == opt[name].dtype


def test_a_recovery_under_the_wrapper_continues_the_no_fault_losses_bit_for_bit(
        store_server, tmp_path):
    """Six steps without a fault; then the same under ``Wrapper``: a save
    after step 2, an exception after step 4, and the re-entered function
    restores the save and runs steps 3-6 again: every loss equals the
    no-fault run's, bit for bit, and so do the buffers at the end."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.inprocess import Wrapper
    from tpu_resiliency.store import StoreClient

    cfg, params, opt = bfloat16_state(seed=3)
    step, feed = kv.make_train_step(cfg), feed_of(cfg, 6)
    wanted = []
    for b in feed:
        params, opt, loss = step(params, opt, b)
        wanted.append(np.float32(loss).tobytes())
    end = {name: np.asarray(opt[name]) for name in kv.BUFFERS}

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
        return {name: np.asarray(opt[name]) for name in kv.BUFFERS}

    wrapper = Wrapper(
        store_factory=lambda: StoreClient("127.0.0.1", store_server.port, timeout=10.0),
        group="keye-vl2", soft_timeout=3600.0, hard_timeout=7200.0,
        enable_monitor_process=False, enable_sibling_monitor=False)
    try:
        found = wrapper(train)()
    finally:
        cp.close()
    assert seen["entries"] == 2
    assert [len(seen["losses"][i]) for i in range(6)] == [1, 1, 1, 2, 2, 1]
    for i in range(6):
        assert set(seen["losses"][i]) == {wanted[i]}, i
    for name in kv.BUFFERS:
        assert np.array_equal(found[name], end[name])


# -- the benchmark's copy, and the cell's counts --------------------------------------------

def test_the_benchmarks_reference_is_this_repositorys_byte_for_byte():
    with open(os.path.join(ROOT, "tpu_resiliency/models/keye_vl2_reference.py"), "rb") as f:
        ours = f.read()
    with open(os.path.join(ROOT, "chipbench/reference/keye_vl2.py"), "rb") as f:
        assert f.read() == ours


def test_the_benchmarks_reference_gives_equal_numbers(params, batch):
    sys.path.insert(0, ROOT)
    from chipbench.reference import keye_vl2 as copy

    ours = jax.jit(lambda p: ref.loss_of(p, *batch, DIMS))(params)
    theirs = jax.jit(lambda p: copy.loss_of(
        p, *batch, copy.Dims(**dataclasses.asdict(DIMS))))(params)
    assert float(ours[0]) == float(theirs[0])
    for name in ours[1]:
        assert np.array_equal(ours[1][name], theirs[1][name])


def test_the_cells_counts_from_shapes_nothing_allocated():
    sys.path.insert(0, ROOT)
    from chipbench import families, weights

    family, sizes = families.of_file(CELL_CONFIG)
    assert sizes.layer_params == 59_150_720 and sizes.n_params == 373_546_880
    assert sizes.tokens_per_step == 4096
    assert sizes.state_bytes == 5_229_658_924  # 14 B a parameter, 2,600 B of buffers, the count
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(weights.make_state_fn(family, sizes), key)
    leaves = jax.tree_util.tree_leaves(state)
    assert sum(x.size * x.dtype.itemsize for x in leaves) == sizes.state_bytes
    assert len(leaves) == 356  # 88 trained leaves x 4, the count, three buffers
    assert {str(x.dtype) for x in leaves} == {"bfloat16", "float32", "int32"}
    draw = jax.eval_shape(lambda k: family.draw_params(sizes, k, jnp.bfloat16), key)
    assert sum(x.size for x in jax.tree_util.tree_leaves(draw)) == sizes.n_params
    # 1536.25 selected pairs a query in the mean (2304 in the dense causal
    # blocks the program multiplies): 5.7 TFLOP a step, 1.5 of them the
    # attention's scores and values and 0.26 the index scores
    assert sizes.selected_pairs_per_token == 1536.25
    assert family.train_flops_per_token(sizes) * sizes.tokens_per_step == pytest.approx(
        5.679e12, rel=0.001)
    # the widths are the source's; only depth, the experts held and the vocabulary are cut
    with open(CELL_CONFIG) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[cfg["name"]]
    assert set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"}
    assert cfg["model_type"] == "keye_vl2" and cfg["published"]["model_type"] == "KeyeVL2"
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["rope_theta"]) == (2048, 32, 4, 128, 10_000_000)
    assert cfg["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16,
                                "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                                "q_chunk_size": 512, "topk": 2048}
    assert (cfg["deployment"]["experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"]) == (128, 8, 768)
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (5, 8, 8, 18992)
    assert cfg["rms_norm_eps"] == 1e-6 and cfg["deployment"]["chips_sharing_a_layer"] == 16
    fits = cfg["compiled_for_v5e"]
    assert 2 * sizes.state_bytes + fits["train_step"]["temp_bytes"] <= 16.4e9
