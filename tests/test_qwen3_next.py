"""The third reference workload (``tpu_resiliency/models/qwen3_next.py``)
against its plain reference (``qwen3_next_reference.py``): seeded random
weights, tiny sizes, CPU, the program in float32 against the float32
reference.  The chunked scalar-gate scan against the token-by-token
recurrence, each block forward and gradients, the whole model's loss and every
leaf's gradient, three train steps with the load count, the share test (the
shares of the expert layer add up to the uncut layer, the slices of the
vocabulary to the uncut logits), no token dropped, one compilation over
batches of different routing, the reference with and without its per-block
checkpoint, the benchmark's copy of the reference, and the cell's counts from
shapes.
"""

import dataclasses
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_CONFIG = os.path.join(ROOT, "chipbench", "configs", "qwen3-next-80b-a3b-1chip.json")

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_resiliency.models import kimi_linear  # noqa: E402
from tpu_resiliency.models import qwen3_next as qn  # noqa: E402
from tpu_resiliency.models import qwen3_next_reference as ref  # noqa: E402

# 2 key heads serving 4 value heads; 16 query heads on 2 key/value heads, so
# that query head j reads head j // 8; a quarter of the head rotated
CFG = qn.Qwen3NextConfig(
    hidden_size=32, linear_num_key_heads=2, linear_num_value_heads=4, linear_head_dim=8,
    num_attention_heads=16, num_key_value_heads=2, head_dim=8, rotary_dim=2,
    moe_intermediate_size=16, shared_expert_intermediate_size=16, num_experts=32,
    experts_held=4, expert_offset=4, num_experts_per_token=3, vocab_rows=64,
    gdn_chunk=8, attn_block=8, dtype=jnp.float32,
    layer_kinds=("gdn", "attn"))  # one layer of each kind: half the compilation of a period
DIMS = ref.Dims(rotary_dim=2, experts_per_token=3, expert_offset=4, scan_block=8)
ROWS, SEQ = 2, 20  # neither a multiple of the chunk nor of the block of queries
N_LEAVES = 36  # 7 + 6 of the blocks, 2 norms and 8 of the expert layer a layer, + 3


@pytest.fixture(scope="module", autouse=True)
def quick_compilation():
    """Forty small programs are compiled here and none is timed: XLA's
    optimisation passes are a third of this file's minute."""
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
    return jax.jit(lambda key: qn.init_params(CFG, key))  # compiled once for the file


@pytest.fixture(scope="module")
def params(draw):
    """The draw with every vector moved off its start (the ``1 + w`` scales
    are drawn 0, where ``w`` and ``1 + w`` norms could not be told apart by
    their gradients' scale)."""
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


# -- the chunked scalar-gate scan against the token-by-token recurrence ------------

def delta_rule_inputs(seq, key=5):
    """2 key heads, 2 value heads a key head, width 8."""
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (ROWS, seq, 2, 8)))
    k = unit(jax.random.normal(ks[1], (ROWS, seq, 2, 8)))
    v = jax.random.normal(ks[2], (ROWS, seq, 2, 2, 8))
    g = -2.0 * jax.random.uniform(ks[3], (ROWS, seq, 2, 2))  # decays down to exp(-2) a token
    beta = jax.random.uniform(ks[4], (ROWS, seq, 2, 2))
    return q, k, v, g, beta


def token_by_token(q, k, v, g, beta):
    """The reference's recurrence over the chunked form's arguments: key head
    j serves value heads 2j and 2j + 1."""
    rows, seq = q.shape[:2]
    heads = lambda z: z.reshape(rows, seq, 4, *z.shape[4:])  # noqa: E731
    o = ref.delta_rule(jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), heads(v),
                       jnp.exp(heads(g)), heads(beta), DIMS)
    return o.reshape(v.shape)


@pytest.fixture(scope="module")
def recurrence_at_24():
    """The token-by-token recurrence and its gradients at 24 tokens, compiled
    once: it is causal, so with the weights of the outputs past token T at 0
    its first T tokens are the recurrence over T tokens, gradients included."""
    inputs = delta_rule_inputs(24)
    rule = jax.jit(lambda weigh, *a: (token_by_token(*a), jax.grad(
        lambda *a: jnp.sum(token_by_token(*a) * weigh), argnums=(0, 1, 2, 3, 4))(*a)))
    return inputs, lambda weigh: rule(weigh, *inputs)


@pytest.mark.parametrize("seq", [24, 20, 3], ids=lambda s: f"T{s}")
def test_gdn_chunked_is_the_token_by_token_recurrence(seq, recurrence_at_24):
    """Sequence lengths that are and are not multiples of the chunk (8)."""
    whole, recurrence = recurrence_at_24
    inputs = tuple(z[:, :seq] for z in whole)
    weigh = jax.random.normal(jax.random.PRNGKey(6), whole[2].shape)
    weigh = weigh * (jnp.arange(24) < seq)[None, :, None, None, None]
    rule = lambda *a: qn.gdn_chunked(*a, chunk=8)  # noqa: E731
    out, grads = jax.jit(lambda *a: (rule(*a), jax.grad(
        lambda *a: jnp.sum(rule(*a) * weigh[:, :seq]), argnums=(0, 1, 2, 3, 4))(*a)))(*inputs)
    want, wanted = recurrence(weigh)
    close(out, want[:, :seq])
    for got, want in zip(grads, wanted):
        close(got, want[:, :seq])
        assert seq == 24 or float(jnp.max(jnp.abs(want[:, seq:]))) == 0.0  # causal


def test_gdn_chunked_gives_each_value_head_its_own_decay_and_its_key_heads_keys():
    """Value head (j, i) computed alone, with key head j's q and k, is the
    joint result's slice: two value heads a key head share nothing else."""
    q, k, v, g, beta = delta_rule_inputs(16, key=15)
    rule = jax.jit(lambda *a: qn.gdn_chunked(*a, chunk=8))
    joint = rule(q, k, v, g, beta)
    for j in range(2):
        for i in range(2):
            alone = rule(q[:, :, j:j + 1], k[:, :, j:j + 1], v[:, :, j:j + 1, i:i + 1],
                         g[:, :, j:j + 1, i:i + 1], beta[:, :, j:j + 1, i:i + 1])
            close(alone[:, :, 0, 0], joint[:, :, j, i])


def test_gdn_chunked_stays_finite_under_decays_near_zero():
    """64 tokens of log-decay -8 a token: exp(+512) in any one-sided factor."""
    q, k, v, g, beta = delta_rule_inputs(64)
    g = jnp.full_like(g, -8.0)
    rule = lambda g: qn.gdn_chunked(q, k, v, g, beta, chunk=64)  # noqa: E731
    out, grad = jax.jit(lambda g: (rule(g), jax.grad(lambda g: jnp.sum(rule(g)))(g)))(g)
    assert bool(jnp.all(jnp.isfinite(out)))
    close(out, jax.jit(token_by_token)(q, k, v, g, beta))
    assert bool(jnp.all(jnp.isfinite(grad)))


def test_the_reference_with_and_without_its_block_checkpoint_gives_equal_gradients():
    q, k, v, g, beta = delta_rule_inputs(20, key=16)
    rows, seq = q.shape[:2]
    heads = lambda z: z.reshape(rows, seq, 4, *z.shape[4:])  # noqa: E731
    args = (jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), heads(v),
            jnp.exp(heads(g)), heads(beta))
    plain = dataclasses.replace(DIMS, checkpoint_blocks=False)

    def grads(dims):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.square(ref.delta_rule(*a, dims))),
            argnums=(0, 1, 2, 3, 4)))(*args)

    (with_loss, with_grads), (plain_loss, plain_grads) = grads(DIMS), grads(plain)
    assert float(with_loss) == float(plain_loss)
    for a, b in zip(with_grads, plain_grads):
        assert np.array_equal(a, b)


# -- each block against the reference, forward and gradients -----------------------

BLOCKS = {
    "gdn": (lambda x, p: qn.gdn_block(x, p, CFG), lambda x, p: ref.gdn(x, p, DIMS), 0),
    "attn": (lambda x, p: qn.attn_block(x, p, CFG), lambda x, p: ref.attn(x, p, DIMS), 1),
}


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_block_forward_and_gradients_match_the_reference(name, params, hidden):
    program, reference, layer = BLOCKS[name]
    p = params["layers"][layer][name]
    weigh = jax.random.normal(jax.random.PRNGKey(7), hidden.shape)

    def out_and_grads(block):
        return jax.jit(lambda x, p: (block(x, p), jax.grad(
            lambda x, p: jnp.sum(block(x, p) * weigh), argnums=(0, 1))(x, p)))(hidden, p)

    (out, got), (wanted, want) = out_and_grads(program), out_and_grads(reference)
    close(out, wanted)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0
        close(g, w)


def test_attention_rotates_a_quarter_of_the_head_and_reads_head_j_over_8(params, hidden):
    """Written out head by head from the equations, without the reference's
    grouped einsum: query head j against key/value head j // 8, channels 0-1 of
    8 rotated by the position, 2-7 as they are, the output gated."""
    p = params["layers"][1]["attn"]
    x = np.asarray(hidden[0], np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    t, dh = x.shape[0], 8
    def norm(z, g):
        return z / np.sqrt(np.mean(z * z, -1, keepdims=True) + 1e-6) * (1 + g)


    def turn(z):  # rotary_dim 2: one pair, one frequency (theta^0 = 1)
        angle = np.arange(t)[:, None]
        first, second = z[:, :1], z[:, 1:2]
        return np.concatenate([first * np.cos(angle) - second * np.sin(angle),
                               second * np.cos(angle) + first * np.sin(angle), z[:, 2:]], -1)

    qg = (x @ w["q_proj"]).reshape(t, 16, 2 * dh)
    k = (x @ w["k_proj"]).reshape(t, 2, dh)
    v = (x @ w["v_proj"]).reshape(t, 2, dh)
    heads = []
    for j in range(16):
        q_j = turn(norm(qg[:, j, :dh], w["q_norm"]))
        k_j = turn(norm(k[:, j // 8], w["k_norm"]))
        scores = np.where(np.tril(np.ones((t, t), bool)), q_j @ k_j.T / np.sqrt(dh), -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        heads.append(probs @ v[:, j // 8] / (1 + np.exp(-qg[:, j, dh:])))
    want = np.concatenate(heads, -1) @ w["o_proj"]
    close(jax.jit(lambda x, p: qn.attn_block(x, p, CFG))(hidden[:1], p)[0], want)
    close(jax.jit(lambda x, p: ref.attn(x, p, DIMS))(hidden[:1], p)[0], want)


def test_both_gates_of_the_gdn_block_are_silu_of_z_and_sigmoid_of_b(params, hidden):
    """The output gate: scaling z's columns to 0 zeroes the block (silu(0) =
    0).  The write gate: b at -inf (beta 0) writes nothing, so the state stays
    0 and the block's output with it."""
    p = params["layers"][0]["gdn"]
    dh, per = 8, 2
    cols = np.arange(p["in_proj_qkvz"].shape[1]).reshape(2, (2 + 2 * per) * dh)
    z_cols = cols[:, (2 + per) * dh:].reshape(-1)
    no_z = {**p, "in_proj_qkvz": p["in_proj_qkvz"].at[:, z_cols].set(0.0)}
    block = jax.jit(lambda x, p: qn.gdn_block(x, p, CFG))
    assert float(jnp.max(jnp.abs(block(hidden, no_z)))) == 0.0
    b_cols = np.arange(p["in_proj_ba"].shape[1]).reshape(2, 2 * per)[:, :per].reshape(-1)
    shut = {**p, "in_proj_ba": p["in_proj_ba"].at[:, b_cols].set(0.0)}
    half_open = block(hidden, shut)                   # beta = 1/2 everywhere
    close(half_open, jax.jit(lambda x, p: ref.gdn(x, p, DIMS))(hidden, shut))
    assert float(jnp.max(jnp.abs(half_open))) > 0


def test_expert_layer_forward_load_and_gradients_match_the_reference(params, hidden):
    p, x = params["layers"][1]["moe"], hidden.reshape(ROWS * SEQ, -1)
    weigh = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def out_and_grads(layer):
        return jax.jit(lambda x, p: (layer(x, p), jax.grad(
            lambda x, p: jnp.sum(layer(x, p)[0] * weigh), argnums=(0, 1))(x, p)))(x, p)

    (out, load), got = out_and_grads(lambda x, p: qn.moe_block(x, p, CFG))
    (wanted, want_load), want = out_and_grads(lambda x, p: ref.moe(x, p, DIMS))
    close(out, wanted)
    assert np.array_equal(load, want_load)
    assert int(load.sum()) == ROWS * SEQ * CFG.num_experts_per_token
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        close(g, w)


def test_the_router_renormalises_its_top_k_and_the_shared_expert_is_gated(params, hidden):
    p, x = params["layers"][0]["moe"], hidden.reshape(ROWS * SEQ, -1)
    chosen, weights, load = jax.jit(lambda x, r: qn.route(x, r, CFG))(x, p["router"])
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    order = jnp.argsort(-probs, axis=-1)[:, :3]
    assert np.array_equal(np.sort(chosen, -1), np.sort(order, -1))
    close(jnp.sum(weights, axis=-1), jnp.ones(ROWS * SEQ))
    close(weights, jnp.take_along_axis(probs, chosen, -1)
          / jnp.sum(jnp.take_along_axis(probs, chosen, -1), -1, keepdims=True))
    assert load.shape == (CFG.num_experts,)
    # no held expert chosen: what is left is the gated shared expert
    nobody = dataclasses.replace(CFG, expert_offset=CFG.num_experts)
    out, _ = jax.jit(lambda x, p: qn.moe_block(x, p, nobody))(x, p)
    close(out, jax.nn.sigmoid(x @ p["shared_gate"]) * kimi_linear._swiglu(x, p["shared"]))


# -- the whole model: loss and every leaf's gradient -------------------------------

@pytest.fixture(scope="module")
def model_grads(params, batch):
    got = jax.jit(jax.value_and_grad(
        lambda p: qn.loss_fn(p, batch, CFG), has_aux=True))(params)
    want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_of(p, *batch, DIMS), has_aux=True))(params)
    return got, want


def test_model_loss_and_load_match_the_reference(model_grads):
    ((loss, load), _), ((want, want_load), _) = model_grads
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    assert load.shape == (len(CFG.layer_kinds), CFG.num_experts)
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


# -- three steps of the train step against the reference's AdamW -------------------

def test_three_train_steps_follow_the_reference_with_the_load_count(draw):
    start, live = draw(jax.random.PRNGKey(1)), draw(jax.random.PRNGKey(1))  # the step donates
    feed = []
    for i in range(3):
        tokens = jax.random.randint(jax.random.PRNGKey(20 + i), (ROWS, SEQ), 0, CFG.vocab_rows)
        feed.append((tokens, jnp.roll(tokens, -1, axis=-1)))
    opt = jax.jit(lambda p: qn.init_opt_state(p, CFG))(live)
    assert jax.tree_util.tree_leaves(opt["master"]) == []  # float32 leaves need none
    step = qn.make_train_step(CFG)
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
    assert int(opt["count"]) == 3
    norms = jax.jit(lambda tree: jnp.stack(
        [jnp.linalg.norm(x) for x in jax.tree_util.tree_leaves(tree)]))
    change = norms(jax.tree_util.tree_map(lambda a, b: a - b, live, start))
    np.testing.assert_allclose(change, want["change_norm"], rtol=0.02)
    assert bool(jnp.all(norms(opt["mu"]) > 0))


def test_a_bfloat16_tree_has_float32_only_leaves_and_a_buffer_no_gradient_touches():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.jit(lambda k: qn.init_params(cfg, k))(jax.random.PRNGKey(1))
    opt = jax.jit(lambda p: qn.init_opt_state(p, cfg))(params)
    names = leaf_names(params)
    f32 = [n for n, p in zip(names, jax.tree_util.tree_leaves(params)) if p.dtype == jnp.float32]
    assert len(f32) == 2 and all(n.endswith("['A_log']") or n.endswith("['dt_bias']") for n in f32)
    assert len(jax.tree_util.tree_leaves(opt["master"])) == N_LEAVES - 2
    assert len(jax.tree_util.tree_leaves((params, opt))) == 4 * N_LEAVES - 2 + 2
    assert set(opt) == {"mu", "nu", "count", "master", "router_load"}  # no router bias
    tokens = jax.random.randint(jax.random.PRNGKey(2), (ROWS, SEQ), 0, cfg.vocab_rows)
    before = jax.tree_util.tree_structure((params, opt))
    scales = [float(jnp.max(jnp.abs(p["attn_norm"]))) for p in params["layers"]]
    assert scales == [0.0] * 2  # 1 + w norms start at w = 0 ...
    assert float(params["layers"][0]["gdn"]["head_norm"][0]) == 1.0  # ... the head norm at 1
    params, opt, loss = qn.make_train_step(cfg)(params, opt, (tokens, jnp.roll(tokens, -1, -1)))
    assert jax.tree_util.tree_structure((params, opt)) == before
    assert np.isfinite(float(loss))
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves((params, opt))} == {
        "bfloat16", "float32", "int32"}
    assert opt["router_load"].dtype == jnp.int32
    assert int(opt["router_load"].sum()) == 2 * ROWS * SEQ * cfg.num_experts_per_token
    # a 1e-3 step moves a bfloat16 scale at 0, where it could not move one at 1
    assert all(float(jnp.max(jnp.abs(p["attn_norm"]))) > 0 for p in params["layers"])


# -- the share test: the shares of a layer add up to the uncut layer ---------------

def test_all_32_shares_of_the_expert_layer_add_up_to_the_uncut_layer(hidden):
    """32 chips with one of 32 experts each; the gated shared expert, which
    every chip computes alike, counted once."""
    whole = dataclasses.replace(CFG, experts_held=32, expert_offset=0)
    p = jax.jit(lambda k: qn.init_params(whole, k)["layers"][1]["moe"])(jax.random.PRNGKey(9))
    x = hidden.reshape(ROWS * SEQ, -1)
    uncut, _ = ref.moe(x, p, dataclasses.replace(DIMS, expert_offset=0))
    shared = ref.shared(x, p)
    total, loads = shared, []

    @jax.jit
    def one_share(chip):  # one compilation: the offset is an argument
        share = dataclasses.replace(CFG, experts_held=1, expert_offset=chip)
        mine = {k: jax.lax.dynamic_slice_in_dim(w, chip, 1) for k, w in p["experts"].items()}
        return qn.moe_block(x, {**p, "experts": mine}, share)

    for chip in range(32):
        out, load = one_share(chip)
        assert float(jnp.max(jnp.abs(out - shared))) > 0  # every expert got a token
        total = total + (out - shared)
        loads.append(np.asarray(load))
    close(total, uncut)
    assert all(np.array_equal(loads[0], load) for load in loads)  # every chip routes alike


def test_the_8_slices_of_the_vocabulary_concatenate_to_the_uncut_logits(params, batch):
    uncut, _ = jax.jit(lambda p: ref.logits_of(p, batch[0], DIMS))(params)
    one_slice = jax.jit(lambda p, head: qn.forward({**p, "head": head}, batch[0], CFG)[0])
    slices = [one_slice(params, params["head"][:, lo:lo + 8])
              for lo in range(0, CFG.vocab_rows, 8)]
    assert len(slices) == 8
    close(jnp.concatenate(slices, axis=-1), uncut)


# -- no token dropped -----------------------------------------------------------------

@pytest.mark.parametrize("favoured, all_held", [((4, 5, 7), True), ((0, 15, 31), False)],
                         ids=["every-token-routes-here", "no-token-routes-here"])
def test_no_token_is_dropped_at_either_end_of_the_load(favoured, all_held, params, hidden):
    p, x = params["layers"][0]["moe"], hidden.reshape(ROWS * SEQ, -1)
    # a router whose logits favour three experts by far, whatever the token
    router = jnp.zeros_like(p["router"]).at[:, jnp.array(favoured)].set(
        10.0 * jnp.sign(jnp.sum(x, axis=0))[:, None] / x.shape[1])
    x = jnp.abs(x) * jnp.sign(jnp.sum(x, axis=0))[None, :]  # every logit of the three positive
    held = lambda x, chosen, weights: kimi_linear.held_experts(  # noqa: E731
        x, chosen, weights, p["experts"], CFG)
    chosen, weights, load = jax.jit(lambda x, r: qn.route(x, r, CFG))(x, router)
    assert int(load[jnp.array(favoured)].sum()) == 3 * ROWS * SEQ  # every choice of every token
    mine, grad = jax.jit(lambda x, c, w: (held(x, c, w), jax.grad(
        lambda x: jnp.sum(held(x, c, w)))(x)))(x, chosen, weights)
    want, _ = ref.routed(x, {**p, "router": router}, DIMS)
    if all_held:
        close(mine, want)
        assert float(jnp.min(jnp.max(jnp.abs(mine), axis=-1))) > 0  # every token got its part
    else:
        assert float(jnp.max(jnp.abs(mine))) == 0.0 and float(jnp.max(jnp.abs(want))) == 0.0
    assert bool(jnp.all(jnp.isfinite(grad)))


# -- spans and counters -------------------------------------------------------------------

def test_the_lowered_step_names_its_blocks(batch):
    params = jax.eval_shape(lambda k: qn.init_params(CFG, k), jax.random.PRNGKey(1))
    opt = jax.eval_shape(lambda p: qn.init_opt_state(p, CFG), params)
    text = qn.make_train_step(CFG).lower(params, opt, batch).as_text(debug_info=True)
    for scope in ("gdn", "attn", "moe.route", "moe.experts", "moe.shared", "head.loss"):
        assert f"jit(step)/jvp({scope})/" in text, scope
    assert "module @jit_step" in text  # the trace readers find ``jit_step``


def test_routing_stats_is_the_repositorys_one_and_sets_its_gauges():
    from tpu_resiliency.telemetry import get_registry

    assert qn.routing_stats is kimi_linear.routing_stats  # the gauges are declared once
    load = np.zeros((len(CFG.layer_kinds), CFG.num_experts), np.int32)
    load[:, 4:8] = [[1, 2, 3, 14]] * len(CFG.layer_kinds)
    load[:, 0] = 20
    stats = qn.routing_stats({"router_load": load}, CFG)
    assert stats == {"max": 14.0, "mean": 5.0, "share": 0.5}
    snapshot = get_registry().snapshot()
    assert snapshot["tpurx_model_expert_load_max"]["samples"][0]["value"] == 14.0
    assert snapshot["tpurx_model_expert_load_mean"]["samples"][0]["value"] == 5.0


# -- the benchmark's copy, and the cell's counts --------------------------------------------

def test_the_benchmarks_reference_is_this_repositorys_byte_for_byte():
    with open(os.path.join(ROOT, "tpu_resiliency/models/qwen3_next_reference.py"), "rb") as f:
        ours = f.read()
    with open(os.path.join(ROOT, "chipbench/reference/qwen3_next.py"), "rb") as f:
        assert f.read() == ours


def test_the_benchmarks_reference_gives_equal_numbers(params, batch):
    import sys

    sys.path.insert(0, ROOT)
    from chipbench.reference import qwen3_next as copy

    ours = jax.jit(lambda p: ref.loss_of(p, *batch, DIMS))(params)
    theirs = jax.jit(lambda p: copy.loss_of(
        p, *batch, copy.Dims(**dataclasses.asdict(DIMS))))(params)
    assert float(ours[0]) == float(theirs[0]) and np.array_equal(ours[1], theirs[1])


def test_the_cells_counts_from_shapes_nothing_allocated():
    import json
    import sys

    sys.path.insert(0, ROOT)
    from chipbench import families, weights

    family, sizes = families.of_file(CELL_CONFIG)
    assert sizes.n_params == 424_340_544 and sizes.tokens_per_step == 4096
    assert sizes.state_bytes == 5_940_775_428  # ISSUE 33's 5,940,775,424 and the step count's 4
    assert sizes.layer_kinds == ("gdn", "gdn", "gdn", "attn")
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(weights.make_state_fn(family, sizes), key)
    leaves = jax.tree_util.tree_leaves(state)
    assert sum(x.size * x.dtype.itemsize for x in leaves) == sizes.state_bytes
    assert len(leaves) == 276
    assert {str(x.dtype) for x in leaves} == {"bfloat16", "float32", "int32"}
    draw = jax.eval_shape(lambda k: family.draw_params(sizes, k, jnp.bfloat16), key)
    assert sum(x.size for x in jax.tree_util.tree_leaves(draw)) == sizes.n_params
    # 188.0 M matmul parameters a token (the routed experts at their expected
    # 0.3125 assignments), 16.8 M of causal scores at 4096 tokens, 4.7 M of scans
    assert family.train_flops_per_token(sizes) * sizes.tokens_per_step == pytest.approx(
        5.147e12, rel=0.001)
    # the widths are the source's; only depth, the experts held and the vocabulary are cut
    with open(CELL_CONFIG) as f:
        cfg = json.load(f)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert (cfg["hidden_size"], cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_conv_kernel_dim"]) == (2048, 16, 32, 128, 4)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
            sizes.rotary_dim, cfg["rope_theta"]) == (16, 2, 256, 64, 10_000_000)
    assert (cfg["deployment"]["experts"], cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"]) == (512, 10, 512, 512)
    assert cfg["rms_norm_eps"] == 1e-6
