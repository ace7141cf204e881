"""Trinity-Mini's block through the normal path at a small size on the CPU
(1 dense + 4 routed layers at tiny widths, 16 experts of which 2 held,
top-4, window 8, sequence 32): the program against the plain reference
``benchmark/reference/trinity.py`` on seeded weights — forward, loss, every
leaf's gradient; grouped K/V heads against repeated ones; the eight shares
of an expert-parallel layer against the uncut layer; no token dropped; what
the new fields leave alone."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402
from deeplearning4j_tpu.nn.layers.attention import (MultiHeadAttention,  # noqa: E402
                                                    TransformerBlock)
from deeplearning4j_tpu.nn.layers.moe import (MixtureOfExpertsLayer,  # noqa: E402
                                              RoutedExperts,
                                              publish_expert_tokens)
from deeplearning4j_tpu.nn.multilayer import _stack_loss  # noqa: E402
from deeplearning4j_tpu.observability.registry import (MetricsRegistry,  # noqa: E402
                                                       default_registry,
                                                       set_default_registry)
from deeplearning4j_tpu.parallel.expert import (pair_capacity,  # noqa: E402
                                                routed_ffn)

ref = common.load_module("reference", "trinity")
traffic = common.load_module("traffic", "moe_lm_fit_stream")

SMALL = {
    "family": "trinity", "hidden_size": 32, "head_dim": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 48, "moe_intermediate_size": 16, "num_experts": 2,
    "experts_held": [0, 2], "published": {"num_experts": 16},
    "num_experts_per_tok": 4, "num_shared_experts": 1, "route_norm": True,
    "route_scale": 2.826, "sliding_window": 8, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "vocab_size": 48, "num_hidden_layers": 5,
    "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention",
                                                "sliding_attention"],
    "init_std": 0.2, "train_seq_len": 32, "precision": "float32",
    "cache_mode": "none",
    "optimizer": {"kind": "adam", "learning_rate": 3e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}


@pytest.fixture(scope="module")
def seeded():
    """The program's network on the reference's seeded weights, a batch."""
    net = traffic.build(SMALL)
    theirs = ref.init_params(SMALL, jax.random.PRNGKey(3))
    net.params = {**{k: v for k, v in net.params.items() if not v},
                  **traffic.as_program(theirs)}
    ids = np.random.default_rng(0).integers(0, 48, (2, 33)).astype(np.int32)
    return net, theirs, ids[:, :-1], ids[:, 1:]


def test_the_layers_are_the_configurations(seeded):
    net = seeded[0]
    blocks = net.conf.layers[1:6]
    assert [b.attention for b in blocks] == ["sliding"] * 3 + ["full",
                                                               "sliding"]
    assert [b.positions for b in blocks] == ["rotary"] * 3 + ["none",
                                                              "rotary"]
    assert [b.moe_experts for b in blocks] == [0, 16, 16, 16, 16]
    assert all(b.moe_top_k == 4 and tuple(b.moe_held) == (0, 2)
               for b in blocks[1:])
    assert net.conf.layers[0].scale == 32 ** 0.5
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(net.params))
    assert n == ref.n_params(SMALL)


def test_forward_matches_the_reference(seeded):
    """Softmax outputs against the reference's logits; 2e-5 absolute on
    probabilities: float32 on both sides, sums in another order."""
    net, theirs, x, _ = seeded
    out = np.asarray(net.output(x))
    for r in range(2):
        logits, _ = ref.row_logits(ref._static(SMALL), "float32", None,
                                   theirs, jnp.asarray(x[r]))
        np.testing.assert_allclose(out[r], jax.nn.softmax(logits, axis=-1),
                                   atol=2e-5)


def test_loss_and_every_leafs_gradient_match_the_reference(seeded):
    """Relative to each leaf's largest entry: 1e-4 (float32 on both sides;
    measured 1.3e-5 at worst, the rest is room for another host's sums)."""
    net, theirs, x, y = seeded
    (loss, _), grads = jax.value_and_grad(
        lambda p: _stack_loss(net.conf, p, net.state, x, y, train=True,
                              key=None), has_aux=True)(net.params)
    their_loss, their_grads, _ = ref.loss_and_grads(SMALL, theirs, x, y)
    assert float(loss) == pytest.approx(float(their_loss), rel=1e-6)
    flat = ref.flat(their_grads)
    seen = set()
    for layer, leaves in grads.items():
        for leaf, g in leaves.items():
            name = traffic.reference_name(5, layer, leaf)
            seen.add(name)
            scale = float(jnp.max(jnp.abs(flat[name])))
            assert scale > 0, name
            assert float(jnp.max(jnp.abs(g - flat[name]))) <= 1e-4 * scale, \
                name
    assert seen == set(flat)


def test_the_programs_routing_is_the_references(seeded):
    net, theirs, x, y = seeded
    _, _, chosen = ref.loss_and_grads(SMALL, theirs, x, y)
    mine = traffic.program_choices(net, SMALL, x)
    assert mine.shape == np.asarray(chosen).shape == (2, 4, 32, 4)
    assert traffic.routing_agreement(mine, chosen) == 1.0
    # a choice the reference did not make counts against the agreement
    other = np.array(mine)
    other[0, 0, 0, 0] = 15 - other[0, 0, 0, 0]
    assert traffic.routing_agreement(other, chosen) < 1.0


def test_fit_trains_and_publishes_each_held_experts_tokens(seeded):
    net, _, x, y = seeded
    before = set_default_registry(MetricsRegistry())
    try:
        twin = traffic.build(SMALL)
        twin.fit([(x, y)])
        first = twin.get_score()
        for _ in range(3):
            twin.fit([(x, y)])
        assert twin.get_score() < first
        gauge = default_registry().get("moe_expert_tokens")
        by_layer = {}
        for (layer, expert), child in gauge.samples():
            by_layer.setdefault(layer, {})[int(expert)] = child.value
        assert sorted(by_layer) == ["layer_2", "layer_3", "layer_4",
                                    "layer_5"]
        # the gauge holds what the step's state holds: the pairs routed to
        # the experts held here, of the 2 x 32 x 4 routed in all
        for layer, counts in by_layer.items():
            state = np.asarray(twin.state[layer]["expert_tokens"])
            assert [counts[0], counts[1]] == state.tolist()
            assert 0 < state.sum() < 2 * 32 * 4
        traced = default_registry().get("moe_layers_traced_total")
        assert traced.labels("2", "16", "4").value >= 4
    finally:
        set_default_registry(before)


# ------------------------------------------------------ grouped K/V heads
def test_grouped_kv_heads_are_repeated_heads():
    """Two K/V heads under four query heads give what four K/V heads give
    whose projections repeat the two (1e-6: the same float32 products)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    kw = dict(n_in=32, n_out=32, n_heads=4, head_dim=8, causal=True,
              has_bias=False, attn_impl="reference", qk_norm=True,
              out_gate=True, positions="rotary", attention="sliding",
              window=5)
    grouped = MultiHeadAttention(n_kv_heads=2, **kw)
    whole = MultiHeadAttention(**kw)
    itype = InputType.recurrent(32, 16)
    p = grouped.init(jax.random.PRNGKey(2), itype)["params"]
    assert p["Wk"].shape == p["Wv"].shape == (32, 16)
    wide = dict(p)
    for name in ("Wk", "Wv"):
        wide[name] = jnp.repeat(p[name].reshape(32, 2, 8), 2,
                                axis=1).reshape(32, 32)
    np.testing.assert_allclose(grouped.attend(p, x), whole.attend(wide, x),
                               atol=1e-6)
    g = jax.grad(lambda p: jnp.sum(jnp.sin(grouped.attend(p, x))))(p)
    gw = jax.grad(lambda p: jnp.sum(jnp.sin(whole.attend(p, x))))(wide)
    # a K/V head's gradient is the sum over the query heads it serves
    np.testing.assert_allclose(
        g["Wk"], gw["Wk"].reshape(32, 2, 2, 8).sum(axis=2).reshape(32, 16),
        atol=1e-5)


def test_query_heads_must_be_whole_groups():
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    with pytest.raises(ValueError, match="multiple"):
        MultiHeadAttention(n_in=32, n_out=32, n_heads=4, n_kv_heads=3,
                           head_dim=8).init(jax.random.PRNGKey(0),
                                            InputType.recurrent(32, 8))


# --------------------------------------------- the share and the whole layer
def _layer_params(key, e=32, f=16, total=16, scale=0.3):
    ks = jax.random.split(key, 7)
    return {"router": scale * jax.random.normal(ks[0], (e, total)),
            "eg": scale * jax.random.normal(ks[1], (total, e, f)),
            "e1": scale * jax.random.normal(ks[2], (total, e, f)),
            "e2": scale * jax.random.normal(ks[3], (total, f, e)),
            "sg": scale * jax.random.normal(ks[4], (e, f)),
            "s1": scale * jax.random.normal(ks[5], (e, f)),
            "s2": scale * jax.random.normal(ks[6], (f, e))}


def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The tie between the share and the model: the routed parts that the
    eight shares give (2 experts each of 16, all routing over the 16), with
    the shared expert counted once, add up to what the uncut reference
    gives for the whole layer.  5e-6 absolute on outputs of order one:
    float32, the experts' sum in another order."""
    whole = _layer_params(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (64, 32))
    uncut = {**SMALL, "num_experts": 16}
    want, chosen = ref.routed_ffn(uncut, whole, x, SMALL["route_scale"])
    module = RoutedExperts(n_in=32, hidden=16, experts_total=16, top_k=4,
                           scoring="sigmoid", route_norm=True,
                           route_scale=SMALL["route_scale"],
                           shared_experts=1, gated=True, has_bias=False)
    total, tokens = 0.0, []
    for share in range(8):
        mine = {"router": whole["router"],
                "wg": whole["eg"][2 * share:2 * share + 2],
                "w1": whole["e1"][2 * share:2 * share + 2],
                "w2": whole["e2"][2 * share:2 * share + 2]}
        y, took = routed_ffn(mine, x, top_k=4, scoring="sigmoid",
                             route_norm=True,
                             route_scale=SMALL["route_scale"],
                             held=(2 * share, 2), act=jax.nn.silu)
        total = total + y
        tokens.append(took)
    shared = ref.mlp(ref._highest, x, whole["sg"], whole["s1"], whole["s2"])
    np.testing.assert_allclose(total + shared, want, atol=5e-6)
    # every (token, slot) pair went to exactly one share
    tokens = np.concatenate(tokens)
    assert tokens.sum() == 64 * 4
    assert tokens.tolist() == np.bincount(np.asarray(chosen).ravel(),
                                          minlength=16).tolist()
    # and the module with every expert held is the uncut layer itself
    p = {"router": whole["router"], "wg": whole["eg"], "w1": whole["e1"],
         "w2": whole["e2"], "sg": whole["sg"], "s1": whole["s1"],
         "s2": whole["s2"]}
    y, state = module.apply(p, {}, x, jax.nn.silu)
    np.testing.assert_allclose(y, want, atol=5e-6)
    assert int(state["expert_tokens"].sum()) == 64 * 4


# Which buffers a routing lands on (``pair_capacity``: twice the even
# routing's share in whole tiles of 1024 rows, at most every pair): all 256
# pairs of 64 tokens ("whole": the one path of a small layer, as of a layer
# that holds every expert); of 512 tokens' 2048 pairs onto 2 or 3 of 16
# experts a capacity of 1024, with under it the pairs one favoured expert
# draws ("compact"), exactly it where every token chooses both held experts
# ("boundary"), and over it where every token chooses all three held
# ("overflow": computed in full by the whole-size branch).
PATHS = {"whole": (64, (2, 2), (3,)), "compact": (512, (2, 2), (3,)),
         "boundary": (512, (2, 2), (2, 3)), "overflow": (512, (2, 3),
                                                         (2, 3, 4))}


def _share(whole, first, n):
    """The router whole and experts ``first .. first + n - 1`` of a layer."""
    return {"router": whole["router"], "wg": whole["eg"][first:first + n],
            "w1": whole["e1"][first:first + n],
            "w2": whole["e2"][first:first + n]}


def _lands_on(path, tokens, top_k, took, n_held, total=16):
    capacity = pair_capacity(tokens * top_k, n_held, total)
    sent = int(jnp.sum(took))
    if path == "whole":
        assert capacity == tokens * top_k
    else:
        assert capacity == 1024 < tokens * top_k
        assert {"compact": sent < capacity, "boundary": sent == capacity,
                "overflow": sent > capacity}[path], (sent, capacity)


@pytest.mark.parametrize("path", list(PATHS))
def test_no_token_is_dropped_under_a_router_biased_to_one_expert(path):
    """Every token sends a pair to each favoured expert (its router column
    dominates), 64 pairs onto one expert of 16 where an even routing gives
    it 16: each is computed, none dropped; the top-1 capacity path at the
    same load drops three quarters of them.  So on every path of
    ``PATHS``, the overflowing one among them."""
    tokens, (first, n), favoured = PATHS[path]
    whole = _layer_params(jax.random.PRNGKey(7))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (tokens, 32))) + 0.1
    whole["router"] = whole["router"].at[:, np.asarray(favoured)].set(5.0)
    mine = _share(whole, first, n)
    y, took = routed_ffn(mine, x, top_k=4, scoring="sigmoid",
                         route_norm=True, route_scale=1.0, held=(first, n))
    assert all(int(took[e - first]) == tokens for e in favoured)
    _lands_on(path, tokens, 4, took, n)
    uncut = {**SMALL, "num_experts": 16}
    _, chosen = ref.routed_ffn(uncut, whole, x, 1.0)
    assert all((np.asarray(chosen) == e).any(axis=1).all() for e in favoured)
    # the held experts' part of every token, against a loop over them
    scores = jax.nn.sigmoid(x @ whole["router"])
    sel = jnp.take_along_axis(scores, chosen, axis=1)
    w = sel / jnp.sum(sel, axis=1, keepdims=True)
    want = 0.0
    for e in range(first, first + n):
        we = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1)
        want = want + we[:, None] * ref.mlp(ref._highest, x, whole["eg"][e],
                                            whole["e1"][e], whole["e2"][e])
    np.testing.assert_allclose(y, want, atol=5e-6)
    assert float(jnp.min(jnp.linalg.norm(y, axis=1))) > 0


@pytest.mark.parametrize("path", ["all-held"] + list(PATHS))
def test_the_gradient_of_the_routed_part_is_the_loops(path):
    """Every gradient (router, the three expert matrices, the input)
    against the loop over the held experts, 1e-5 absolute; ``all-held`` is
    the case this test had before the buffers were sized (4 of 8 experts,
    top-3, softmax scores, no favoured expert, 48 tokens), the others are
    ``PATHS`` under a balancing bias that favours experts in the choice and
    not in the weights.  Their loss is weighted by 48 over the tokens, so
    that a weight's gradient, a sum over 512 tokens, has entries of the
    order 48 tokens give and the one tolerance means what it meant."""
    if path == "all-held":
        tokens, total, (first, n), k, favoured = 48, 8, (4, 4), 3, ()
    else:
        (tokens, (first, n), favoured), total, k = PATHS[path], 16, 4
    whole = _layer_params(jax.random.PRNGKey(9), total=total)
    x = jax.random.normal(jax.random.PRNGKey(10), (tokens, 32))
    mine = _share(whole, first, n)
    bias = jnp.zeros((total,)).at[np.asarray(favoured, int)].set(2.0)
    weight = 48 / tokens

    def program(p, x):
        y, took = routed_ffn(p, x, top_k=k, scoring="softmax",
                             held=(first, n), bias=bias)
        return weight * jnp.sum(jnp.sin(y)), took

    def loop(p, x):
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
        _, idx = jax.lax.top_k(probs + bias, k)
        w = jnp.take_along_axis(probs, idx, axis=1)
        y = 0.0
        for e in range(n):
            we = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=1)
            y = y + we[:, None] * ((jax.nn.silu(x @ p["wg"][e])
                                    * (x @ p["w1"][e])) @ p["w2"][e])
        return weight * jnp.sum(jnp.sin(y))
    got, took = jax.grad(program, argnums=(0, 1), has_aux=True)(mine, x)
    if path != "all-held":
        _lands_on(path, tokens, k, took, n)
    want = jax.grad(loop, argnums=(0, 1))(mine, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-5)


# -------------------------------------------- what the sized buffers hold
def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _held_subset_vjp(tokens=512, held=(2, 2), total=16, k=4):
    """The jaxpr of ``routed_ffn``'s forward and backward at a size whose
    capacity (1024) is half its 2048 pairs."""
    whole = _layer_params(jax.random.PRNGKey(11), total=total)
    x = jax.random.normal(jax.random.PRNGKey(12), (tokens, 32))
    first, n = held
    mine = _share(whole, first, n)

    def both(p, x):
        y, back = jax.vjp(lambda p, x: routed_ffn(
            p, x, top_k=k, scoring="sigmoid", held=held)[0], p, x)
        return back(jnp.ones_like(y))
    return jax.make_jaxpr(both)(mine, x).jaxpr


def test_a_held_subset_keeps_and_multiplies_capacity_rows_alone():
    """512 tokens x 4 onto 2 of 16 experts: one conditional each way, and
    what its forward hands the backward (the step's saved buffers) has
    1024 rows or the tokens' 512, never the 2048 pairs; on the branch a
    routing within the capacity takes, every product's operands have 1024
    rows, and nothing of 2048 rows is 32 wide but what the per-token sum
    gathers on its way (``[512, 4, 32]``, read once and kept nowhere); the
    other branch is the whole-size code, checkpointed."""
    jaxpr = _held_subset_vjp()
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 2
    forward = conds[0]
    whole_size = {(2048, 32), (2048, 16), (512, 4, 32), (512, 4, 16)}
    assert not whole_size & {v.aval.shape for v in forward.outvars}
    assert (1024, 32) in {v.aval.shape for v in forward.outvars}
    # branches are (false, true): the predicate is ``overflowed``
    compact, overflow = forward.params["branches"]
    products = [e for e in _eqns(compact.jaxpr)
                if e.primitive.name == "ragged_dot_general"]
    assert len(products) == 3
    assert all(e.invars[0].aval.shape[0] == 1024 for e in products)
    wide = [e.outvars[0].aval.shape for e in _eqns(compact.jaxpr)
            if e.outvars and e.outvars[0].aval.shape in ((2048, 32),
                                                         (512, 4, 32))]
    assert wide and set(wide) == {(512, 4, 32)}
    # the overflow branch multiplies all 2048 and saves none of it: its
    # backward is one checkpointed replay
    assert all(e.invars[0].aval.shape[0] == 2048
               for e in _eqns(overflow.jaxpr)
               if e.primitive.name == "ragged_dot_general")
    replayed = conds[1].params["branches"][1].jaxpr.eqns
    assert [e.primitive.name for e in replayed] == ["remat2"]


def test_a_layer_holding_all_its_experts_lowers_with_no_conditional():
    whole = _layer_params(jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(12), (512, 32))
    p = {"router": whole["router"], "wg": whole["eg"], "w1": whole["e1"],
         "w2": whole["e2"]}
    assert pair_capacity(2048, 16, 16) == 2048

    def loss(p, x):
        return jnp.sum(routed_ffn(p, x, top_k=4, scoring="sigmoid")[0])
    step = jax.grad(loss, argnums=(0, 1))
    assert not any(e.primitive.name == "cond"
                   for e in _eqns(jax.make_jaxpr(step)(p, x).jaxpr))
    text = jax.jit(step).lower(p, x).as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    # the same layer holding 2 of the 16 does have one
    mine = {**p, **{k: p[k][2:4] for k in ("wg", "w1", "w2")}}
    text = jax.jit(lambda p, x: routed_ffn(
        p, x, top_k=4, scoring="sigmoid", held=(2, 2))[0]).lower(
            mine, x).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text


def test_pair_capacity_is_twice_the_even_share_in_whole_tiles():
    # the benchmark's share: 8192 tokens x 8 onto 16 of 128 experts
    assert pair_capacity(8192 * 8, 16, 128) == 16384
    assert pair_capacity(8192 * 8, 128, 128) == 8192 * 8
    assert pair_capacity(2048, 2, 16) == pair_capacity(2048, 3, 16) == 1024
    assert pair_capacity(2048, 7, 16) == 2048      # 1792 -> two tiles: all
    assert pair_capacity(256, 2, 16) == 256        # under a tile: all


def test_overflowing_steps_are_counted_and_published():
    """``expert_overflows`` rises by one a call whose routing sends the held
    experts more pairs than the capacity, and by none otherwise; the gauges
    read what the state and the trace hold."""
    module = RoutedExperts(n_in=32, hidden=16, experts_total=16, top_k=4,
                           scoring="sigmoid", route_norm=True,
                           experts_held=(2, 3), gated=True, has_bias=False)
    make = lambda key, shape: 0.3 * jax.random.normal(key, shape)  # noqa: E731
    p, state = module.init(jax.random.PRNGKey(0), make, jnp.zeros)
    assert int(state["expert_overflows"]) == 0
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 32))
    before = set_default_registry(MetricsRegistry())
    try:
        pushed = {**state, "route_bias": state["route_bias"].at[2:5].set(9.)}
        apply = jax.jit(lambda st: module.apply(p, st, x, jax.nn.silu)[1])
        one = apply(pushed)
        two = apply({**one, "route_bias": pushed["route_bias"]})
        assert int(one["expert_overflows"]) == 1
        assert int(two["expert_overflows"]) == 2
        assert int(two["expert_tokens"].sum()) == 3 * 512 > 1024
        calm = apply({**two, "route_bias": state["route_bias"]})
        assert int(calm["expert_overflows"]) == 2
        assert int(calm["expert_tokens"].sum()) < 1024
        # a state from before the key was there counts from nought
        old = {k: v for k, v in pushed.items() if k != "expert_overflows"}
        assert int(module.apply(p, old, x, jax.nn.silu)[1][
            "expert_overflows"]) == 1

        class Model:
            state = {"layer_7": two, "layer_8": calm, "layer_9": {}}
        publish_expert_tokens(Model())
        steps = default_registry().get("moe_overflow_steps")
        assert {k: c.value for k, c in steps.samples()} == {
            ("layer_7",): 2.0, ("layer_8",): 2.0}
        capacity = default_registry().get("moe_pair_capacity")
        assert capacity.labels("3", "16", "4").value == 1024
    finally:
        set_default_registry(before)


def test_a_state_tree_saved_before_the_overflow_count_loads(tmp_path):
    """A model file whose routed layers' state has no ``expert_overflows``
    (one written before PR 34) loads into today's network: the count starts
    at nought and training goes on."""
    from deeplearning4j_tpu.utils import model_serializer
    net = traffic.build(SMALL)
    keys = set(net.state["layer_2"])
    assert keys == {"route_bias", "expert_tokens", "expert_overflows"}
    net.state = {name: ({k: v for k, v in st.items()
                         if k != "expert_overflows"}
                        if isinstance(st, dict) else st)
                 for name, st in net.state.items()}
    path = str(tmp_path / "old.zip")
    model_serializer.write_model(net, path)
    again = model_serializer.restore_model(path)
    assert set(again.state["layer_2"]) == keys
    assert int(again.state["layer_2"]["expert_overflows"]) == 0
    ids = np.random.default_rng(1).integers(0, 48, (2, 33)).astype(np.int32)
    again.fit([(ids[:, :-1], ids[:, 1:])])
    assert np.isfinite(again.get_score())
    assert int(again.state["layer_5"]["expert_overflows"]) == 0


# ----------------------------------------------- the layers that call it
def test_mixture_of_experts_layer_calls_the_one_routed_module():
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    layer = MixtureOfExpertsLayer(n_in=12, n_out=12, n_experts=8, hidden=10,
                                  top_k=2, scoring="sigmoid",
                                  route_norm=True, experts_held=(2, 4),
                                  shared_experts=1, activation="relu")
    v = layer.init(jax.random.PRNGKey(0), InputType.feed_forward(12))
    assert v["params"]["w1"].shape == (4, 12, 10)
    assert v["params"]["router"].shape == (12, 8)
    assert "aux_loss" not in v["state"]
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 12))
    y, state = layer.apply(v, x)
    assert y.shape == (3, 5, 12)
    assert 0 < int(state["expert_tokens"].sum()) <= 3 * 5 * 2
    # the top-1 capacity path is the default and keeps its auxiliary loss
    old = MixtureOfExpertsLayer(n_in=12, n_out=12, n_experts=4)
    assert "aux_loss" in old.init(jax.random.PRNGKey(0),
                                  InputType.feed_forward(12))["state"]


def test_a_gated_bias_free_block_routes_and_the_top1_path_says_how():
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    itype = InputType.recurrent(16, 8)
    block = TransformerBlock(n_in=16, n_heads=2, gated=True, has_bias=False,
                             norm="rms", ffn_hidden=24, moe_experts=8,
                             moe_top_k=2, moe_hidden=12, moe_shared=1,
                             attn_impl="reference")
    v = block.init(jax.random.PRNGKey(0), itype)
    assert v["params"]["wg"].shape == (8, 16, 12)
    assert set(v["state"]) == {"route_bias", "expert_tokens",
                               "expert_overflows"}
    y, state = block.apply(v, jnp.ones((2, 8, 16)))
    assert y.shape == (2, 8, 16) and int(state["expert_tokens"].sum()) == 32
    with pytest.raises(ValueError, match="moe_top_k"):
        TransformerBlock(n_in=16, n_heads=2, gated=True, has_bias=False,
                         moe_experts=4).init(jax.random.PRNGKey(0), itype)


def test_the_kv_cache_path_refuses_what_it_cannot_serve():
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    for kw in ({"n_kv_heads": 1}, {"qk_norm": True}, {"out_gate": True},
               {"attention": "sliding", "window": 4, "causal": True}):
        mha = MultiHeadAttention(n_in=16, n_out=16, n_heads=2, **kw)
        p = mha.init(jax.random.PRNGKey(0),
                     InputType.recurrent(16, 8))["params"]
        with pytest.raises(NotImplementedError, match="KV-cache"):
            mha.attend_cached(p, jnp.ones((1, 1, 16)),
                              mha.init_carry(1, max_len=8))


def test_the_configuration_round_trips_through_json(seeded):
    from deeplearning4j_tpu.nn.conf.multi_layer import \
        MultiLayerConfiguration
    conf = seeded[0].conf
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert json.loads(again.to_json()) == json.loads(conf.to_json())
    assert tuple(again.layers[2].moe_held) == (0, 2)
