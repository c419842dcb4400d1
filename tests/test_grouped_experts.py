"""The held experts as one grouped product (models/experts.py
``held_experts``, ops/grouped_ffn.py) and, several experts a token over many
rows, an expert at a time: against the benchmark's references, which take an
expert at a time over every row; the kernel in interpret mode against the
compiled map it stands for; the gradient through the differentiable spelling;
and the structure that makes it one product a layer (one sort, no loop a held
expert) where it is one."""

import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cst_captioning_tpu.models import experts
from cst_captioning_tpu.ops import grouped_ffn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, M = 32, 16


def _reference(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def cca():
    return _reference("reference_cca_moe")


@pytest.fixture(scope="module")
def window():
    return _reference("reference_window_moe")


def _stacked(rng, *lead):
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    return draw(*lead, H, M), draw(*lead, H, M), draw(*lead, M, H)


def _dense(chosen, weight, n_experts: int):
    """The references' combine weights [N, n_experts] of a top-1 choice (the
    last id is "no expert")."""
    return np.asarray(jax.nn.one_hot(chosen, n_experts + 1)
                      * weight[:, None])[:, :n_experts]


# name -> (rows, experts, held, share, what the rows choose, layers or None)
TOP1 = {
    "rows_that_choose_no_expert": (40, 4, 4, 0, "any", None),
    "an_expert_with_no_row": (40, 4, 4, 0, "never_2", None),
    "every_row_on_one_expert": (600, 4, 4, 0, "always_1", None),
    "fewer_rows_than_a_tile": (10, 4, 4, 0, "any", None),
    "rows_not_a_multiple_of_the_tile": (37, 4, 4, 0, "any", None),
    "a_share_of_the_experts": (40, 8, 4, 1, "any", None),
    "a_traced_layer_in_a_scan": (40, 4, 4, 0, "any", 3),
    "no_layer": (40, 4, 4, 0, "any", 0),
}


@pytest.mark.parametrize("case", sorted(TOP1))
def test_top_1_is_the_reference_s_experts(cca, case):
    """``k`` = 1 (models/cca_moe.py): the grouped product over the rows
    sorted once against ``reference_cca_moe.experts``, an expert at a time
    over every row."""
    N, E, held, share, rule, layers = TOP1[case]
    rng = np.random.default_rng(sorted(TOP1).index(case))
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    chosen = rng.integers(0, E + 1, size=N)
    if rule == "never_2":
        chosen[chosen == 2] = 3
    if rule == "always_1":
        chosen[:] = 1
    weight = jnp.asarray(rng.uniform(0.2, 1.0, size=N), jnp.float32)
    live = jnp.asarray(rng.uniform(size=N) < 0.9)
    w = _dense(jnp.asarray(chosen), jnp.where(live, weight, 0.0), E)
    sizes = dict(experts_held=held, expert_share_index=share)
    same = lambda y: y  # noqa: E731
    args = (jnp.asarray(chosen)[:, None], weight[:, None], live)
    with jax.default_matmul_precision("highest"):
        if layers:
            stack = _stacked(rng, layers, held)

            def block(carry, index):
                out, tally = experts.held_experts(
                    x, *args, *stack, share * held, E, False, layer=index)
                return carry + tally[:, :-1].sum(), (out, tally)

            rows, (got, tally) = jax.jit(lambda: jax.lax.scan(
                block, jnp.int32(0), jnp.arange(layers)))()
            want = np.stack([np.asarray(cca.experts(
                tuple(a[i] for a in stack), sizes, x, w, same))
                for i in range(layers)])
            assert int(rows) == layers * int(tally[0, :, :-1].sum())
            tally = tally[0]
        else:
            stack = _stacked(rng, held)
            spelled = {} if layers is None else {"layer": 0}
            lead = (lambda a: a) if layers is None else (lambda a: a[None])
            got, tally = jax.jit(lambda: experts.held_experts(
                x, *args, *map(lead, stack), share * held, E, False, **spelled))()
            want = np.asarray(cca.experts(stack, sizes, x, w, same))
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    mine = (chosen >= share * held) & (chosen < (share + 1) * held) & np.asarray(live)
    local = np.where(mine, chosen - share * held, held)
    assert np.asarray(tally)[:, :-1].tolist() == \
        (local[:, None] == np.arange(held)).astype(int).tolist()
    assert np.asarray(tally)[:, -1].tolist() == np.asarray(live).astype(int).tolist()
    assert (np.asarray(got)[..., ~mine, :] == 0).all()


def test_the_tile_follows_the_rows_an_expert_expects():
    """16 rows at a beam step (ZAYA's and MiMo's 10 lanes), 128 at Kimi's
    1280 rows of 8 choices over 384 experts, 512 over a prefix; and the cases
    above cross it: more rows than a tile, fewer, and not a multiple."""
    rows = experts.expert_tile_rows
    assert (rows(10, 1, 16), rows(10, 8, 256), rows(1280, 8, 384),
            rows(32768, 1, 16), rows(32768, 8, 256)) == (16, 16, 128, 512, 512)
    assert rows(600, 1, 4) < 600 and rows(10, 1, 4) > 10 and 37 % rows(37, 1, 4)


# name -> (rows, share, bytes the grouped product's sorted copy may hold)
TOP8 = {
    "a_partial_share": (40, 1, experts.CHUNK_BYTES),
    "the_last_share": (40, 3, experts.CHUNK_BYTES),
    "fewer_rows_than_a_tile": (10, 2, experts.CHUNK_BYTES),
    "more_pairs_than_one_product_holds": (300, 0, 2 * 16 * H * 4),
}


@pytest.mark.parametrize("case", sorted(TOP8))
def test_top_8_of_a_share_is_the_reference_s_expert_ffn(window, case, monkeypatch):
    """``k`` = 8 over 16 experts of which a share of 4 is held
    (``expert_share_index`` > 0): ``experts.expert_ffn`` against
    ``reference_window_moe.expert_ffn``; where the sorted copy would outgrow
    ``CHUNK_BYTES`` the experts are walked one at a time, to the same sum."""
    N, share, chunk = TOP8[case]
    monkeypatch.setattr(experts, "CHUNK_BYTES", chunk)
    E, k, held = 16, 8, 4
    rng = np.random.default_rng(20 + sorted(TOP8).index(case))
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    gate, up, down = _stacked(rng, E)
    p = {"gate": jnp.asarray(rng.normal(size=(H, E)) * 0.3, jnp.float32),
         "e_score_correction_bias": jnp.asarray(rng.normal(size=E) * 0.3, jnp.float32)}
    cut = slice(share * held, (share + 1) * held)
    p.update(experts_gate_proj=gate[cut], experts_up_proj=up[cut],
             experts_down_proj=down[cut])
    sizes = dict(num_experts_per_tok=k, routed_scaling_factor=1.5,
                 n_routed_experts=E, experts_held=held, expert_share_index=share)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(window.expert_ffn(p, sizes, x, lambda y: y))
        got, tally = jax.jit(lambda: experts.expert_ffn(
            types.SimpleNamespace(**sizes), p, p["e_score_correction_bias"], x,
            jnp.ones((N,), bool), differentiable=False))()
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert int(np.asarray(tally)[:, -1].sum()) == N * k
    assert 0 < int(np.asarray(tally)[:, :-1].sum()) < N * k


@pytest.mark.parametrize("n_tiles", [0, 3, 5])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_in_interpret_mode_is_the_compiled_map(dtype, n_tiles):
    """ops/grouped_ffn.py: the kernel against the map over the same sorted
    rows, groups and tile count; a tile that does not exist comes back as
    zeros from both. In bfloat16 the kernel keeps ``silu(.) * .`` in float32
    up to its one rounding where the map rounds each product."""
    rng = np.random.default_rng(3)
    tile, G, h, m = 16, 6, 128, 256
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.1, dtype)  # noqa: E731
    xs, gate, up, down = draw(5 * tile, h) * 10, draw(G, h, m), draw(G, h, m), draw(G, m, h)
    groups = jnp.asarray([0, 0, 2, 5, 5], jnp.int32)
    run = lambda impl: np.asarray(grouped_ffn.grouped_gated(  # noqa: E731
        xs, groups, jnp.int32(n_tiles), gate, up, down, tile=tile, impl=impl),
        np.float32)
    with jax.default_matmul_precision("highest"):
        mapped, kernel = run("xla"), run("interpret")
    assert (mapped[n_tiles * tile:] == 0).all() and (kernel[n_tiles * tile:] == 0).all()
    assert n_tiles == 0 or np.abs(mapped).max() > 1.0
    np.testing.assert_allclose(
        kernel, mapped, atol=1e-5 if dtype == "float32" else 0.05)


def test_the_gradient_through_the_differentiable_spelling_is_the_reference_s(cca):
    """Teacher forcing may differentiate the layer: ``differentiable=True``
    takes the compiled map in one chunk, and its gradient with respect to the
    rows and every expert's matrices is the reference's."""
    N, E = 40, 4
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    stack = _stacked(rng, E)
    chosen = jnp.asarray(rng.integers(0, E + 1, size=N))
    weight = jnp.asarray(rng.uniform(0.2, 1.0, size=N), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    sizes = dict(experts_held=E, expert_share_index=0)

    def mine(x, stack):
        out, _ = experts.held_experts(
            x, chosen[:, None], weight[:, None], jnp.ones((N,), bool), *stack,
            0, E, differentiable=True)
        return (out * cot).sum()

    def theirs(x, stack):
        w = (jax.nn.one_hot(chosen, E + 1) * weight[:, None])[:, :E]
        return (cca.experts(stack, sizes, x, w, lambda y: y) * cot).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(mine, argnums=(0, 1)))(x, stack)
        want = jax.jit(jax.grad(theirs, argnums=(0, 1)))(x, stack)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.abs(np.asarray(b)).max() > 0.05
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def _primitives(jaxpr, found=None):
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] = found.get(eqn.primitive.name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("spelled", ["no_layer", "a_layer"])
def test_a_layer_sorts_once_and_loops_over_no_expert(spelled, k, backend, monkeypatch):
    """The mechanism, held in place: whatever the number of held experts the
    traced function holds one sort, no scatter and one loop (the compiled
    map's, off the TPU) or none (around the kernel, on it), with and without
    ``layer``; where the sorted copy of several experts a token would outgrow
    ``CHUNK_BYTES`` it is the walk: a loop and a scatter-add a held expert."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    N, E = 64, 16

    def count(held: int):
        lead = (held,) if spelled == "no_layer" else (2, held)
        shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (N, H), lead + (H, M), lead + (H, M), lead + (M, H))]

        def walk(x, gate, up, down, chosen, weights, index):
            with_layer = {} if spelled == "no_layer" else {"layer": index}
            return experts.held_experts(
                x, chosen, weights, jnp.ones((N,), bool), gate, up, down, 0,
                E, False, **with_layer)

        return _primitives(jax.make_jaxpr(walk)(
            *shapes, jax.ShapeDtypeStruct((N, k), jnp.int32),
            jax.ShapeDtypeStruct((N, k), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32)).jaxpr)

    few, many = count(2), count(12)
    loops = lambda found: sum(  # noqa: E731
        found.get(name, 0) for name in ("while", "scan"))
    assert few.get("sort") == many.get("sort") == 1
    assert loops(few) == loops(many) == (backend == "cpu")
    assert few.get("pallas_call", 0) == many.get("pallas_call", 0) == \
        (backend == "tpu")
    assert "scatter-add" not in few and "scatter-add" not in many
    if k > 1:
        # many rows of several experts a token: an expert at a time
        monkeypatch.setattr(experts, "CHUNK_BYTES", 0)
        walked = count(12)
        assert walked.get("sort") == 1 and loops(walked) == 12 and \
            walked.get("scatter-add") == 12 and "pallas_call" not in walked
