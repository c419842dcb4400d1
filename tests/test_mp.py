"""Flagship-XL model parallelism: partition rules, the compile layer, and
the sharded-vocab decode kernels (train/mesh.py, parallel/compile.py,
ops/decode_mp.py).

The parity pins run the mp>=2 shard_map programs on the 8 fake CPU devices
(conftest.py) — the per-shard kernel falls back to its jnp composite there
(interpret mode), the exact contract the replicated kernel tests use.
Tokens and beam candidates must be BIT-exact vs the replicated references;
logprobs/scores/carries get a few-f32-ulp allowance (the cross-shard
logsumexp reassociates, and the shard_map program jit-fuses differently
than the eager reference).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from cst_captioning_tpu.config.config import (
    EOS_ID,
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
)
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.models.captioner import CaptionModel as CM
from cst_captioning_tpu.ops.decode_mp import (
    mp_beam_step,
    mp_cell_specs,
    mp_decode_stride,
)
from cst_captioning_tpu.ops import decode_mp
from cst_captioning_tpu.ops.decode_pallas import (
    _reference_beam_topk,
    _reference_stride,
)
from cst_captioning_tpu.parallel.comms import ledger, mp_shard_view
from cst_captioning_tpu.parallel.compile import (
    CompileError,
    CompilePlan,
    compile_fn,
    partition,
)
from cst_captioning_tpu.parallel.submesh import (
    grow_actors,
    plan_submesh,
    shrink_actors,
)
from cst_captioning_tpu.train.mesh import (
    MP_PARAM_PARTITION_RULES,
    PARAM_PARTITION_RULES,
    make_mesh,
    match_partition_rules,
    match_rule,
    param_partition_specs,
    param_path_names,
    rule_coverage,
    rule_provenance,
)

ULP = 5e-6  # few-f32-ulp allowance for reassociated logsumexp / jit fusion


def _setup(V, B, d, F, K, dtype="float32", L=1, seed=0):
    cfg = ModelConfig(
        vocab_size=V, modalities=(("resnet", 16),), d_embed=d, d_hidden=d,
        d_att=max(4, d // 2), encoder="temporal_attention", dropout=0.0,
        max_len=8, max_frames=F, dtype=dtype, num_layers=L,
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(seed)
    feats = {"resnet": jnp.asarray(rng.normal(size=(B, F, 16)), jnp.float32)}
    masks = {"resnet": jnp.asarray(
        np.arange(F)[None] < rng.integers(2, F + 1, size=(B, 1)), jnp.float32
    )}
    labels = jnp.asarray(rng.integers(4, V, size=(B, 8)), jnp.int32)
    params = model.init(jax.random.key(0), feats, masks, labels)
    enc = model.apply(params, feats, masks, method=CM.encode)
    G = 1 + K
    carry = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), enc.carry
    )
    token = jnp.asarray(rng.integers(1, V, size=(G, B)), jnp.int32)
    return model, params, enc, carry, token, rng


# ---- partition rule tables ---------------------------------------------------


def test_match_rule_first_match_wins():
    rules = (
        ("specific", r"a/b/kernel", P("mp")),
        ("broad", r"a/.*", P()),
    )
    assert match_rule(rules, "a/b/kernel") == ("specific", P("mp"))
    assert match_rule(rules, "a/b/bias") == ("broad", P())
    # swapped order: the broad family shadows the specific one — order IS
    # the semantics (GL018 flags genuinely dead rows)
    shadowed = (rules[1], rules[0])
    assert match_rule(shadowed, "a/b/kernel") == ("broad", P())


def test_match_rule_requires_fullmatch_and_raises_on_no_match():
    rules = (("fam", r"params/x", P()),)
    with pytest.raises(ValueError, match="matches no partition rule"):
        match_rule(rules, "params/x/kernel")  # prefix is not fullmatch
    with pytest.raises(ValueError, match="matches no partition rule"):
        match_rule(MP_PARAM_PARTITION_RULES, "params/new_head/kernel")


def test_mp_rules_route_real_param_tree():
    """The flagship table puts the vocab head / embedding / gate matrices
    on 'mp' and replicates everything upstream — checked on a REAL
    2-layer param tree, not fixture strings."""
    model, params, *_ = _setup(V=24, B=2, d=8, F=3, K=1, L=2)
    specs = match_partition_rules(MP_PARAM_PARTITION_RULES, params)
    flat = dict(zip(
        param_path_names(params),
        jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P)
        ),
    ))
    assert flat["params/cell/word_embed/embedding"] == P("mp")
    assert flat["params/cell/out_proj/kernel"] == P(None, "mp")
    assert flat["params/cell/out_proj/bias"] == P("mp")
    assert flat["params/cell/lstm0/ii/kernel"] == P(None, "mp")
    assert flat["params/cell/lstm1/hf/kernel"] == P(None, "mp")
    assert flat["params/cell/lstm0/hf/bias"] == P("mp")
    assert flat["params/cell/attention/query_proj/kernel"] == P()
    assert flat["params/init_h0/kernel"] == P()
    # full coverage both ways, both tables, on the live tree
    names = list(flat)
    for rules in (PARAM_PARTITION_RULES, MP_PARAM_PARTITION_RULES):
        unmatched, unruled = rule_coverage(names, rules=rules)
        assert unmatched == [] and unruled == []


def test_dp_table_is_fully_replicated_and_provenance_names_rules():
    model, params, *_ = _setup(V=24, B=2, d=8, F=3, K=1)
    specs = param_partition_specs(params)  # default: the canonical table
    assert all(
        s == P() for s in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P)
        )
    )
    prov = rule_provenance(
        MP_PARAM_PARTITION_RULES, ["params/cell/out_proj/kernel"]
    )
    assert prov == {"params/cell/out_proj/kernel": "output_head_kernel"}


# ---- make_mesh ---------------------------------------------------------------


def test_make_mesh_mp_grid_and_degenerate():
    mesh = make_mesh(mp_devices=2)
    assert mesh.axis_names == ("data", "mp")
    assert mesh.shape["data"] == 4 and mesh.shape["mp"] == 2
    # mp=1 degenerates to the exact pre-mp 1-D mesh
    flat = make_mesh(mp_devices=1)
    assert flat.axis_names == ("data",)
    assert flat.devices.tolist() == make_mesh().devices.tolist()


def test_make_mesh_rejects_bad_mp():
    with pytest.raises(ValueError, match="must divide"):
        make_mesh(mp_devices=3)
    with pytest.raises(ValueError, match="cannot compose"):
        make_mesh(seq_devices=2, mp_devices=2)


# ---- sharded-vocab stride ----------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "mp",
    # mp=2 is THE tier-1 acceptance pin; the mp=4 twin proves the merges
    # generalize past two shards but costs another 4-way CPU mesh compile,
    # so it rides the slow tier with the other redundant-compile sweeps
    [2, pytest.param(4, marks=pytest.mark.slow)],
)
def test_mp_stride_matches_replicated(dtype, mp):
    """Tokens BIT-exact vs the replicated stride composite across an
    eos-ragged rollout (min_len block + lanes finishing at different
    steps); logprobs/carry within the ulp allowance."""
    V, B, d, F, K, S = 24, 5, 12, 6, 2, 6
    model, params, enc, carry, token, rng = _setup(V, B, d, F, K, dtype)
    cell = params["params"]["cell"]
    finished = jnp.zeros((1 + K, B), bool)
    noise = jnp.asarray(rng.gumbel(size=(S, K, B, V)), jnp.float32)
    t0 = jnp.asarray(0, jnp.int32)

    c_r, tok_r, lp_r = _reference_stride(
        cell, carry, token, finished, enc.memory, enc.memory_proj,
        enc.memory_mask, noise, t0, steps=S, temperature=0.7, min_len=2,
    )
    mesh = make_mesh(mp_devices=mp)
    c_m, tok_m, lp_m = mp_decode_stride(
        cell, carry, token, finished, enc.memory, enc.memory_proj,
        enc.memory_mask, noise, t0, mesh=mesh, steps=S, temperature=0.7,
        min_len=2,
    )
    np.testing.assert_array_equal(np.asarray(tok_m), np.asarray(tok_r))
    np.testing.assert_allclose(
        np.asarray(lp_m), np.asarray(lp_r), atol=ULP, rtol=0
    )
    for a, b in zip(jax.tree.leaves(c_m), jax.tree.leaves(c_r)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-3 if dtype == "bfloat16" else ULP, rtol=0,
        )
    # the rollout actually went ragged: some lane hit EOS mid-stride,
    # some row kept going (otherwise the freeze path wasn't exercised)
    assert bool((np.asarray(tok_r) == EOS_ID).any())


def test_mp_stride_respects_prefinished_lanes():
    V, B, d, F, K, S = 24, 4, 12, 5, 2, 4
    model, params, enc, carry, token, rng = _setup(V, B, d, F, K)
    cell = params["params"]["cell"]
    finished = jnp.zeros((1 + K, B), bool).at[1, :2].set(True)
    noise = jnp.asarray(rng.gumbel(size=(S, K, B, V)), jnp.float32)
    mesh = make_mesh(mp_devices=2)
    _c, tok, lp = mp_decode_stride(
        cell, carry, token, finished, enc.memory, enc.memory_proj,
        enc.memory_mask, noise, jnp.asarray(3, jnp.int32), mesh=mesh,
        steps=S, temperature=1.0, min_len=0,
    )
    assert (np.asarray(tok)[:, 1, :2] == 0).all()  # PAD forever
    assert (np.asarray(lp)[:, 1, :2] == 0.0).all()


def test_mp_stride_program_cache_reuses_compiled_program():
    """Repeated strides (the serving loop's shape) must NOT rebuild the
    shard_map program — the lru_cache keyed on (mesh, structure, knobs)
    is what keeps the jit cache warm."""
    V, B, d, F, K, S = 24, 3, 8, 4, 1, 2
    model, params, enc, carry, token, rng = _setup(V, B, d, F, K)
    cell = params["params"]["cell"]
    finished = jnp.zeros((1 + K, B), bool)
    noise = jnp.asarray(rng.gumbel(size=(S, K, B, V)), jnp.float32)
    mesh = make_mesh(mp_devices=2)
    before = decode_mp._stride_program.cache_info()
    args = (cell, carry, token, finished, enc.memory, enc.memory_proj,
            enc.memory_mask, noise, jnp.asarray(0, jnp.int32))
    mp_decode_stride(*args, mesh=mesh, steps=S, temperature=1.0)
    mp_decode_stride(*args, mesh=mesh, steps=S, temperature=1.0)
    after = decode_mp._stride_program.cache_info()
    assert after.hits >= before.hits + 1


def test_mp_stride_validates_inputs():
    fake = {"out_proj": {"kernel": np.zeros((4, 23), np.float32)},
            "word_embed": {"embedding": np.zeros((23, 4), np.float32)}}
    mesh = make_mesh(mp_devices=2)
    with pytest.raises(ValueError, match="does not divide"):
        mp_decode_stride(fake, None, None, None, None, None, None,
                         np.zeros((1, 1, 1, 23)), 0, mesh=mesh, steps=1)
    with pytest.raises(ValueError, match=r"no 'mp' axis"):
        mp_decode_stride(fake, None, None, None, None, None, None,
                         np.zeros((1, 1, 1, 24)), 0, mesh=make_mesh(),
                         steps=1)
    fake24 = {"out_proj": {"kernel": np.zeros((4, 24), np.float32)},
              "word_embed": {"embedding": np.zeros((24, 4), np.float32)}}
    with pytest.raises(ValueError, match="noise vocab dim"):
        mp_decode_stride(fake24, None, None, None, None, None, None,
                         np.zeros((1, 1, 1, 23)), 0, mesh=mesh, steps=1)


# ---- sharded-vocab beam ------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "mp",
    # mp=2 is THE tier-1 acceptance pin; the mp=4 twin proves the merges
    # generalize past two shards but costs another 4-way CPU mesh compile,
    # so it rides the slow tier with the other redundant-compile sweeps
    [2, pytest.param(4, marks=pytest.mark.slow)],
)
def test_mp_beam_step_matches_replicated(dtype, mp):
    """Candidate flat ids BIT-exact (including top_k tie order — the
    finished lane's PAD continuation manufactures exact score ties);
    scores within the ulp allowance."""
    V, B, d, F, W = 24, 4, 12, 5, 3
    model, params, enc, carry0, _tok, rng = _setup(V, B, d, F, K=W - 1,
                                                   dtype=dtype)
    cell = params["params"]["cell"]
    carry = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), enc.carry
    )
    token = jnp.asarray(rng.integers(1, V, size=(W, B)), jnp.int32)
    finished = jnp.zeros((W, B), bool).at[W - 1].set(True)
    scores = jnp.asarray(rng.normal(size=(W, B)), jnp.float32)

    _cr, ts_r, fl_r = _reference_beam_topk(
        cell, carry, token, finished, scores, enc.memory, enc.memory_proj,
        enc.memory_mask, t=jnp.asarray(1, jnp.int32), min_len=2,
    )
    mesh = make_mesh(mp_devices=mp)
    _cm, ts_m, fl_m = mp_beam_step(
        cell, carry, token, finished, scores, enc.memory, enc.memory_proj,
        enc.memory_mask, mesh=mesh, t=1, min_len=2,
    )
    np.testing.assert_array_equal(np.asarray(fl_m), np.asarray(fl_r))
    np.testing.assert_allclose(
        np.asarray(ts_m), np.asarray(ts_r), atol=ULP, rtol=0
    )


def test_mp_beam_step_rejects_wide_beam():
    fake = {"out_proj": {"kernel": np.zeros((4, 8), np.float32)},
            "word_embed": {"embedding": np.zeros((8, 4), np.float32)}}
    mesh = make_mesh(mp_devices=2)
    with pytest.raises(ValueError, match="beam width"):
        mp_beam_step(fake, None, np.zeros((5, 2), np.int32), None, None,
                     None, None, None, mesh=mesh, t=0)


def test_mp_cell_specs_shard_only_vocab_families():
    model, params, *_ = _setup(V=24, B=2, d=8, F=3, K=1)
    cell = params["params"]["cell"]
    specs = mp_cell_specs(cell)
    assert specs["word_embed"]["embedding"] == P("mp")
    assert specs["out_proj"]["kernel"] == P(None, "mp")
    assert specs["out_proj"]["bias"] == P("mp")
    # the decode kernels consume the recurrent weights whole -> replicated
    # on this path even though the TRAINING table shards the gates
    assert specs["lstm0"]["ii"]["kernel"] == P()
    assert specs["attention"]["query_proj"]["kernel"] == P()


# ---- compile layer -----------------------------------------------------------


def test_compile_fn_jit_mode_is_bit_identical_to_plain_jit():
    def f(x, y):
        return x @ y + jnp.tanh(x).sum()

    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 8)),
                    jnp.float32)
    a = compile_fn(f, CompilePlan())(x, x)
    b = jax.jit(f)(x, x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compile_fn_shard_map_is_bit_identical_to_direct_spelling():
    from jax import shard_map

    mesh = make_mesh()

    def mean_grad(x):
        return jax.lax.pmean(jnp.sin(x) * x, "data")

    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 4)),
                    jnp.float32)
    plan = CompilePlan(mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    a = compile_fn(mean_grad, plan)(x)
    b = jax.jit(shard_map(mean_grad, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data")))(x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compile_plan_error_cases():
    mesh = make_mesh()
    with pytest.raises(CompileError, match="unknown compile mode"):
        CompilePlan(how="vmap")
    with pytest.raises(CompileError, match="one-sided"):
        CompilePlan(mesh=mesh, in_specs=P("data"))
    with pytest.raises(CompileError, match="ignores partition specs"):
        CompilePlan(how="jit", mesh=mesh, in_specs=P("data"),
                    out_specs=P("data")).resolve()
    with pytest.raises(CompileError, match="needs a mesh"):
        CompilePlan(how="shard_map", in_specs=P("data"),
                    out_specs=P("data")).resolve()
    with pytest.raises(CompileError, match="needs in_specs"):
        CompilePlan(how="pjit", mesh=mesh).resolve()
    with pytest.raises(CompileError, match="only builds shard_map"):
        partition(lambda x: x, CompilePlan())


# ---- dp x mp submesh compose -------------------------------------------------


def test_plan_submesh_splits_2d_mesh_along_data_only():
    mesh = make_mesh(mp_devices=2)  # (4, 2) dp x mp
    plan = plan_submesh(mesh, actor_fraction=0.5, batch_size=4)
    assert not plan.shared
    assert plan.actor.axis_names == ("data", "mp")
    assert plan.learner.axis_names == ("data", "mp")
    assert plan.actor.shape["mp"] == 2 and plan.learner.shape["mp"] == 2
    assert plan.actor.shape["data"] + plan.learner.shape["data"] == 4
    # disjoint device sets covering the full mesh
    ad = {d.id for d in plan.actor_devices}
    ld = {d.id for d in plan.learner_devices}
    assert not (ad & ld) and len(ad | ld) == 8


def test_elastic_resize_rejects_2d_plans():
    mesh = make_mesh(mp_devices=2)
    plan = plan_submesh(mesh, actor_fraction=0.5, batch_size=4)
    with pytest.raises(ValueError, match="1-D"):
        shrink_actors(plan, 0, batch_size=4)
    with pytest.raises(ValueError, match="1-D"):
        grow_actors(plan, plan.actor_devices[0], plan, batch_size=4)


# ---- mp comms ledger ---------------------------------------------------------


def test_mp_shard_view_and_ledger_accounting():
    model, params, *_ = _setup(V=24, B=2, d=8, F=3, K=1)
    led1 = ledger(params, None)
    # mp=1 is bit-identical to the pre-mp ledger (the degenerate pin)
    assert ledger(params, None, mp_devices=1) == led1
    led2 = ledger(params, None, mp_devices=2)
    assert led2["bytes_on_wire_per_update"] < led1["bytes_on_wire_per_update"]
    # the saving is exactly half of every mp-sharded leaf's f32 payload
    view = mp_shard_view(params, 2)
    full = dict(zip(
        param_path_names(params), jax.tree_util.tree_leaves(params)
    ))
    sharded_f32 = 0
    for path, leaf in full.items():
        _fam, spec = match_rule(MP_PARAM_PARTITION_RULES, path)
        if any(a == "mp" for a in spec if a is not None):
            sharded_f32 += (leaf.size - -(-leaf.size // 2)) * 4
    assert led1["bytes_on_wire_per_update"] - \
        led2["bytes_on_wire_per_update"] == sharded_f32
    # shapes in the view: sharded leaves shrink, replicated leaves don't
    assert sum(l.size for l in jax.tree_util.tree_leaves(view)) < \
        sum(l.size for l in jax.tree_util.tree_leaves(params))


# ---- config validation -------------------------------------------------------


def test_experiment_config_validates_mp_devices():
    ok = ExperimentConfig(
        model=ModelConfig(vocab_size=512, d_hidden=128),
        mesh=MeshConfig(mp_devices=2, num_devices=8),
    )
    assert ok.mesh.mp_devices == 2
    with pytest.raises(ValueError, match="must be >= 1"):
        ExperimentConfig(mesh=MeshConfig(mp_devices=0))
    with pytest.raises(ValueError, match="pick one second mesh axis"):
        ExperimentConfig(
            model=ModelConfig(vocab_size=512, d_hidden=128),
            mesh=MeshConfig(mp_devices=2, seq_devices=2),
        )
    with pytest.raises(ValueError, match="vocab_size"):
        ExperimentConfig(
            model=ModelConfig(vocab_size=511, d_hidden=128),
            mesh=MeshConfig(mp_devices=2),
        )
    with pytest.raises(ValueError, match="d_hidden"):
        ExperimentConfig(
            model=ModelConfig(vocab_size=512, d_hidden=127),
            mesh=MeshConfig(mp_devices=2),
        )
    with pytest.raises(ValueError, match="num_devices"):
        ExperimentConfig(
            model=ModelConfig(vocab_size=512, d_hidden=128),
            mesh=MeshConfig(mp_devices=2, num_devices=3),
        )
