"""The chip's compiler, without the chip: every Pallas kernel and the two
default-path step programs compile for a DESCRIBED TPU v5e at the paper's
widths (``msrvtt_cst_consensus``: V=9000, d=512, d_att=256, 2x28 frames,
bf16, B=64, 1+K=6 lanes, stride 8).

Interpret mode — what every other kernel test runs — cannot see what Mosaic
refuses: block shapes off the (8, 128) tiling, unaligned DMA slices, more
fast memory than a kernel may hold. libtpu compiles for a topology that is
described and not attached, so those refusals surface here at no chip time.
Nothing RUNS in this file: a compile that passes says nothing about results
or speed (``chip_smoke.py`` on the chip does).

``jax.default_backend()`` still answers "cpu" here, which would send the
kernels down their interpret branch; the ``chip`` fixture steers that check
inside the test so the program keeps no option for it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from cst_captioning_tpu.config import get_preset
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.models.captioner import CaptionModel as CM
from cst_captioning_tpu.ops.decode_pallas import (
    fused_beam_step,
    fused_decode_step,
    fused_decode_stride,
    fused_decode_stride_paged,
)
from cst_captioning_tpu.rl import scst
from cst_captioning_tpu.rl.scst import make_rl_decode
from cst_captioning_tpu.train.schedule import make_optimizer
from cst_captioning_tpu.train.state import create_train_state
from cst_captioning_tpu.train.steps import make_xe_step

B, K, S, BEAM = 64, 5, 8, 5
G = 1 + K
PRESET_BLOCK = 32   # the kernels' default batch block (offline decode)
SERVING_BLOCK = 1   # CaptionService's kernel_block_b default
# serving pages (n_mod x frame_bucket slots): the service default — one
# 56-slot page per max-length clip — at the offline block, ragged 8-slot
# pages (frame bucket 4, seven per row) at the serving block. The kernel
# unrolls block_b x pages-per-row DMAs, so this pairing also keeps the
# trace small
PAGE = {PRESET_BLOCK: 56, SERVING_BLOCK: 8}


@pytest.fixture(scope="module")
def chip():
    """One described v5e device; persistent cache off around the module (a
    described-chip executable is written to the cache but cannot be read
    back without a chip — the next compile would warn and recompile)."""
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / no topology support on this box
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    yield SingleDeviceSharding(topo.devices[0])
    patch.undo()
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.fixture(scope="module")
def shapes():
    """Abstract params / encoder output / lane state at preset widths."""
    cfg = get_preset("msrvtt_cst_consensus")
    mc = cfg.model
    model = CaptionModel(mc)
    feats = {n: _sds((B, mc.max_frames, d), jnp.float32)
             for n, d in mc.modalities}
    masks = {n: _sds((B, mc.max_frames), jnp.float32)
             for n, _ in mc.modalities}
    labels = _sds((B, mc.max_len), jnp.int32)
    params = jax.eval_shape(
        lambda f, m, lab: model.init(jax.random.key(0), f, m, lab),
        feats, masks, labels,
    )
    enc = jax.eval_shape(
        lambda p, f, m: model.apply(p, f, m, method=CM.encode),
        params, feats, masks,
    )

    def lanes(n):
        return jax.tree.map(lambda x: _sds((n,) + x.shape, x.dtype), enc.carry)

    return dict(
        cfg=cfg, model=model, feats=feats, masks=masks, labels=labels,
        params=params, cell=params["params"]["cell"], enc=enc, lanes=lanes,
    )


def _compile(fn, chip, *args):
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), args
    )
    return jax.jit(fn).lower(*args).compile()


def _kernel_case(name, block_b, sh):
    """-> (fn, abstract args) for one kernel at one batch block."""
    enc, cell, mc = sh["enc"], sh["cell"], sh["cfg"].model
    V, M = mc.vocab_size, enc.memory.shape[1]
    bank = (enc.memory, enc.memory_proj, enc.memory_mask)
    tok, fin = _sds((G, B), jnp.int32), _sds((G, B), jnp.bool_)
    noise = _sds((S, K, B, V), jnp.float32)
    i32 = _sds((), jnp.int32)
    if name == "step":
        return (
            lambda c, ca, t, m, p, k: fused_decode_step(
                c, ca, t, m, p, k, block_b=block_b),
            (cell, sh["lanes"](G), tok) + bank,
        )
    if name == "stride":
        return (
            lambda c, ca, t, f, m, p, k, n, t0, na: fused_decode_stride(
                c, ca, t, f, m, p, k, n, t0, na, steps=S, block_b=block_b),
            (cell, sh["lanes"](G), tok, fin) + bank + (noise, i32, i32),
        )
    if name == "paged_stride":
        page = PAGE[block_b]
        width = -(-M // page)
        n_pages = B * width + 1
        pools = (
            _sds((n_pages, page, enc.memory.shape[2]), enc.memory.dtype),
            _sds((n_pages, page, mc.d_att), enc.memory_proj.dtype),
            _sds((n_pages, page), jnp.float32),
        )
        table, lens = _sds((B, width), jnp.int32), _sds((B,), jnp.int32)
        return (
            lambda c, ca, t, f, m, p, k, tb, n, t0, na, ln:
            fused_decode_stride_paged(
                c, ca, t, f, m, p, k, tb, n, t0, na, steps=S,
                block_b=block_b, mem_lens=ln),
            (cell, sh["lanes"](G), tok, fin) + pools
            + (table, noise, i32, i32, lens),
        )
    if name == "beam":
        tokw, finw = _sds((BEAM, B), jnp.int32), _sds((BEAM, B), jnp.bool_)
        scores = _sds((BEAM, B), jnp.float32)
        return (
            lambda c, ca, t, f, s, m, p, k, tt: fused_beam_step(
                c, ca, t, f, s, m, p, k, t=tt, block_b=block_b),
            (cell, sh["lanes"](BEAM), tokw, finw, scores) + bank + (i32,),
        )
    raise AssertionError(name)


@pytest.mark.parametrize("name,block_b", [
    ("step", PRESET_BLOCK), ("step", SERVING_BLOCK),
    ("stride", PRESET_BLOCK), ("stride", SERVING_BLOCK),
    ("paged_stride", PRESET_BLOCK), ("paged_stride", SERVING_BLOCK),
    ("beam", PRESET_BLOCK), ("beam", SERVING_BLOCK),
])
def test_kernel_compiles_for_v5e(name, block_b, chip, shapes):
    """Mosaic accepts the kernel at preset widths — at the offline block
    and at the serving block (which the chip path rounds up to a legal
    sublane tile) — and the custom call is really in the program."""
    fn, args = _kernel_case(name, block_b, shapes)
    compiled = _compile(fn, chip, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_stride_refuses_unaligned_page_on_chip(chip, shapes):
    """A page that is not whole sublane tiles cannot be DMA'd: the chip path
    raises with the reason at build time instead of handing Mosaic a slice
    it rejects (and never falls back to the composite)."""
    fn, args = _kernel_case("paged_stride", PRESET_BLOCK, shapes)
    args = list(args)
    for i in (4, 5, 6):   # the three pools: page axis 56 -> 14
        p = args[i]
        args[i] = _sds((p.shape[0], 14) + p.shape[2:], p.dtype)
    with pytest.raises(ValueError, match="page_size"):
        _compile(fn, chip, *args)


def test_row_blocked_update_compiles_for_v5e_in_half_the_memory(
        chip, shapes, monkeypatch):
    """The north-star update (B=1792, K=5, update_chunks=5, donated and
    guarded as the Trainer builds it) for one described chip: cut into row
    blocks by the shape rule it compiles, and the compiler plans no more
    than half the temporaries of the uncut program (7.65 GB). Two compiles
    of about 9 s."""
    cfg, model = shapes["cfg"], shapes["model"]
    mc, rows = cfg.model, 1792
    feats = {n: _sds((rows, mc.max_frames, d), jnp.float32)
             for n, d in mc.modalities}
    masks = {n: _sds((rows, mc.max_frames), jnp.float32)
             for n, _ in mc.modalities}
    tx = make_optimizer(cfg.train, steps_per_epoch=4)
    state = jax.eval_shape(
        lambda f, m, lab: create_train_state(model, tx, (f, m, lab)),
        feats, masks, _sds((rows, mc.max_len), jnp.int32),
    )
    args = (state, feats, masks, _sds((K, rows, mc.max_len), jnp.int32),
            _sds((K, rows), jnp.float32), _sds((rows,), jnp.float32))

    def temp_bytes():
        update = scst.make_rl_update(model, chunks=K, donate=True, guard=True)
        return _compile(update, chip, *args).memory_analysis(
        ).temp_size_in_bytes

    assert scst._row_block(rows, scst._ROW_BLOCK_CAP) < rows
    cut = temp_bytes()
    monkeypatch.setattr(scst, "_ROW_BLOCK_CAP", rows)
    uncut = temp_bytes()
    assert 0 < cut <= uncut / 2, (cut, uncut)


# The two default-path step programs take ~10 s each to compile — a price
# the tier-1 budget (already overdrawn) cannot carry, so they run with
# `-m slow`; chip_smoke.py compiles both for real on every chip run.


@pytest.mark.slow
def test_default_rl_decode_compiles_for_v5e(chip, shapes):
    """The default (XLA) fused RL decode — while_loop over scan(8) strides
    with argsort/gather/scatter compaction — at preset widths."""
    model = shapes["model"]
    decode = make_rl_decode(
        model, K, max_len=model.cfg.max_len, with_greedy=True
    )
    rng = jax.eval_shape(lambda: jax.random.key(0))
    compiled = _compile(
        decode, chip, shapes["params"], shapes["feats"], shapes["masks"], rng
    )
    assert compiled.memory_analysis().temp_size_in_bytes > 0


@pytest.mark.slow
def test_xe_step_compiles_for_v5e(chip, shapes):
    """The donated, guarded XE train step Trainer builds on one device."""
    cfg, model = shapes["cfg"], shapes["model"]
    tx = make_optimizer(cfg.train, steps_per_epoch=4)
    state = jax.eval_shape(
        lambda f, m, lab: create_train_state(model, tx, (f, m, lab)),
        shapes["feats"], shapes["masks"], shapes["labels"],
    )
    step = make_xe_step(model, donate=True, guard=True)
    mask = _sds((B, cfg.model.max_len), jnp.float32)
    weights = _sds((B,), jnp.float32)
    compiled = _compile(
        step, chip, state, shapes["feats"], shapes["masks"],
        shapes["labels"], mask, weights,
    )
    assert compiled.memory_analysis().temp_size_in_bytes > 0


# ---- the sparse/linear decoder's two prefix kernels (PR 38) -------------------


@pytest.mark.parametrize("mixer", ["sparse_attn_prefill", "linear_attn_prefill"])
def test_prefix_mixer_kernel_compiles_for_v5e_at_the_published_widths(mixer, chip):
    """MiniCPM-SALA's two mixers over a 16384-position prefix of two clips,
    bfloat16, at the preset's tiles: Mosaic accepts each, and the custom call
    carries the kernel's name — which is how the benchmark's readers find its
    operations in a device trace (``layer_metrics/_kernels.py``)."""
    from cst_captioning_tpu.models.sparse_linear import LINEAR_CHUNK, sparse_spec
    from cst_captioning_tpu.ops import linear_attention, sparse_attention

    mc = get_preset("minicpm_sala_8l_eval_beam5").model
    rows, P, bf16 = 2, mc.max_frames, jnp.bfloat16
    n = _sds((rows,), jnp.int32)
    if mixer == "sparse_attn_prefill":
        spec = sparse_spec(mc)
        H, G, d = mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim
        kv = _sds((rows, P, G, d), bf16)
        ck = _sds((rows, sparse_attention.n_compressed(P, spec), G, d), jnp.float32)
        compiled = _compile(
            lambda q, k, v, c, n: sparse_attention.sparse_prefill(
                q, k, v, c, n, spec, impl="pallas"),
            chip, _sds((rows, P, H, d), bf16), kv, kv, ck, n)
    else:
        H, d = mc.lightning_nh, mc.lightning_head_dim
        x = _sds((rows, P, H, d), bf16)
        compiled = _compile(
            lambda q, k, v, s, n: linear_attention.chunked_linear_attention(
                q, k, v, s, n, LINEAR_CHUNK, "pallas"),
            chip, x, x, x, _sds((H,), jnp.float32), n)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{mixer}" in text


# ---- the EVA decoder's prefix kernel (PR 42) ----------------------------------


def test_eva_prefix_kernel_compiles_for_v5e_at_the_published_widths(chip):
    """EvaByte's attention over a 16384-position prefix of two clips,
    bfloat16, 32 heads of 128, windows of 2048 and chunks of 16, at the
    program's tiles: Mosaic accepts it, and the custom call carries the
    kernel's name, which is how the benchmark's readers find its operations
    in a device trace (``layer_metrics/_kernels.py``)."""
    from cst_captioning_tpu.models.eva import eva_spec, head_dim
    from cst_captioning_tpu.ops import eva_attention

    mc = get_preset("evabyte_8l_eval_beam5").model
    spec = eva_spec(mc)
    rows, P, H, d = 2, mc.max_frames, mc.num_attention_heads, head_dim(mc)
    x = _sds((rows, P, H, d), jnp.bfloat16)
    pooled = _sds((rows, P // spec.chunk, H, d), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, ks, vs, n: eva_attention.eva_prefill(
            q, k, v, ks, vs, n, spec, impl="pallas"),
        chip, x, x, x, pooled, pooled, _sds((rows,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%eva_attn_prefill" in text


# ---- the window/full decoder's two prefix kernels (PR 45) ---------------------


@pytest.mark.parametrize("kernel", ["window_attn_prefill", "full_attn_prefill"])
def test_window_and_full_prefix_kernels_compile_for_v5e_at_the_published_widths(
        kernel, chip):
    """MiMo-V2.5's two attention kinds over a 16384-position prefix of two
    clips, bfloat16, 64 query heads over 8 (window) or 4 (full) key/value
    heads, keys of 192 (not a multiple of the 128 lanes: head-major blocks
    whose last axis is the whole 192) and values of 128, at the program's
    tiles: Mosaic accepts each, and the custom call carries the kernel's
    name, which is how the benchmark's readers tell the two apart in a device
    trace (``layer_metrics/_kernels.py``)."""
    from cst_captioning_tpu.ops import window_attention

    mc = get_preset("mimo_v2_5_ep16_eval_beam5").model
    rows, P, H = 2, mc.max_frames, mc.num_attention_heads
    dk, dv, bf16 = mc.head_dim, mc.v_head_dim, jnp.bfloat16
    assert (dk, dv) == (192, 128)
    n = _sds((rows,), jnp.int32)
    if kernel == "window_attn_prefill":
        G = mc.swa_num_key_value_heads
        compiled = _compile(
            lambda q, k, v, s, n: window_attention.window_prefill(
                q, k, v, s, n, mc.sliding_window, impl="pallas"),
            chip, _sds((rows, P, H, dk), bf16), _sds((rows, P, G, dk), bf16),
            _sds((rows, P, G, dv), bf16), _sds((H,), jnp.float32), n)
    else:
        G = mc.num_key_value_heads
        compiled = _compile(
            lambda q, k, v, n: window_attention.full_prefill(
                q, k, v, n, impl="pallas"),
            chip, _sds((rows, P, H, dk), bf16), _sds((rows, P, G, dk), bf16),
            _sds((rows, P, G, dv), bf16), n)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{kernel}" in text


# ---- the compressed-latent decoder's prefix kernel (PR 50) --------------------


def test_cca_prefix_kernel_compiles_for_v5e_at_the_published_widths(chip):
    """ZAYA1-8B's latent attention over a 16384-position prefix of two clips,
    bfloat16, 8 query heads over 2 key/value heads of 128 (4 query heads a
    block), at the program's tiles: the full-attention flash kernel body under
    its third name, by which the benchmark's readers find it in a device
    trace (``layer_metrics/cca_attn_ms_per_step.py``)."""
    from cst_captioning_tpu.ops import window_attention

    mc = get_preset("zaya1_8b_20l_eval_beam5").model
    rows, P = 2, mc.max_frames
    H, G, d = mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim
    assert (H, G, d) == (8, 2, 128)
    compiled = _compile(
        lambda q, k, v, n: window_attention.cca_prefill(q, k, v, n, impl="pallas"),
        chip, _sds((rows, P, H, d), jnp.bfloat16),
        _sds((rows, P, G, d), jnp.bfloat16), _sds((rows, P, G, d), jnp.bfloat16),
        _sds((rows,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%cca_attn_prefill" in text


# ---- the held experts' grouped product (PR 51) -------------------------------


@pytest.mark.parametrize("preset,rows,grouped", [
    ("zaya1_8b_20l_eval_beam5", 32768, True),
    ("mimo_v2_5_ep16_eval_beam5", 10, True),
    ("kimi_k2_ep32_eval_beam5", 1280, False)])
def test_the_held_experts_compile_for_v5e_at_the_published_widths(
        preset, rows, grouped, chip):
    """``held_experts`` of the three routed-expert cells (ZAYA's prefix of
    two clips of 16384 positions, MiMo's beam step of 10 lanes, Kimi's step of
    256 clips' five lanes), bfloat16, the experts' matrices as they are stored (ZAYA's
    every layer's in one stack, a traced layer). Where a token has one expert
    or the rows are few the held experts are one grouped product: the kernel
    is in the program under the name a device trace shows
    (``held_experts_gmm``), with no loop around it, and reads the stack
    through a view and no copy. Several experts a token over many rows (Kimi's
    step) walk an expert at a time: a loop a held expert and
    no kernel."""
    from cst_captioning_tpu.models import experts

    mc = get_preset(preset).model
    h, m, held, k = (mc.hidden_size, mc.moe_intermediate_size, mc.experts_held,
                     mc.num_experts_per_tok)
    stacked = mc.decoder == "cca_moe"
    lead = (mc.num_hidden_layers, held) if stacked else (held,)

    def walk(x, chosen, weights, gate, up, down, layer):
        out, tally = experts.held_experts(
            x, chosen, weights, jnp.ones((rows,), bool), gate, up, down, 0,
            mc.n_routed_experts, False, **({"layer": layer} if stacked else {}))
        return out.astype(x.dtype), tally

    compiled = _compile(
        walk, chip, _sds((rows, h), jnp.bfloat16), _sds((rows, k), jnp.int32),
        _sds((rows, k), jnp.float32), _sds(lead + (h, m), jnp.bfloat16),
        _sds(lead + (h, m), jnp.bfloat16), _sds(lead + (m, h), jnp.bfloat16),
        _sds((), jnp.int32))
    text = compiled.as_text()
    assert ("%held_experts_gmm" in text) == grouped
    assert text.count(" while(") == (0 if grouped else held)
    stack = "bf16[" + ",".join(map(str, lead + (h, m))) + "]"
    assert not [line for line in text.splitlines()
                if " copy(" in line and stack in line.split(" copy(")[0]]
    # the sorted copy of x and the products' output: no more than the parent's
    # float32 accumulator and its blocks took over a ZAYA prefix (613 MiB)
    if stacked and rows == 32768:
        assert compiled.memory_analysis().temp_size_in_bytes < 400 * 2**20
