"""``chip_smoke.py`` off the chip: what it must do WITHOUT a TPU, the small
rules it stands on, and (slow-marked) its phases walked at tiny widths.

The driver runs ``chip_smoke.py`` on a real chip after every PR, so the
phases' control flow is exercised there at full width; the CPU walk below
(~45 s one-chip mode, ~25 s mesh mode — compiles, even tiny ones, cost
seconds each) is what a builder runs BEFORE spending chip time
(``pytest tests/test_chip_smoke.py -m slow``), and stays out of the
overdrawn tier-1 budget. Tier-1 keeps the cheap guards: the script fails
honestly without a chip, a failed phase can never exit 0, the corpus is cut
exactly, the preset scale really is the presets' widths, the compile-cache
rule, and the no-default peak table.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402  (repo root on the path first)

from cst_captioning_tpu.config import get_preset  # noqa: E402
from cst_captioning_tpu.obs import flops  # noqa: E402
from cst_captioning_tpu.utils import compile_cache  # noqa: E402

TINY = cs.Scale(
    model_sets=(
        "model__modalities=(('resnet',16),('c3d',8))", "model__d_embed=16",
        "model__d_hidden=16", "model__d_att=8", "model__max_len=8",
        "model__max_frames=4", "model__dtype='float32'", "eval__max_len=8",
    ),
    modalities=(("resnet", 16), ("c3d", 8)), max_frames=4, vocab_words=40,
    train_videos=32, val_videos=8, test_videos=8, batch=8, large_batch=16,
    serve_requests=6, serve_capacity=2, serve_frames=(1, 4), frame_bucket=2,
    mesh_batches=2,
)


# ---- the script as the driver runs it, minus the chip ------------------------


def test_script_without_a_chip_exits_nonzero_with_ok_false():
    """Under JAX_PLATFORMS=cpu the script prints ``"ok": false`` with the
    device jax reported as its LAST stdout line and exits non-zero — it
    never carries on on the CPU."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=240, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}


def test_failed_phase_is_reported_and_never_swallowed(tmp_path, capsys):
    """A phase that raises prints ``ok: false`` with the error, the later
    phases still run, and the run is failed."""
    smoke = cs.Smoke(TINY, 0, str(tmp_path))

    def bad(_):
        raise ValueError("boom")

    def unmet(_):
        cs._require(False, "two is three")

    smoke.run_phase("good", lambda _: {"check": "nothing"})
    smoke.run_phase("bad", bad)
    smoke.run_phase("unmet", unmet)
    smoke.run_phase("after", lambda _: None)
    lines = [json.loads(line)
             for line in capsys.readouterr().out.strip().splitlines()]
    assert [(rec["phase"], rec["ok"]) for rec in lines] == [
        ("good", True), ("bad", False), ("unmet", False), ("after", True),
    ]
    assert "boom" in lines[1]["error"] and "two is three" in lines[2]["error"]
    assert all({"compile_s", "run_s"} <= set(rec) for rec in lines)
    assert smoke.failed == ["bad", "unmet"]


def test_preset_scale_is_the_presets_own_widths():
    """The chip run overrides NO model field: the corpus it writes matches
    the presets' vocab, modalities and frame budget, and its large batch is
    the benchmark cells' operating point."""
    mc = get_preset("msrvtt_cst_consensus").model
    assert cs.PRESET.model_sets == ()
    assert cs.PRESET.vocab_words + 4 == mc.vocab_size == 9000
    assert cs.PRESET.modalities == mc.modalities
    assert cs.PRESET.max_frames == mc.max_frames
    assert (cs.PRESET.large_batch, cs.PRESET.large_chunks) == (1792, 5)
    assert cs.PRESET.train_videos == 2 * cs.PRESET.large_batch
    # the paged kernel's DMA granule on the chip: whole 8-row sublane tiles
    assert (len(cs.PRESET.modalities) * cs.PRESET.frame_bucket) % 8 == 0


def test_corpus_is_cut_exactly(tmp_path):
    smoke = cs.Smoke(TINY, 3, str(tmp_path))
    cs.build_corpus(smoke)
    assert smoke.paths["vocab_size"] == TINY.vocab_words + 4
    for split, n in (("train", 32), ("val", 8), ("test", 8)):
        ds = smoke.dataset(split)
        try:
            assert len(ds) == n
        finally:
            ds.close()


# ---- the compile-cache rule --------------------------------------------------


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_env_is_left_to_jax(monkeypatch, cache_config):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code."""
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/somewhere/outside")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/somewhere/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch,
                                                      cache_config):
    """Unset: the fixed in-checkout path — no temp name, pid or time."""
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable_compile_cache() == want  # stable across calls


# ---- no default peak ----------------------------------------------------------


def test_peak_lookup_raises_for_a_kind_without_a_published_peak():
    with pytest.raises(KeyError, match="cpu"):
        flops.peak_flops("cpu")


def test_peak_lookup_knows_the_v5e():
    assert flops.peak_flops("TPU v5 lite") == 197e12


# ---- the phases, walked at tiny widths (slow: ~70 s of tiny compiles) --------


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_phases_walk_at_tiny_widths(chips, tmp_path, cache_config, capsys):
    """Control flow only: every phase of the mode runs in-process on the CPU
    (Pallas in interpret mode, so no ``tpu_custom_call`` is demanded) and
    passes its own checks. ``chips=4`` takes the mesh mode over however
    many virtual devices conftest gave."""
    assert cs.run(TINY, 0, chips, str(tmp_path))
    lines = [json.loads(line)
             for line in capsys.readouterr().out.strip().splitlines()]
    want = cs.MESH_PHASES if chips > 1 else cs.ONE_CHIP_PHASES
    assert [rec["phase"] for rec in lines[1:]] == [
        "corpus", *(name for name, _ in want)
    ]
    assert all(rec["ok"] for rec in lines[1:])
