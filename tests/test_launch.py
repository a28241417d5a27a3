"""Unit tests for the launch layer: HLO collective parsing, roofline math,
input-spec construction (no 512-device init — pure host-side logic)."""
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch.hlo_stats import collective_stats, op_histogram
from repro.launch.roofline import analyze, model_flops
from repro.launch import specs


SAMPLE_HLO = """
HloModule jit_step
%x.1 = bf16[128,1024]{1,0} parameter(0)
%y.2 = f32[256,512]{1,0} parameter(1)
%ag.3 = bf16[2048,1024]{1,0} all-gather(%x.1), replica_groups={{0,1}}
%ar.4 = f32[256,512]{1,0} all-reduce(%y.2), to_apply=%add
%rs.5 = f32[16,512]{1,0} reduce-scatter(%y.2), dimensions={0}
%cp.6 = bf16[128,1024]{1,0} collective-permute(%x.1), source_target_pairs={{0,1}}
%ags.7 = (bf16[128,1024], bf16[2048,1024]) all-gather-start(%x.1)
%agd.8 = bf16[2048,1024]{1,0} all-gather-done(%ags.7)
"""


def test_collective_stats_operand_bytes():
    st = collective_stats(SAMPLE_HLO)
    x_bytes = 128 * 1024 * 2
    y_bytes = 256 * 512 * 4
    assert st["by_type"]["all-gather"] == 2 * x_bytes  # ag.3 + ags.7 (done skipped)
    assert st["by_type"]["all-reduce"] == y_bytes
    assert st["by_type"]["reduce-scatter"] == y_bytes
    assert st["by_type"]["collective-permute"] == x_bytes
    assert st["count"] == 5
    assert st["total_bytes"] == sum(st["by_type"].values())


def test_op_histogram():
    h = op_histogram(SAMPLE_HLO)
    assert h.get("all-gather") == 1


def test_roofline_analyze_terms_and_dominance():
    cell = {
        "arch": "x", "shape": "train_4k", "mesh": "pod16x16", "kind": "train",
        "chips": 256, "seq_len": 4096, "global_batch": 256,
        "flops_per_device": 197e12,  # exactly 1 second of compute
        "bytes_per_device": 819e9 * 2,  # 2 seconds of HBM
        "collective_bytes_per_device": 50e9 * 0.5,  # 0.5 s of ICI
        "params_active": 1e9, "params_total": 1e9,
        "memory": {"argument_bytes": 2**30, "temp_bytes": 2**30,
                   "output_bytes": 0, "alias_bytes": 0},
    }
    r = analyze(cell)
    assert r["dominant"] == "memory"
    assert abs(r["compute_s"] - 1.0) < 1e-9
    assert abs(r["memory_s"] - 2.0) < 1e-9
    assert abs(r["collective_s"] - 0.5) < 1e-9
    assert r["fits_v5e_16g"]
    # 6 N D / (flops/dev * chips)
    want = 6 * 1e9 * 256 * 4096 / (197e12 * 256)
    assert abs(r["useful_compute_ratio"] - want) < 1e-9


def test_model_flops_kinds():
    base = {"params_active": 2e9, "global_batch": 32, "seq_len": 1000}
    assert model_flops({**base, "kind": "train"}) == 6 * 2e9 * 32 * 1000
    assert model_flops({**base, "kind": "prefill"}) == 2 * 2e9 * 32 * 1000
    assert model_flops({**base, "kind": "decode"}) == 2 * 2e9 * 32


@pytest.mark.parametrize("arch", ["qwen3_0_6b", "seamless_m4t_large_v2",
                                  "qwen2_vl_7b", "jamba_1_5_large_398b"])
def test_batch_specs_cover_modalities(arch):
    cfg = configs.get(arch)
    shape = configs.SHAPES["train_4k"]
    out = specs.batch_specs(cfg, shape, mesh=None, rules=None)
    assert out["tokens"].shape == (256, 4096)
    assert out["tokens"].dtype == jnp.int32
    if cfg.enc_dec:
        assert out["encoder_embeds"].shape == (256, 1024, cfg.d_model)
    if cfg.vision_len_ratio:
        assert out["vision_embeds"].shape == (256, 512, cfg.d_model)
        assert out["positions3"].shape == (3, 256, 4096)


def test_decode_specs_cache_structure():
    cfg = configs.get("jamba_1_5_large_398b")
    shape = configs.SHAPES["decode_32k"]
    caches, token, pos = specs.decode_specs(cfg, shape, mesh=None, rules=None)
    assert token.shape == (128, 1)
    assert pos.shape == ()
    # hybrid: attention position p3 has kv cache, mamba positions have h/conv
    assert set(caches["p3"]) == {"k", "v"}
    assert caches["p3"]["k"].shape == (9, 128, 32768, 8, 128)
    assert set(caches["p0"]) == {"h", "conv"}
    assert caches["p0"]["h"].dtype == jnp.float32


def test_cell_runnable_rules():
    assert configs.cell_runnable(configs.get("internlm2_20b"),
                                 configs.SHAPES["long_500k"])[0] is False
    for a in ("mixtral_8x22b", "rwkv6_1_6b", "jamba_1_5_large_398b"):
        assert configs.cell_runnable(configs.get(a),
                                     configs.SHAPES["long_500k"])[0] is True
    assert configs.cell_runnable(configs.get("internlm2_20b"),
                                 configs.SHAPES["train_4k"])[0] is True


def test_compile_cache_keeps_the_environment_directory(monkeypatch):
    import jax

    from repro.launch.compile_cache import ENV, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV, "/elsewhere/cache")
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads ENV itself


def test_compile_cache_defaults_to_one_fixed_checkout_path(monkeypatch):
    import os

    import jax

    from repro.launch.compile_cache import DEFAULT_DIR, ENV, enable_compile_cache

    checkout = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    monkeypatch.delenv(ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == DEFAULT_DIR == enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert DEFAULT_DIR == os.path.join(checkout, ".jax_cache")


def test_train_then_serve_from_the_checkpoint_commit(tmp_path, monkeypatch):
    """The launchers in-process: train writes a checkpoint commit, serve
    restores that commit's parameters and generates from them."""
    from repro.launch import serve, train
    from repro.launch.compile_cache import ENV

    monkeypatch.setenv(ENV, str(tmp_path / "cache"))  # leave JAX's cache as is
    repo = str(tmp_path / "run")
    res = train.main(["--arch", "qwen3_0_6b", "--steps", "2", "--ckpt-every", "2",
                      "--repo", repo, "--seq-len", "16", "--batch", "2"])
    assert res.end_step == 2 and len(res.losses) == 2
    out = serve.main(["--arch", "qwen3_0_6b", "--repo", repo, "--batch", "2",
                      "--prompt-len", "8", "--gen", "3"])
    assert out.commit == res.checkpoint_commit
    assert out.tokens.shape == (2, 3)
    assert out.first_decode_logits.shape == (2, configs.get_smoke("qwen3_0_6b").padded_vocab)


SMOKE_REHEARSAL = r"""
import os, shutil, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import chip_smoke
from repro.models import transformer as T

# take the kernels (in interpret mode) wherever the chip would take them
T._use_pallas = lambda cfg: cfg.use_pallas != "off"
work = tempfile.mkdtemp()
try:
    if sys.argv[2] == "one_chip":
        chip_smoke.one_chip(
            work, full=False, candidates=((2, 128),), expect_kernels=False,
            kernel_shapes={"flash": dict(B=2, H=4, KV=2, S=128, Dh=64),
                           "flash_prefill": dict(B=2, H=4, KV=2, S=384, Dh=64),
                           "rwkv6": dict(B=1, H=2, S=64, Dh=32),
                           "mamba": dict(B=1, S=128, Di=256, St=16)})
    else:
        chip_smoke.four_chips(work, full=False, batch=4, seq_len=64,
                              expect_kernels=False)
finally:
    shutil.rmtree(work, ignore_errors=True)
print("REHEARSAL OK")
"""


@pytest.mark.parametrize("phase", ["one_chip", "four_chips"])
def test_chip_smoke_phases_at_smoke_size(phase, tmp_path):
    """chip_smoke.py's phases end to end on the CPU at smoke widths, the
    kernels in interpret mode and four virtual devices for the mesh."""
    import os
    import subprocess
    import sys

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", SMOKE_REHEARSAL, root, phase],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.rstrip().endswith("REHEARSAL OK")
