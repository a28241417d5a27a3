"""Bring-up check on TPU: training -> versioned checkpoint commit -> serving,
through the launchers a user runs, at published widths.

    python chip_smoke.py            # one chip (the default)
    python chip_smoke.py --chips 4  # one four-chip host, sharded training only

One chip: the three Pallas kernels at real widths against the float32
references in ``kernels/ref.py``; ``qwen3_0_6b --full`` trained through
``repro.launch.train`` (4 steps uninterrupted, and 2 steps then a resume to
4, whose checkpoints must hold bit-identical leaves); then served from the
resumed run's checkpoint commit through ``repro.launch.serve``, with the
first decode step checked against ``forward_train``.

Four chips: ``granite_3_2b`` at full width on a ``(data=1, model=4)`` mesh.
One step at 4 layers is compared with the same step on one device; then 3
full-depth steps are checkpointed and restored under the mesh shardings.

A phase that fails raises, so the script exits non-zero and prints no
result; so does a run where JAX finds no TPU. Times printed on the way are
observations of this one run, not metrics. The last line of stdout is the
JSON result. Weights and data are random, from fixed seeds.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.repo import Repository  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

WORK = os.path.join(HERE, ".smoke_work")

# kernel widths: qwen3 attention, granite-3-2b's longest served prefill
# (S = 31 * 128, 992-wide tiles), rwkv6_1_6b heads, jamba_1_5_large_398b scan
KERNEL_SHAPES = {
    "flash": dict(B=2, H=16, KV=8, S=2048, Dh=128),
    "flash_prefill": dict(B=16, H=32, KV=8, S=3968, Dh=64),
    "rwkv6": dict(B=2, H=32, S=512, Dh=64),
    "mamba": dict(B=1, S=512, Di=16384, St=16),
}
# bf16 inputs and outputs carry 8 significant bits (relative step 2^-8):
# a kernel passes when max|kernel - ref| <= KERNEL_TOL * max(1, max|ref|)
KERNEL_TOL = 2e-2
# two bf16 programs of the same math (serving vs forward_train, sharded vs
# one device) agree when ||a - b|| / ||b|| <= REL_TOL
REL_TOL = 5e-2
# the loss, a mean of fp32 log-softmaxes over bf16 logits, agrees tighter
LOSS_TOL = 1e-2
# a train step may use this share of the device's memory
MEMORY_SHARE = 0.8


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def require_kernel(compiled, what: str) -> None:
    require("tpu_custom_call" in compiled.as_text(),
            f"{what}: no tpu_custom_call in the compiled program")


# ----------------------------------------------------------------- kernels
def check_kernels(shapes: dict, seed: int = 0) -> None:
    from repro.kernels import ops, ref
    from repro.models.ssm import MAX_DECAY

    rng = np.random.default_rng(seed)

    def bf16(shape, scale=1.0):
        return jnp.asarray(rng.normal(0, scale, shape), jnp.bfloat16)

    def f32(x):
        return jnp.asarray(x, jnp.float32)

    def compare(name, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        require(np.isfinite(got).all(), f"{name}: non-finite output")
        err = float(np.max(np.abs(got - want)))
        bound = KERNEL_TOL * max(1.0, float(np.max(np.abs(want))))
        log(f"kernel {name}: max abs error {err!r} (bound {bound!r})")
        require(err <= bound, f"{name}: max abs error {err} > {bound}")

    flash = jax.jit(lambda *a: ops.flash_attention(*a, True, None))
    # the float32 reference one sequence at a time: its scores for a whole
    # batch of 3968-token prompts would not fit the chip
    flash_ref = jax.jit(lambda *a: ref.attention_ref(*map(f32, a), True, None))
    for name in [n for n in shapes if n.startswith("flash")]:
        s = shapes[name]
        q = bf16((s["B"], s["S"], s["H"], s["Dh"]))
        k, v = (bf16((s["B"], s["S"], s["KV"], s["Dh"])) for _ in range(2))
        got = flash(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = jnp.concatenate([flash_ref(q[i:i + 1], k[i:i + 1], v[i:i + 1])
                                    for i in range(s["B"])])
        compare(name, got, want)

    s = shapes["rwkv6"]
    shp = (s["B"], s["S"], s["H"], s["Dh"])
    r, k, v = bf16(shp), bf16(shp), bf16(shp)
    logw = jnp.maximum(-jnp.abs(f32(bf16(shp))) - 0.05, -MAX_DECAY).astype(jnp.bfloat16)
    u = f32(bf16((s["H"], s["Dh"]), 0.5))
    s0 = f32(bf16((s["B"], s["H"], s["Dh"], s["Dh"]), 0.3))
    got = jax.jit(ops.rwkv6)(r, k, v, logw, u, s0)
    with jax.default_matmul_precision("highest"):
        want = ref.rwkv6_ref(f32(r), f32(k), f32(v), f32(logw), u, s0)
    compare("rwkv6 out", got[0], want[0])
    compare("rwkv6 state", got[1], want[1])

    s = shapes["mamba"]
    seq = (s["B"], s["S"], s["Di"])
    u_ = bf16(seq)
    dt = jnp.abs(bf16(seq, 0.1))
    A = -jnp.abs(f32(bf16((s["Di"], s["St"]))))
    B_, C_ = bf16((s["B"], s["S"], s["St"])), bf16((s["B"], s["S"], s["St"]))
    h0 = f32(bf16((s["B"], s["Di"], s["St"]), 0.3))
    got = jax.jit(ops.mamba_scan)(u_, dt, A, B_, C_, h0)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba_ref(f32(u_), f32(dt), A, f32(B_), f32(C_), h0)
    compare("mamba y", got[0], want[0])
    compare("mamba state", got[1], want[1])


# ------------------------------------------------------------ one chip
def pick_train_size(cfg, optimizer, candidates, budget: int | None,
                    expect_kernels: bool):
    """The first (batch, seq_len) whose compiled train step needs at most
    ``budget`` bytes by the compiler's memory analysis."""
    from repro.models import transformer as T
    from repro.models.params import abstract_params
    from repro.train.loop import jit_train_step

    params = abstract_params(T.param_defs(cfg), jnp.bfloat16)
    opt_state = jax.eval_shape(optimizer.init, params)
    step = jit_train_step(cfg, None, optimizer)[0]
    for b, s in candidates:
        tokens = jax.ShapeDtypeStruct((b, s), jnp.int32)
        t0 = time.perf_counter()
        compiled = step.lower(params, opt_state, {"tokens": tokens}).compile()
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        log(f"observation: train step B={b} S={s} compiled in "
            f"{time.perf_counter() - t0:.3f} s, needs {need} bytes "
            f"(budget {budget})")
        if budget is None or need <= budget:
            if expect_kernels:
                require_kernel(compiled, "train step")
            return b, s
    raise RuntimeError(f"no train size in {candidates} fits {budget} bytes")


def leaf_keys_of(repo_root: str, commit: str | None = None) -> dict:
    from repro.train.checkpoint import CheckpointManager

    _, manifest = CheckpointManager(Repository(repo_root)).manifest(commit)
    return {p: m["key"] for p, m in manifest["leaves"].items()}


def train_and_resume(arch: str, full: bool, candidates, budget,
                     expect_kernels: bool, work: str):
    """Returns (repository of the resumed run, its last checkpoint commit)."""
    from repro.launch import train as train_launcher

    cfg = configs.get(arch) if full else configs.get_smoke(arch)
    steps = 4
    b, s = pick_train_size(cfg, train_launcher.make_optimizer(cfg, 3e-4, steps),
                           candidates, budget, expect_kernels)
    log(f"train size: batch {b} x seq_len {s}")
    args = ["--arch", arch, "--batch", str(b), "--seq-len", str(s),
            "--lr", "3e-4"] + (["--full"] if full else [])

    # uninterrupted: 4 steps, one checkpoint
    repo_a = os.path.join(work, "uninterrupted")
    t0 = time.perf_counter()
    a = train_launcher.main(args + ["--repo", repo_a, "--steps", str(steps),
                                    "--ckpt-every", str(steps)])
    log(f"observation: 4 steps + 1 checkpoint in {time.perf_counter() - t0:.3f} s")
    keys_a = leaf_keys_of(repo_a, a.checkpoint_commit)
    shutil.rmtree(repo_a)  # only its digests are needed from here on

    # interrupted: 2 steps and a checkpoint, then a resume to 4 (the 10-step
    # warmup makes the learning rate independent of --steps this early)
    repo_b = os.path.join(work, "resumed")
    t0 = time.perf_counter()
    b1 = train_launcher.main(args + ["--repo", repo_b, "--steps", "2",
                                     "--ckpt-every", "2"])
    b2 = train_launcher.main(args + ["--repo", repo_b, "--steps", str(steps),
                                     "--ckpt-every", "2"])
    log(f"observation: 2 steps + checkpoint, restore, 2 steps + checkpoint "
        f"in {time.perf_counter() - t0:.3f} s")
    log(f"observation: checkpoint save {a.save_s:.3f} s (4-step run), "
        f"{b1.save_s:.3f} s + {b2.save_s:.3f} s (2 + 2 steps); "
        f"restore for the resume {b2.restore_s:.3f} s")

    first = a.losses[0]
    log(f"step-1 loss {first!r}, ln(vocab) {math.log(cfg.vocab_size)!r}")
    require(all(np.isfinite(a.losses)), f"non-finite loss {a.losses}")
    require(abs(first - math.log(cfg.vocab_size)) < 1.0,
            f"step-1 loss {first} is not near ln({cfg.vocab_size})")
    require(b2.start_step == 2, f"resume started at step {b2.start_step}")
    require(a.losses == b1.losses + b2.losses,
            f"losses differ: {a.losses} vs {b1.losses + b2.losses}")
    keys_b = leaf_keys_of(repo_b, b2.checkpoint_commit)
    differ = sorted(p for p in keys_a if keys_a[p] != keys_b.get(p))
    require(keys_a.keys() == keys_b.keys() and not differ,
            f"resumed checkpoint differs from the uninterrupted one in {differ}")
    log(f"resumed checkpoint == uninterrupted checkpoint: {len(keys_a)} leaves "
        "bit-identical by digest")
    return repo_b, b2.checkpoint_commit


def serve_and_check(arch: str, full: bool, repo: str, commit: str,
                    expect_kernels: bool) -> None:
    from repro.launch import serve as serve_launcher
    from repro.models import transformer as T
    from repro.train.checkpoint import CheckpointManager, leaf_keys

    cfg = configs.get(arch) if full else configs.get_smoke(arch)
    res = serve_launcher.main(["--arch", arch, "--repo", repo, "--batch", "8",
                               "--prompt-len", "128", "--gen", "16"]
                              + (["--full"] if full else []))
    require(res.commit == commit,
            f"served commit {res.commit} is not the checkpoint {commit}")
    if expect_kernels:
        require_kernel(res.prefill, "prefill")
    require(res.tokens.shape == (8, 16) and int(res.tokens.max()) < cfg.vocab_size,
            f"bad generated tokens {res.tokens.shape}")

    state, manifest = CheckpointManager(Repository(repo)).restore(
        commit, subtree="params")
    want = {p: manifest["leaves"][p]["key"] for p in manifest["leaves"]
            if p.startswith("params/")}
    require(leaf_keys(state) == want, "restored params differ from the checkpoint")
    seq = np.concatenate([res.prompt, res.tokens[:, :1]], axis=1)
    logits, _ = jax.jit(lambda p, b: T.forward_train(cfg, None, p, b))(
        state["params"], {"tokens": jnp.asarray(seq)})
    pos = res.prompt.shape[1]
    ref_logits = np.asarray(logits[:, pos, : cfg.vocab_size], np.float32)
    got = res.first_decode_logits[:, : cfg.vocab_size]
    err = rel_err(got, ref_logits)
    log(f"first decode logits vs forward_train: relative error {err!r}, "
        f"max abs {float(np.max(np.abs(got - ref_logits)))!r}")
    require(np.isfinite(got).all(), "non-finite decode logits")
    require(err <= REL_TOL, f"decode logits relative error {err} > {REL_TOL}")


def one_chip(work: str, full: bool = True, kernel_shapes=KERNEL_SHAPES,
             candidates=((2, 2048), (1, 2048), (1, 1024)), budget=None,
             expect_kernels: bool = True) -> None:
    t0 = time.perf_counter()
    check_kernels(kernel_shapes)
    log(f"observation: kernels phase {time.perf_counter() - t0:.3f} s")
    repo, commit = train_and_resume("qwen3_0_6b", full, candidates, budget,
                                    expect_kernels, work)
    t0 = time.perf_counter()
    serve_and_check("qwen3_0_6b", full, repo, commit, expect_kernels)
    log(f"observation: serve phase {time.perf_counter() - t0:.3f} s")


# ----------------------------------------------------------- four chips
def four_chips(work: str, full: bool = True, batch: int = 4,
               seq_len: int = 2048, expect_kernels: bool = True) -> None:
    from repro.data.tokens import SyntheticTokens
    from repro.distributed.sharding import make_rules
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.optim.adamw import AdamW
    from repro.train.checkpoint import CheckpointManager, leaf_keys
    from repro.train.loop import jit_train_step, state_shardings, train_segment

    arch = "granite_3_2b"
    cfg = configs.get(arch) if full else configs.get_smoke(arch)
    mesh = make_host_mesh(4)
    rules = make_rules(mesh)
    opt = AdamW(lr=1e-3, moment_dtype=cfg.opt_moment_dtype)
    ds = SyntheticTokens(cfg.vocab_size, seq_len, batch, seed=0)

    # 1) one step at full width, 4 layers: mesh with rules vs one device
    short = cfg.replace(n_layers=min(4, cfg.n_layers))
    batch0 = {"tokens": ds.shard_batch_at(0, 0, 1)}
    step_m, init_m, _, batch_sharding = jit_train_step(short, rules, opt)
    pm = init_params(T.param_defs(short, rules), seed=0, mesh=mesh)
    om = init_m(pm)
    batch_m = {"tokens": jax.device_put(batch0["tokens"], batch_sharding)}
    compiled = step_m.lower(pm, om, batch_m).compile()
    if expect_kernels:
        require_kernel(compiled, "sharded train step")
    pm, om, mm = compiled(pm, om, batch_m)
    step_1, init_1, _, _ = jit_train_step(short, None, opt)
    p1 = init_params(T.param_defs(short), seed=0)
    p1, o1, m1 = step_1(p1, init_1(p1), batch0)
    loss_m, loss_1 = float(mm["loss"]), float(m1["loss"])
    log(f"4-layer step: loss on mesh {loss_m!r}, on one device {loss_1!r}")
    require(abs(loss_m - loss_1) <= LOSS_TOL * abs(loss_1),
            f"sharded loss {loss_m} vs single-device {loss_1}")
    # AdamW's first moment after one step is 0.1 * the gradient, in fp32
    for path in (("embed",), ("final_norm",), ("blocks", "p0", "attn", "wq"),
                 ("blocks", "p0", "ffn", "w2")):
        a, b = om["m"], o1["m"]
        for key in path:
            a, b = a[key], b[key]
        err = rel_err(jax.device_get(a), jax.device_get(b))
        log(f"4-layer step: first moment {'/'.join(path)} relative error {err!r}")
        require(err <= REL_TOL, f"{'/'.join(path)}: relative error {err}")
    del pm, om, p1, o1

    # 2) 3 full-depth steps on the mesh, checkpointed; restored under the
    #    mesh shardings, bit-identical
    repo = Repository.init(os.path.join(work, "sharded"))
    t0 = time.perf_counter()
    res = train_segment(repo, cfg, ds, n_steps=3, ckpt_every=3, optimizer=opt,
                        rules=rules)
    log(f"observation: {cfg.n_layers}-layer segment (3 steps + checkpoint) in "
        f"{time.perf_counter() - t0:.3f} s; losses {res.losses}")
    require(all(np.isfinite(res.losses)), f"non-finite loss {res.losses}")
    want = state_shardings(T.param_defs(cfg, rules), mesh)
    t0 = time.perf_counter()
    state, manifest = CheckpointManager(repo).restore(res.checkpoint_commit,
                                                      shardings=want)
    log(f"observation: restore under shardings in {time.perf_counter() - t0:.3f} s")
    misplaced = [
        jax.tree_util.keystr(path)
        for (path, leaf), s in zip(jax.tree_util.tree_flatten_with_path(state)[0],
                                   jax.tree.leaves(want))
        if leaf.sharding != s
    ]
    require(not misplaced, f"leaves not under the requested shardings: {misplaced}")
    keys = leaf_keys(state)
    differ = sorted(p for p, m in manifest["leaves"].items() if keys[p] != m["key"])
    require(not differ, f"restored leaves differ from the checkpoint: {differ}")
    log(f"restored {len(keys)} leaves bit-identical, under the mesh shardings")
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in jax.devices()
             if d.memory_stats()]
    log(f"observation: peak bytes in use per device {peaks}")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"device: {device}")
    log(f"observation: set-up (backend start) {time.perf_counter() - t0:.3f} s")
    if device["platform"] != "tpu" or jax.default_backend() != "tpu":
        raise SystemExit(f"no TPU: JAX found {device}")
    if device["count"] < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices")
    cache = enable_compile_cache()
    log(f"compile cache: {cache}")
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    log(f"work dir {WORK}: {shutil.disk_usage(WORK).free} bytes free")
    try:
        if args.chips == 4:
            four_chips(WORK)
        else:
            limit = devices[0].memory_stats()["bytes_limit"]
            one_chip(WORK, budget=int(MEMORY_SHARE * limit))
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
