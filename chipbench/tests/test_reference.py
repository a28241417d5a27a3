"""Each configuration's float32 reference agrees with
``models/transformer.py`` at smoke widths on the CPU: training logits, loss
and gradients, and prefill then decode through the cache; and the weights
the benchmark makes from the seed have the program's tree, shapes and dtype.
Every configuration of ``BENCHMARK.json`` is a case, run through the
reference its file names (``reference``) against the program's
architecture it names (``arch``), at the widths of its smoke file."""
import importlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import transformer as T
from repro.models.params import init_params
from repro.train.steps import make_decode_step, make_prefill_step, masked_loss

from chipbench.tests import tiny

CONFIGS = {c["name"]: c["file"] for c in tiny.load(os.path.join(tiny.ROOT, "BENCHMARK.json"))["configs"]}


def _pair(name):
    """(the program's config, the reference module, its model), at smoke
    widths."""
    c = tiny.load(os.path.join(tiny.ROOT, CONFIGS[name]))
    c.update(tiny.smoke("configs", name))
    R = importlib.import_module(f"chipbench.references.{c['reference']}")
    cfg = configs.get(c["arch"]).replace(**c["program_overrides"])
    hf = dict(c, rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps, qk_norm=cfg.qk_norm)
    return cfg, R, R.Model.from_config(hf)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_training_init_is_the_programs(name):
    """A training job starts from ``R.weights`` in the place of the
    program's ``init_params``: the same tree, shapes and dtype, the norms
    ones, each other leaf at the program's scale, and another seed another
    start."""
    cfg, R, m = _pair(name)
    got = R.flatten(jax.device_get(R.weights(m, seed=2**31 + 11)))
    want = R.flatten(jax.device_get(init_params(T.param_defs(cfg), seed=2**31 + 11)))
    other = R.flatten(jax.device_get(R.weights(m, seed=2**31 + 12)))
    assert got.keys() == want.keys()
    for k in want:
        assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape, k
        g, w = np.asarray(got[k], np.float64), np.asarray(want[k], np.float64)
        if np.all(w == 1):
            assert np.all(g == 1), k
        else:
            assert abs(g.std() / w.std() - 1) < 0.1, k
            assert not np.array_equal(got[k], other[k]), k


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_logits_loss_and_gradients(name):
    cfg, R, m = _pair(name)
    cfg = cfg.replace(dtype="float32")
    params = _f32(init_params(T.param_defs(cfg), seed=3))
    tokens = jnp.asarray(R.synthetic_batch(5, 0, cfg.vocab_size, 2, 32))
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(lambda p: T.forward_train(cfg, None, p, {"tokens": tokens}))(params)
        want = jax.jit(lambda p: R.head_logits(m, p, R.final_hidden(m, p, tokens)))(params)
        np.testing.assert_allclose(np.asarray(got)[..., :m.vocab], np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

        def prog_loss(p):
            lg, _ = T.forward_train(cfg, None, p, {"tokens": tokens})
            return masked_loss(lg, tokens, cfg.vocab_size)

        lp, gp = jax.jit(jax.value_and_grad(prog_loss))(params)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p: R.loss(m, p, tokens, row_block=16)))(params)
    assert abs(float(lp) - float(lr)) < 1e-5
    gp, gr = R.flatten(gp), R.flatten(gr)
    for k in gr:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gr[k]),
                                   rtol=2e-3, atol=1e-6, err_msg=k)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_prefill_then_decode(name):
    cfg, R, m = _pair(name)
    cfg = cfg.replace(dtype="float32")
    params = _f32(init_params(T.param_defs(cfg), seed=4))
    prompt = R.synthetic_batch(6, 0, cfg.vocab_size, 2, 16)
    gen = 4
    with jax.default_matmul_precision("highest"):
        caches, logits = jax.jit(make_prefill_step(cfg, None, 16 + gen))(
            params, {"tokens": jnp.asarray(prompt)})
        step = jax.jit(make_decode_step(cfg, None))
        got, toks = [np.asarray(logits)], []
        for i in range(gen - 1):
            tok = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)[:, None]
            toks.append(np.asarray(tok))
            logits, caches = step(params, caches, tok, jnp.asarray(16 + i, jnp.int32))
            got.append(np.asarray(logits))
        seq = jnp.asarray(np.concatenate([prompt] + toks, axis=1))
        want = R.head_logits(m, params, R.final_hidden(m, params, seq))
    for i, g in enumerate(got):
        np.testing.assert_allclose(g[:, :m.vocab], np.asarray(want[:, 15 + i]),
                                   rtol=2e-4, atol=2e-4)


def test_train_steps_follow_the_program():
    """Three reference steps against three program steps, both from the
    same seeded start, the program in its own bf16: the gaps the cell
    compares stay small."""
    from repro.optim.adamw import AdamW
    from repro.train.loop import jit_train_step

    cfg, R, m = _pair("qwen3-0.6b")
    batches = [R.synthetic_batch(9, s, cfg.vocab_size, 2, 64) for s in range(3)]
    step, init_opt, _, _ = jit_train_step(cfg, None, AdamW(lr=1e-3))
    params = R.weights(m, seed=9)
    opt = init_opt(params)
    losses, m1 = [], None
    for b in batches:
        params, opt, met = step(params, opt, {"tokens": jnp.asarray(b)})
        losses.append(float(met["loss"]))
        m1 = m1 or R.flatten(jax.device_get(opt["m"]))
    ref = R.train_steps(m, 9, batches)
    assert max(abs(a - b) / b for a, b in zip(losses, ref["losses"])) < 1e-3
    for k, v in ref["m1"].items():
        a, b = np.linalg.norm(np.asarray(m1[k], np.float64)), np.linalg.norm(v)
        assert abs(a - b) <= 0.05 * b + 1e-12, k


def test_a_batch_taken_in_blocks_of_sequences_gives_the_same_loss_and_gradients():
    """``seqs`` only splits the sum: blocks of one or two sequences give the
    whole batch's loss and gradients to float32 rounding."""
    cfg, R, m = _pair("granite-3-2b")
    params = _f32(R.weights(m, seed=2**31 + 13))
    tokens = jnp.asarray(R.synthetic_batch(13, 0, cfg.vocab_size, 4, 32))
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p: R.loss_and_grads(m, p, tokens))(params)
        for seqs in (1, 2):
            value, grads = jax.jit(lambda p: R.loss_and_grads(m, p, tokens, seqs=seqs))(params)
            assert abs(float(value) - float(whole[0])) <= 1e-6 * abs(float(whole[0]))
            for k, g in R.flatten(grads).items():
                want = np.asarray(R.flatten(whole[1])[k])
                np.testing.assert_allclose(np.asarray(g), want, rtol=1e-4,
                                           atol=1e-4 * np.abs(want).max(), err_msg=k)
