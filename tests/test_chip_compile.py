"""The Pallas kernels compile for a TPU v5e at real widths, and the served
models' decode steps update their donated caches in place.

Compiled against a described ``v5e:2x2`` topology: the TPU compiler runs
here, with no chip attached, and refuses what the chip would refuse (tile
alignment, primitives Mosaic cannot lower, kernels it cannot partition).
Nothing runs, so these tests say nothing about results; the interpret-mode
tests in ``test_kernels.py`` decide correctness.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed.sharding import make_rules
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.models.params import abstract_params
from repro.train.steps import make_decode_step

# real widths: qwen3 attention, rwkv6_1_6b heads, jamba_1_5_large_398b scan,
# granite_4_0_h_micro's SSD scan at its longest served prompt;
# flash also at granite-3-2b's longest served prefill (S = 31 * 128, tiles
# 992 wide) and at the qwen3 training step's length
FLASH = {
    "flash": dict(B=2, H=16, KV=8, S=2048, Dh=128),
    "flash_granite_prefill": dict(B=16, H=32, KV=8, S=3968, Dh=64),
    "flash_qwen3_train": dict(B=4, H=16, KV=8, S=4096, Dh=128),
}
KERNELS = [*FLASH, "rwkv6", "mamba", "ssd"]
RWKV6 = dict(B=2, H=32, S=512, Dh=64)
MAMBA = dict(B=1, S=512, Di=16384, St=16)
SSD = dict(B=2, S=2048, H=64, P=64, G=1, N=128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_args(name: str, sds):
    """(kernel call, argument stand-ins) for one kernel; ``sds(shape, dtype,
    spec)`` builds a stand-in, ``spec`` naming the sharded dimensions."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name in FLASH:
        s = FLASH[name]
        heads = P(None, None, "model", None)
        args = (sds((s["B"], s["S"], s["H"], s["Dh"]), bf16, heads),
                sds((s["B"], s["S"], s["KV"], s["Dh"]), bf16, heads),
                sds((s["B"], s["S"], s["KV"], s["Dh"]), bf16, heads))
        return lambda rules: lambda *a: ops.flash_attention(
            *a, True, None, interpret=False, rules=rules), args
    if name == "rwkv6":
        s = RWKV6
        seq = (s["B"], s["S"], s["H"], s["Dh"])
        heads = P(None, None, "model", None)
        args = tuple(sds(seq, bf16, heads) for _ in range(4)) + (
            sds((s["H"], s["Dh"]), f32, P("model", None)),
            sds((s["B"], s["H"], s["Dh"], s["Dh"]), f32, P(None, "model")))
        return lambda rules: lambda *a: ops.rwkv6(
            *a, interpret=False, rules=rules), args
    if name == "ssd":
        s = SSD
        heads = P(None, None, "model", None)
        args = (sds((s["B"], s["S"], s["H"], s["P"]), bf16, heads),
                sds((s["B"], s["S"], s["H"]), f32, P(None, None, "model")),
                sds((s["H"],), f32, P("model")),
                sds((s["B"], s["S"], s["G"], s["N"]), bf16, P()),
                sds((s["B"], s["S"], s["G"], s["N"]), bf16, P()),
                sds((s["B"], s["H"], s["P"], s["N"]), f32, P(None, "model")))
        return lambda rules: lambda *a: ops.ssd(
            *a, interpret=False, rules=rules), args
    s = MAMBA
    seq = (s["B"], s["S"], s["Di"])
    chan = P(None, None, "model")
    args = (sds(seq, bf16, chan), sds(seq, bf16, chan),
            sds((s["Di"], s["St"]), f32, P("model", None)),
            sds((s["B"], s["S"], s["St"]), bf16, P()),
            sds((s["B"], s["S"], s["St"]), bf16, P()),
            sds((s["B"], s["Di"], s["St"]), f32, P(None, "model")))
    return lambda rules: lambda *a: ops.mamba_scan(
        *a, interpret=False, rules=rules), args


def _assert_kernel_in(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_one_chip(topo, name):
    one = SingleDeviceSharding(topo.devices[0])
    make, args = _kernel_args(
        name, lambda shape, dtype, spec: jax.ShapeDtypeStruct(shape, dtype, sharding=one))
    _assert_kernel_in(jax.jit(make(None)).lower(*args).compile())


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_under_shard_map_on_2x2(topo, name):
    """Mosaic kernels cannot be partitioned by the compiler; with sharding
    rules the wrappers run them per shard under shard_map."""
    mesh = make_host_mesh(4, devices=topo.devices)
    make, args = _kernel_args(
        name, lambda shape, dtype, spec: jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)))
    _assert_kernel_in(jax.jit(make(make_rules(mesh))).lower(*args).compile())


# the served models' decode steps at their cells' batch and longest cache
DECODE = {
    "granite_3_2b": dict(B=16, L=4096),
    "granite_4_0_h_micro": dict(B=32, L=2560),
}


@pytest.mark.parametrize("arch", DECODE)
def test_decode_step_updates_its_donated_cache_in_place(topo, arch):
    """With the caches donated, the compiled step asks temporaries under a
    tenth of the caches' bytes and copies no whole K or V stack: each layer's
    new row goes into the stack where it lies."""
    one = SingleDeviceSharding(topo.devices[0])
    cfg, s = configs.get(arch), DECODE[arch]

    def on_chip(tree):
        return jax.tree.map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one), tree)

    params = on_chip(abstract_params(T.param_defs(cfg), jnp.bfloat16))
    caches = on_chip(T.abstract_cache(cfg, None, s["B"], s["L"]))
    token = jax.ShapeDtypeStruct((s["B"], 1), jnp.int32, sharding=one)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    compiled = jax.jit(make_decode_step(cfg, None), donate_argnums=(1,)).lower(
        params, caches, token, pos).compile()

    cache_bytes = sum(t.size * t.dtype.itemsize for t in jax.tree.leaves(caches))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 10, (temp, cache_bytes)
    stacks = {tuple(layer[name].shape) for layer in caches.values()
              for name in ("k", "v") if name in layer}
    assert stacks
    copied = set()
    for line in compiled.as_text().splitlines():
        op = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) copy(?:-start)?\(", line)
        if op:
            copied |= {tuple(int(d) for d in dims.split(",") if d)
                       for dims in re.findall(r"\[([\d,]*)\]", op.group(1))}
    assert not stacks & copied, stacks & copied
