"""Parameter definition trees: shapes + shardings + init, in one walk.

A model is declared as a nested dict of :class:`ParamDef`. From the same
tree we derive (a) materialized parameters for CPU smoke tests / real
training, (b) ``jax.ShapeDtypeStruct`` stand-ins with ``NamedSharding``
attached for the multi-pod dry-run (no allocation), and (c) the
``in_shardings`` pytree for ``jax.jit``.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    spec: P  # logical PartitionSpec (ignored when no mesh)
    init: str = "normal"  # normal | zeros | ones
    scale: float | None = None  # stddev; None -> 1/sqrt(fan_in)


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_paths(defs: dict, prefix: str = "") -> list[tuple[str, ParamDef]]:
    out = []
    for name in sorted(defs):
        node = defs[name]
        path = f"{prefix}/{name}"
        if _is_def(node):
            out.append((path, node))
        else:
            out.extend(tree_paths(node, path))
    return out


def _map_defs(defs, fn):
    if _is_def(defs):
        raise TypeError("expected a dict tree")
    return {
        name: fn(node) if _is_def(node) else _map_defs(node, fn)
        for name, node in defs.items()
    }


def _init_one(path: str, d: ParamDef, seed: int, dtype) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "mamba_a":
        # S4D-real init: A_log[d, n] = log(n + 1), broadcast over channels
        st = d.shape[-1]
        row = jnp.log(jnp.arange(1, st + 1, dtype=jnp.float32))
        return jnp.broadcast_to(row, d.shape).astype(dtype)
    # deterministic per-path key
    digest = hashlib.sha256(f"{seed}:{path}".encode()).digest()
    key = jax.random.PRNGKey(int.from_bytes(digest[:4], "big"))
    fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
    scale = d.scale if d.scale is not None else fan_in**-0.5
    return (jax.random.normal(key, d.shape, jnp.float32) * scale).astype(dtype)


def init_params(defs: dict, seed: int, dtype=jnp.bfloat16, mesh=None) -> dict:
    """Materialize parameters (smoke tests / real training). With a
    ``mesh``, each leaf is generated directly under its ``NamedSharding``,
    so a model that fits only when sharded never lands whole on one device;
    the values are the same as without one."""

    def one(path: str, d: ParamDef) -> jax.Array:
        # jitted with or without a mesh: one compiled program per leaf, so
        # both give the same bits (eager and fused arithmetic can differ)
        sharding = NamedSharding(mesh, d.spec) if mesh is not None else None
        return jax.jit(lambda: _init_one(path, d, seed, dtype),
                       out_shardings=sharding)()

    def walk(node, prefix):
        return {
            name: one(f"{prefix}/{name}", child)
            if _is_def(child)
            else walk(child, f"{prefix}/{name}")
            for name, child in node.items()
        }

    return walk(defs, "")


def abstract_params(defs: dict, dtype, mesh=None) -> dict:
    """ShapeDtypeStruct tree (with shardings when a mesh is given) — the
    dry-run path: weak-type-correct, shardable, no device allocation."""

    def one(d: ParamDef):
        if mesh is not None:
            return jax.ShapeDtypeStruct(
                d.shape, dtype, sharding=NamedSharding(mesh, d.spec)
            )
        return jax.ShapeDtypeStruct(d.shape, dtype)

    return _map_defs(defs, one)


def param_specs(defs: dict) -> dict:
    return _map_defs(defs, lambda d: d.spec)


def param_shardings(defs: dict, mesh) -> dict:
    return _map_defs(defs, lambda d: NamedSharding(mesh, d.spec))


def param_count(defs: dict) -> int:
    total = 0
    for _, d in tree_paths(defs):
        n = 1
        for s in d.shape:
            n *= s
        total += n
    return total
