"""Production mesh + logical-axis resolution.

Logical spec axes used throughout the model code:
  * ``"dp"`` — data/FSDP; resolves to ``("pod", "data")`` when a pod axis
    exists, else ``("data",)``.
  * ``"tp"`` — tensor parallel; resolves to ``"model"``.
  * ``"far"`` — the sharded far tier (repro.core.shardplane): a dedicated
    1-D mesh axis over which the hybrid data plane's slab partitions, frame
    pools and profiling state are sharded (``far_specs`` builds the
    PartitionSpec trees for the stacked ``PlaneState``/``KVPlaneState``).

Nothing in this module touches jax device state at import time.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Mesh shaped from the VISIBLE device count (the seed hardcoded
    ``(16, 16)`` / ``(2, 16, 16)`` and failed on anything else).

    The model axis gets the largest power-of-two factor of the device count
    up to 16 (the production TP width); data parallelism takes the rest.
    With ``multi_pod`` a leading pod axis of 2 is split off first when the
    count allows it.  On 256 / 512 devices this reproduces the original
    shapes exactly."""
    n = jax.device_count()
    if multi_pod:
        pods = 2 if n % 2 == 0 and n >= 2 else 1
        per_pod = n // pods
        model = math.gcd(per_pod, 16)
        return jax.make_mesh((pods, per_pod // model, model),
                             ("pod", "data", "model"))
    model = math.gcd(n, 16)
    return jax.make_mesh((n // model, model), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over the locally visible devices (tests / examples)."""
    n = jax.device_count()
    if data * model > n:
        raise ValueError(
            f"make_host_mesh(data={data}, model={model}) needs "
            f"{data * model} devices but only {n} are visible; lower the "
            "mesh size or simulate devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return jax.make_mesh((data, model), ("data", "model"))


def make_far_mesh(shards: int) -> Mesh:
    """1-D mesh over the ``far`` axis for the sharded data plane.  Uses the
    first ``shards`` visible devices (a plane may occupy a submesh)."""
    n = jax.device_count()
    if shards > n:
        raise ValueError(
            f"make_far_mesh(shards={shards}) needs {shards} devices but "
            f"only {n} are visible; lower the shard count or simulate "
            "devices with XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return Mesh(np.asarray(jax.devices()[:shards]), ("far",))


def far_specs(tree):
    """PartitionSpec tree sharding every leaf's leading axis over ``far`` —
    the layout of a stacked ``[shards, ...]`` plane state pytree."""
    return jax.tree.map(lambda _: P("far"), tree)


def put_far(tree, mesh: Mesh):
    """Lay a stacked ``[shards, ...]`` plane pytree out on a ``far`` mesh
    (one shard slice per device) — the device_put every sharded caller
    (engine, tests, benchmarks) used to hand-roll."""
    return jax.device_put(tree, jax.tree.map(
        lambda _: NamedSharding(mesh, P("far")), tree))


# Logical-axis layout: "2d" (default) = FSDP over (pod, data) x TP over
# model; "fsdp" = pure ZeRO-3 over every mesh axis, no tensor parallelism
# (dense-arch training at large global batch — §Perf iteration 3).
_LAYOUT = "2d"


def set_layout(name: str):
    global _LAYOUT
    assert name in ("2d", "fsdp"), name
    _LAYOUT = name


def get_layout() -> str:
    return _LAYOUT


def _axis(mesh: Mesh, logical):
    if logical is None:
        return None
    if logical == "batch":
        # data-parallel batch axis: never includes "model" (batch size may
        # be smaller than the full chip count under the fsdp layout)
        return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    if logical == "dp":
        axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
        if _LAYOUT == "fsdp":
            axes = axes + ("model",)
        return axes
    if logical == "tp":
        return None if _LAYOUT == "fsdp" else "model"
    return logical


def resolve(mesh: Mesh, spec) -> P:
    """Map a logical spec tuple to a concrete PartitionSpec for ``mesh``."""
    if spec is None:
        return P()
    out = []
    for ax in spec:
        r = _axis(mesh, ax)
        out.append(r)
    return P(*out)


def is_spec(s) -> bool:
    """A logical spec leaf: plain tuple of axis entries (str / None /
    tuple-of-str); NamedTuples (state containers) are NOT leaves."""
    if s is None:
        return True
    if not isinstance(s, tuple) or hasattr(s, "_fields"):
        return False
    return all(e is None or isinstance(e, str)
               or (isinstance(e, tuple)
                   and all(isinstance(x, str) for x in e))
               for e in s)


def resolve_tree(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: resolve(mesh, s), spec_tree, is_leaf=is_spec)


def sharding_tree(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        resolve_tree(mesh, spec_tree),
        is_leaf=lambda s: isinstance(s, P))


def constrain(x, spec):
    """Logical sharding constraint; no-op when tracing without a mesh.

    The constraint names the mesh's axes as ``Auto``: ``jax.make_mesh``
    builds ``Explicit`` axes, which a sharding constraint may not name."""
    try:
        mesh = jax._src.mesh.thread_resources.env.physical_mesh
    except Exception:
        return x
    if mesh is None or mesh.empty:
        return x
    mesh = mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, resolve(mesh, spec)))
