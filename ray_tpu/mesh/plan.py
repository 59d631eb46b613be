"""Mesh construction + compile-with-plan: the single mesh code path.

This module is the ONE place meshes are built (``make_mesh`` — the
``train.session.make_mesh`` entry point is a thin alias onto it) and the
one place a user step function is compiled against a sharding plan
(SNIPPETS [2]/[3] exemplar shape):

- both ``in_shardings`` and ``out_shardings`` given -> pjit-style
  ``jax.jit`` with explicit shardings + ``donate_argnums``, run under
  the named mesh context;
- neither given -> ``shard_map`` fallback over explicit
  ``in_specs``/``out_specs`` (map-style collectives ergonomics, same
  mesh context);
- exactly one given -> :class:`PlanError` (an ambiguous half-plan).

Shardings/specs are accepted as pytrees of ``PartitionSpec`` (the wire
form a MeshGroup controller ships to its ranks — specs pickle, device
objects do not) and resolved to ``NamedSharding`` against the local
mesh at compile time.
"""

from __future__ import annotations

import os
import re
from typing import Any, Callable, Optional, Sequence, Tuple

from ray_tpu.exceptions import RayTpuError


class PlanError(RayTpuError):
    """A sharding plan that cannot compile (half-specified, wrong mesh
    axes, or a pjit/shard_map failure — the cause rides ``__cause__``)."""


_XLA_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=\d+")


def set_host_platform_device_count(n: int) -> bool:
    """Make this process see ``n`` virtual CPU devices.

    Must run BEFORE jax first initializes its backends: edits
    ``XLA_FLAGS`` (replacing any inherited count — test drivers export
    one) and, where jax is already imported, sets ``jax_num_cpu_devices``
    too. Returns False when the backends are already up and neither can
    apply.
    """
    import sys

    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={int(n)}"
    if _XLA_COUNT_RE.search(flags):
        flags = _XLA_COUNT_RE.sub(flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            jax.config.update("jax_num_cpu_devices", int(n))
        except RuntimeError:  # backends already initialized
            return False
    return True


def bootstrap_worker_platform(env: Optional[dict],
                              n_devices: Optional[int]) -> None:
    """The order-sensitive worker-side jax bootstrap, shared by every
    gang worker type (MeshGroup ``_MeshWorker``, train
    ``_TrainWorker``): apply platform env and the virtual-device count
    BEFORE this process first imports jax, then re-pin the platform
    (jax reads ``JAX_PLATFORMS`` once, at import: where unpickling the
    actor already imported it, a simulated run's ``cpu`` must be forced
    through the config)."""
    os.environ.update(env or {})
    if n_devices:
        set_host_platform_device_count(n_devices)
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")


def enable_cpu_cross_process_collectives() -> None:
    """Route CPU-backend cross-process collectives through gloo.

    The default XLA CPU client refuses multi-process computations
    ("Multiprocess computations aren't implemented on the CPU backend");
    with the gloo implementation a simulated multi-host gang runs real
    pjit programs over TCP.
    """
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def normalize_mesh_shape(
    mesh_shape, axis_names: Optional[Sequence[str]] = None
) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """Canonicalize a mesh shape to (axis_names, sizes).

    Accepts an ordered dict ``{"dp": 2, "tp": 4}``, a sequence of sizes
    plus explicit ``axis_names``, or a ``parallel.mesh.MeshConfig``
    (expanded over the canonical five axes, size-1 axes kept — the
    shapes stay mutually resharding-compatible).
    """
    from ray_tpu.parallel.mesh import MESH_AXES, MeshConfig

    if isinstance(mesh_shape, MeshConfig):
        sizes = (mesh_shape.dp, mesh_shape.pp, mesh_shape.ep,
                 mesh_shape.sp, mesh_shape.tp)
        return tuple(MESH_AXES), tuple(sizes)
    if isinstance(mesh_shape, dict):
        if axis_names is not None:
            missing = [a for a in axis_names if a not in mesh_shape]
            if missing:
                raise PlanError(
                    f"axis_names {list(axis_names)} not all present in "
                    f"mesh_shape {mesh_shape}"
                )
            return tuple(axis_names), tuple(
                int(mesh_shape[a]) for a in axis_names
            )
        return tuple(mesh_shape), tuple(int(v) for v in mesh_shape.values())
    sizes = tuple(int(v) for v in mesh_shape)
    if axis_names is None or len(axis_names) != len(sizes):
        raise PlanError(
            f"a plain size tuple {sizes} needs matching axis_names"
        )
    return tuple(axis_names), sizes


def make_mesh(mesh_shape=None, *, axis_names=None, devices=None):
    """Build a ``jax.sharding.Mesh`` — the one mesh-construction path.

    ``mesh_shape=None`` or a ``MeshConfig`` delegates to the canonical
    five-axis ``parallel.mesh.build_mesh`` (axes left at -1 absorb the
    device count). A dict / sizes+axis_names builds a mesh with exactly
    those named axes over ``devices`` (default: every device this
    process can see — after a gang rendezvous that is the GLOBAL device
    set, which is what makes the result a multi-host mesh).
    """
    import jax
    import numpy as np

    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    if mesh_shape is None or isinstance(mesh_shape, MeshConfig):
        return build_mesh(mesh_shape or MeshConfig(), devices=devices)
    names, sizes = normalize_mesh_shape(mesh_shape, axis_names)
    devices = list(devices if devices is not None else jax.devices())
    want = 1
    for s in sizes:
        want *= s
    if want != len(devices):
        raise PlanError(
            f"mesh {dict(zip(names, sizes))} needs {want} devices, "
            f"have {len(devices)}"
        )
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(sizes, devices=devices)
    except Exception:
        dev_array = np.array(devices).reshape(sizes)
    return jax.sharding.Mesh(dev_array, names)


def specs_to_shardings(mesh, tree):
    """Resolve a pytree of ``PartitionSpec`` leaves to ``NamedSharding``
    against ``mesh`` (already-resolved shardings pass through)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, Sharding

    def leaf(x):
        if isinstance(x, PartitionSpec):
            return NamedSharding(mesh, x)
        if isinstance(x, Sharding):
            return x
        raise PlanError(
            f"sharding plan leaf {x!r} is neither a PartitionSpec nor a "
            f"Sharding"
        )

    return jax.tree.map(
        leaf, tree, is_leaf=lambda x: isinstance(
            x, (PartitionSpec, Sharding)
        )
    )


def compile_step_with_plan(
    fn: Callable[..., Any],
    mesh,
    *,
    in_shardings=None,
    out_shardings=None,
    donate_argnums: Sequence[int] = (),
    static_argnums: Sequence[int] = (),
    in_specs=None,
    out_specs=None,
):
    """Compile ``fn`` against a sharding plan under ``mesh``.

    Returns a callable that always executes inside the mesh context.
    ``donate_argnums`` is dropped on the CPU backend: jaxlib's
    zero-copy host aliasing + donation corrupts the driver heap in a
    multi-threaded cluster process (root-caused in PR 2; TPU keeps the
    donation win).
    """
    import functools

    import jax

    one_sided = (in_shardings is None) != (out_shardings is None)
    if one_sided:
        raise PlanError(
            "compile_step_with_plan requires BOTH in_shardings and "
            "out_shardings for the pjit path — pass both, or neither "
            "plus in_specs/out_specs for the shard_map fallback"
        )
    if jax.default_backend() == "cpu":
        donate_argnums = ()

    if in_shardings is not None:
        try:
            compiled = jax.jit(
                fn,
                in_shardings=specs_to_shardings(mesh, in_shardings),
                out_shardings=specs_to_shardings(mesh, out_shardings),
                donate_argnums=tuple(donate_argnums),
                static_argnums=tuple(static_argnums),
            )
        except Exception as exc:
            raise PlanError(
                f"pjit compilation failed: {exc} — verify the sharding "
                f"specs name axes of the mesh {tuple(mesh.axis_names)}"
            ) from exc

        @functools.wraps(fn)
        def run_pjit(*args, **kwargs):
            with mesh:
                return compiled(*args, **kwargs)

        return run_pjit

    if in_specs is None or out_specs is None:
        raise PlanError(
            "no shardings given and no in_specs/out_specs for the "
            "shard_map fallback — the plan is empty"
        )
    try:
        mapped = jax.jit(
            jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs),
            static_argnums=tuple(static_argnums),
        )
    except Exception as exc:
        raise PlanError(
            f"shard_map compilation failed: {exc}"
        ) from exc

    @functools.wraps(fn)
    def run_shard_map(*args, **kwargs):
        with mesh:
            return mapped(*args, **kwargs)

    return run_shard_map
