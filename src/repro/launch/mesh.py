"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run must set XLA_FLAGS before first jax init while smoke tests see 1
device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.config import MeshConfig


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    if multi_pod:
        return MeshConfig(shape=(2, 16, 16), axis_names=("pod", "data",
                                                         "model"))
    return MeshConfig(shape=(16, 16), axis_names=("data", "model"))


def make_mesh(cfg: MeshConfig):
    """Mesh for an arbitrary MeshConfig (tests use small CPU meshes)."""
    return jax.make_mesh(cfg.shape, cfg.axis_names,
                         axis_types=(AxisType.Auto,) * len(cfg.shape))


def parse_mesh_spec(spec: str) -> MeshConfig:
    """``--mesh`` string -> MeshConfig: comma-separated ``axis=N`` pairs,
    e.g. ``model=8`` or ``data=2,model=4`` (axis order is spec order).
    """
    shape, names = [], []
    for part in spec.split(","):
        name, _, n = part.partition("=")
        name, n = name.strip(), n.strip()
        if not name or not n.isdigit() or int(n) < 1:
            raise ValueError(
                f"bad --mesh entry {part!r}: expected axis=N with N >= 1 "
                f"(e.g. --mesh model=8)")
        names.append(name)
        shape.append(int(n))
    return MeshConfig(shape=tuple(shape), axis_names=tuple(names))


def refuse_on_tpu(tool: str) -> None:
    """Stop a CPU tool that re-execs children with forced host devices
    when this process runs on a TPU: the process then holds the chip, and
    a child that reaches for it fails or hangs on the chip lock.  Exits
    with a message instead (``JAX_PLATFORMS=cpu`` runs the tool on the
    host CPU)."""
    if jax.default_backend() == "tpu":
        raise SystemExit(
            f"{tool} is a CPU tool: it re-execs child processes with "
            f"forced host devices, and on a TPU this process holds the "
            f"chip they would need.  Run it with JAX_PLATFORMS=cpu.")


def make_serve_mesh(spec: str):
    """Serving mesh from a ``--mesh`` spec (``model=N`` shards the engine's
    KV-head axis N ways).  Total size must not exceed the visible devices —
    on CPU, set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before the first jax import to fake an N-device host."""
    cfg = parse_mesh_spec(spec)
    if "model" not in cfg.axis_names:
        raise ValueError(
            f"--mesh {spec} has no 'model' axis — serving shards the "
            f"KV-head dim over mesh['model'] (e.g. --mesh model=8)")
    need = 1
    for n in cfg.shape:
        need *= n
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"--mesh {spec} needs {need} devices but only {have} are "
            f"visible (on CPU, export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})")
    return make_mesh(cfg)
