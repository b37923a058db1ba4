"""Production mesh construction.

Importing this module never touches jax device state; every helper is a
function so the dry-run can set XLA_FLAGS before any jax initialization
(see dryrun.py, which must set --xla_force_host_platform_device_count=512
in its very first lines).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis in Auto mode.  The sharding rules of
    distributed/sharding.py place arrays with NamedShardings and let the
    SPMD partitioner propagate from there; the installed JAX's default
    Explicit axes would instead type each array's sharding and refuse the
    batch gathers and slot scatters the engines trace."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over the actually-present devices (tests / smoke runs)."""
    return make_mesh((data, model), ("data", "model"))


def parse_mesh_spec(spec: str) -> dict:
    """Parse a ``--mesh`` flag value into {"data": int, "model": int}.

    Accepted forms (axis names are the serving mesh's ``data``/``model``):

        "data=2"            2-way data parallel, model replicated
        "data=2,model=4"    explicit both axes
        "auto"              all present devices on the data axis
        "2"  / "2x4"        positional shorthand for data(/model)
    """
    spec = spec.strip().lower()
    if spec == "auto":
        return {"data": jax.device_count(), "model": 1}
    out = {"data": 1, "model": 1}
    if "=" in spec:
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            key, _, val = part.partition("=")
            key = key.strip()
            if key not in out:
                raise ValueError(f"unknown mesh axis {key!r} in {spec!r} "
                                 "(serving meshes have axes data, model)")
            try:
                out[key] = int(val)
            except ValueError:
                raise ValueError(
                    f"bad size {val!r} for mesh axis {key} in {spec!r}") \
                    from None
    else:
        sizes = spec.replace("x", ",").split(",")
        try:
            out["data"] = int(sizes[0])
            if len(sizes) > 1:
                out["model"] = int(sizes[1])
            if len(sizes) > 2:
                raise ValueError
        except ValueError:
            raise ValueError(f"bad --mesh spec {spec!r}; try 'data=2', "
                             "'data=2,model=1', '2x1' or 'auto'") from None
    if out["data"] < 1 or out["model"] < 1:
        raise ValueError(f"mesh axis sizes must be >= 1, got {out}")
    return out


def make_serve_mesh(spec):
    """(data, model) mesh for the serving engines from a ``--mesh`` flag
    value; None (or empty) means single-device (no mesh)."""
    if spec is None or (isinstance(spec, str) and not spec.strip()):
        return None
    axes = parse_mesh_spec(spec)
    need = axes["data"] * axes["model"]
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"--mesh {spec!r} needs {need} devices, {have} present "
            "(hint: XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "forces N virtual host devices)")
    return make_local_mesh(data=axes["data"], model=axes["model"])
