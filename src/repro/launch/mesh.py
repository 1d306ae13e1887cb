"""Production mesh builders + simulated host-device plumbing.

These are FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — callers that want simulated devices set
the XLA flag (``force_host_device_count`` below) before first jax use;
smoke tests and benchmarks must keep seeing the real device count.

Simulated host devices: jax locks the device count at first backend init,
so ``force_host_device_count()`` must run before any jax device use —
the dry-run and hillclimb drivers call it as their first statement. The
count comes from the ``REPRO_HOST_DEVICES`` env var (default 512, the
production multi-pod dry-run size), so tests and CI can request small
meshes cheaply: ``REPRO_HOST_DEVICES=8 python -m repro.launch.dryrun …``
or ``XLA_FLAGS=--xla_force_host_platform_device_count=8 pytest …``.
"""
from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

DEFAULT_HOST_DEVICES = 512   # 2x16x16 multi-pod dry-run


def forced_host_device_count() -> int:
    """How many host devices to simulate: ``REPRO_HOST_DEVICES`` env
    override, else the production default of 512."""
    return int(os.environ.get("REPRO_HOST_DEVICES", DEFAULT_HOST_DEVICES))


def host_device_flags(n: int | None = None) -> str:
    """The XLA flag requesting ``n`` simulated host devices (``n=None``
    honours ``REPRO_HOST_DEVICES``) — for building a subprocess env."""
    n = forced_host_device_count() if n is None else n
    return f"--xla_force_host_platform_device_count={n}"


def force_host_device_count(n: int | None = None) -> int:
    """Append the forced-device flag to this process's ``XLA_FLAGS``.
    MUST run before the first jax backend use (importing jax is fine —
    the count locks at first device query, not at import)."""
    n = forced_host_device_count() if n is None else n
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (flags + " " + host_device_flags(n)).strip()
    return n


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``. jax >= 0.7
    defaults to Explicit axes, under which the GSPMD placement hints and
    the shard_map programs of this repo are refused; every mesh the
    program builds goes through here."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (data, model) or 2x16x16 multi-pod (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """A 1x1 mesh over whatever devices actually exist — for smoke runs.
    (Under ``force_host_device_count``/``REPRO_HOST_DEVICES`` that is the
    simulated count, not the physical one.)"""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))


def make_member_mesh(num_pods: int | None = None, *,
                     hosts: int | None = None, pods: int | None = None):
    """The member mesh of the stacked Map-phase executor
    (``runner.MapConfig(backend="stacked"|"mesh")``): one pod per
    distributed-averaging member group; ``make_member_mesh(1)`` is the
    first device, the ``"stacked"`` default.

    Default is the flat 1-D ``('pod',)`` mesh over the first ``num_pods``
    devices (all of them when ``None``) — every Reduce/sync is ONE global
    all-reduce. Passing ``hosts=`` builds the 2-D ``('host', 'pod')``
    topology instead: ``hosts`` machines of ``pods`` local pods each
    (``pods`` defaults to ``devices // hosts``), under which the executor
    stages each Reduce/sync as an intra-host psum then an inter-host psum
    — exactly TWO collectives regardless of fleet size."""
    if hosts is not None:
        if pods is None:
            n = len(jax.devices())
            if n % hosts:
                raise ValueError(
                    f"make_member_mesh: {n} devices do not split over "
                    f"hosts={hosts}; pass pods= explicitly")
            pods = n // hosts
        return auto_mesh((hosts, pods), ("host", "pod"))
    if pods is not None:
        raise ValueError("make_member_mesh: pods= requires hosts= "
                         "(use num_pods for the flat 1-D mesh)")
    n = len(jax.devices()) if num_pods is None else num_pods
    if n == 1:
        # the first device as it is: no topology to lay a ring over
        return Mesh(np.asarray(jax.devices()[:1]), ("pod",),
                    axis_types=(AxisType.Auto,))
    return auto_mesh((n,), ("pod",))


def axis_size(mesh, name: str) -> int:
    return mesh.shape.get(name, 1)
