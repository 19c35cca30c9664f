"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before the
first jax call, and smoke tests must keep seeing 1 device.

Axis semantics (DESIGN §6):
  "pod"   — crosses data-center network (DCN); only the DP gradient
            all-reduce runs here, once per step (optionally 8-bit
            compressed, optim/compress.py)
  "data"  — DP/FSDP within a pod (ICI)
  "model" — tensor/sequence/expert parallelism within a pod (ICI)
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2, expert: int = 1):
    """Small mesh over the local devices: CPU host devices for tests and
    examples (requires the host-device flag), or the chips of one host.

    ``expert`` > 1 appends an ``expert`` axis (EP dispatch —
    ``models/moe_ep.py``); dense archs treat it as one more data axis.
    """
    n = data * model * expert
    avail = len(jax.devices())
    if avail < n:
        hint = ("; set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n}" if jax.default_backend() == "cpu" else
                f"; this {jax.default_backend()} host has {avail} — pass "
                f"smaller --data-axis/--model-axis")
        raise ValueError(f"need {n} devices, have {avail}{hint}")
    auto = jax.sharding.AxisType.Auto
    if expert > 1:
        return jax.make_mesh((data, model, expert),
                             ("data", "model", "expert"),
                             axis_types=(auto, auto, auto))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(auto, auto))
