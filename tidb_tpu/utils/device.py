"""Device-placement policy for the execution runtime.

The TPU-first execution contract (ref: SURVEY.md §7 hard part 5 —
host<->device staging costs): all hot-loop compute runs inside a small
number of *compiled* programs on the accelerator, and everything outside
them (operator glue, finalize over a few groups, the final ORDER BY of
an aggregate's rows, result decode) runs on the host. An eager op on a
handful of values gains nothing from the accelerator and costs a launch
plus a transfer each way — a query must cost O(1) device round trips,
not O(ops).

Two contexts say which side a piece of code is on:

  * ``host_eager()`` pins jax's *default* device to the CPU backend for
    the executor tree walk (``run_plan``). Only uncommitted eager ops
    (numpy inputs) follow the default device.
  * ``device_tier()`` is entered by both device tiers around everything
    that belongs on the accelerator: the mesh tier's fragment dispatches
    (``ShardCache.get_fragment`` — their inputs are committed, sharded
    arrays, so only kernel choice needs it) and the fused segment-store
    pipeline's staging, build tables, state and program dispatches
    (``executor/pipeline.py``). It makes the accelerator the default
    device again and tells the kernel dispatch which platform the
    program runs on (``force_platform``, read back by
    ``target_platform``), so neither placement nor kernel choice depends
    on the glue's pin.

When the default backend is the CPU (tests, ``--device cpu``) there is
no second backend and both contexts do nothing.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax

__all__ = ["host_eager", "host_cpu_device", "accelerator_device",
           "device_tier", "force_platform", "target_platform",
           "device_info", "track_placement", "note_placement", "placement"]

_cpu_device: Optional[object] = None
_accel_device: Optional[object] = None
_probed = False


def _probe() -> None:
    global _cpu_device, _accel_device, _probed
    if not _probed:
        if jax.default_backend() != "cpu":
            _accel_device = jax.devices()[0]
            try:
                _cpu_device = jax.local_devices(backend="cpu")[0]
            except RuntimeError as e:
                raise RuntimeError(
                    "the host glue needs jax's cpu backend beside the "
                    f"{_accel_device.platform}: JAX_PLATFORMS must include "
                    f"cpu (it is {jax.config.jax_platforms!r})") from e
        _probed = True


def host_cpu_device():
    """The host CPU backend device, or None when the default backend is
    already CPU (tests pin jax_platforms=cpu; no second backend exists)."""
    _probe()
    return _cpu_device


def accelerator_device():
    """The device the single-chip tier stages to and runs on: the first
    device of a non-CPU default backend, None when the backend is CPU."""
    _probe()
    return _accel_device


def host_eager():
    """Context manager: eager ops go to host CPU; compiled device
    programs keep their explicit placement."""
    dev = host_cpu_device()
    if dev is None:
        return contextlib.nullcontext()
    return jax.default_device(dev)


# thread-local: the server traces statements on several worker threads,
# and one leaving its block must not un-pin another mid-trace
_forced = threading.local()


@contextlib.contextmanager
def force_platform(p: str):
    """Name the platform the enclosed device programs run on. They are
    traced while the executor glue has jax's default device pinned to
    the host CPU (``host_eager``), yet they execute where their arrays
    live — kernel choice (``target_platform``) must follow the arrays,
    not the glue's pin."""
    prev = getattr(_forced, "platform", None)
    _forced.platform = p
    try:
        yield
    finally:
        _forced.platform = prev


def target_platform() -> str:
    """Platform the *current* computation lands on: an enclosing
    force_platform() wins (both device tiers), then the pinned default
    device (host-eager glue), then the default backend. The backend name
    alone is wrong in both pinned cases."""
    forced = getattr(_forced, "platform", None)
    if forced is not None:
        return forced
    d = jax.config.jax_default_device
    if d is not None:
        return d.platform
    try:
        return jax.default_backend()
    except RuntimeError:  # pragma: no cover
        return "cpu"


@contextlib.contextmanager
def device_tier(platform: Optional[str] = None):
    """Run the enclosed staging/dispatches on the accelerator (see the
    module docstring). ``platform`` names the platform of the arrays the
    programs consume when the caller knows it (a mesh); default is the
    accelerator's."""
    dev = accelerator_device()
    if platform is None and dev is not None:
        platform = dev.platform
    with contextlib.ExitStack() as stack:
        if dev is not None:
            stack.enter_context(jax.default_device(dev))
        if platform is not None:
            stack.enter_context(force_platform(platform))
        yield


# (site, platform) -> arrays seen; None = not tracking (the default:
# note_placement then costs one test). chip_smoke.py switches it on to
# prove nothing of the served path sits on the host backend. Sites are
# the choke points where a tier stages to or gets results from a device
# ("stage", "shard", "fragment", "fused") and where a Pallas kernel
# picks its mode ("pallas"; platform "interpret" when interpreted).
_placement: Optional[dict] = None
_placement_lock = threading.Lock()


def track_placement() -> None:
    """Start recording placements (process-wide, stays on)."""
    global _placement
    with _placement_lock:
        if _placement is None:
            _placement = {}


def note_placement(site: str, tree, platform: Optional[str] = None) -> None:
    """When tracking is on, record where the arrays of ``tree`` live (or
    ``platform`` itself for an event that has no array)."""
    if _placement is None:
        return
    seen = [platform] if platform is not None else [
        next(iter(leaf.devices())).platform
        for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array)]
    with _placement_lock:
        for p in seen:
            _placement[(site, p)] = _placement.get((site, p), 0) + 1


def placement() -> dict:
    """Snapshot: {site: {platform: count}}."""
    with _placement_lock:
        items = list((_placement or {}).items())
    out: dict = {}
    for (site, p), n in items:
        out.setdefault(site, {})[p] = n
    return out


def device_info() -> dict:
    """What the boot line and the status port's /status report. Start-up
    calls it first: it initialises the backend and finds the host glue's
    CPU device, and raises when either is missing — a server must not
    come up to fail on every statement."""
    devs = jax.devices()
    _probe()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs)}
