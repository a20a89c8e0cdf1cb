"""DeviceTopology: enumerate and describe the backend's devices.

The serving-side analog of the reference's Engine.init topology
discovery (one executor = one node, N cores = N task slots): ask the
backend what it has, report it in one serializable dict, and degrade
gracefully — a single-device backend (or one that refuses to answer,
a lost backend) still yields a usable topology object so every
placement-aware code path runs unchanged on a laptop CPU.
"""
from __future__ import annotations

from typing import Optional, Sequence


class DeviceTopology:
    """A frozen snapshot of the backend's device set.

    Args:
        devices: explicit device list (tests pass a slice of the fake
            mesh); default: ``jax.devices()``.

    Attributes:
        devices: tuple of jax Device objects (may be empty only when
            the backend could not be reached — see :meth:`detect`).
        platform / device_kind: of the first device ("unknown" when
            unreachable).
        degraded: True when detection fell back because the backend
            raised (a wedged or lost backend) — carving anything wider
            than the devices actually held raises PlacementError.
    """

    def __init__(self, devices: Optional[Sequence] = None, *,
                 degraded: bool = False):
        if devices is None:
            import jax
            devices = jax.devices()
        self.devices = tuple(devices)
        self.degraded = bool(degraded)
        if self.devices:
            self.platform = getattr(self.devices[0], "platform", "unknown")
            self.device_kind = getattr(self.devices[0], "device_kind",
                                       "unknown")
        else:
            self.platform = "unknown"
            self.device_kind = "unknown"

    @classmethod
    def detect(cls, platform: Optional[str] = None) -> "DeviceTopology":
        """Topology of the live backend; never raises.  A backend that
        fails to answer (lost mid-init) yields an empty degraded
        topology instead of wedging the caller — the serving stack then
        surfaces the real error at first dispatch, where the resilience
        layer's classification and retries own it."""
        import jax
        try:
            devs = jax.devices(platform) if platform else jax.devices()
        except Exception:  # noqa: BLE001 — backend init is the hazard here
            return cls(devices=(), degraded=True)
        return cls(devices=devs)

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def describe(self) -> dict:
        """One serializable snapshot."""
        return {
            "platform": self.platform,
            "device_kind": self.device_kind,
            "n_devices": self.n_devices,
            "degraded": self.degraded,
            "devices": [
                {"id": int(d.id),
                 "platform": getattr(d, "platform", "unknown"),
                 "process_index": int(getattr(d, "process_index", 0))}
                for d in self.devices],
        }

    def __repr__(self) -> str:
        return (f"DeviceTopology({self.n_devices}x{self.platform}"
                f"{', degraded' if self.degraded else ''})")
