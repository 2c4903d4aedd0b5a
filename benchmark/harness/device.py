"""The device as JAX reports it, and the refusal to measure without it."""

from __future__ import annotations


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def row() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require(chips: int, rehearse: bool) -> dict:
    """The device row; raises unless it is ``chips`` TPU chips (a
    rehearsal takes ``chips`` CPU devices instead)."""
    dev = row()
    want = "cpu" if rehearse else "tpu"
    if dev["platform"] != want:
        raise NoAccelerator(f"this run needs platform {want!r}; JAX found "
                            f"{dev['platform']!r} ({dev['kind']})")
    if dev["count"] < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX found "
                            f"{dev['count']}")
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, where the backend reports
    it (the CPU backend of a rehearsal reports nothing: 0). On this
    backend it does not count a program's temporaries (PERF.md, PR 22)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())
