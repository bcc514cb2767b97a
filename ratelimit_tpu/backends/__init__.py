"""RateLimitCache backends.

PEP-562 lazy, like ``models`` and ``cluster``: the device backends
import jax, while the host oracle (``memory_cache``) and the slot
tables are numpy-only — a process that only needs the oracle
(``chip_smoke.py``'s parent, which must never hold the chip) imports
them without loading the device stack.
"""

_EXPORTS = {
    "SlotTable": "slot_table",
    "CounterEngine": "engine",
    "TpuRateLimitCache": "tpu_cache",
    "MemoryRateLimitCache": "memory_cache",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from importlib import import_module

    return getattr(import_module("." + module, __name__), name)
