from .muldiv import floor_muldiv
from .prefix import per_slot_inclusive_prefix

__all__ = ["floor_muldiv", "per_slot_inclusive_prefix"]
